"""What one placement dispatch has to move, from its shapes alone.

The select kernels are bound by their sequential steps, not by
arithmetic, so the roofline they are held against is the memory one:
the least bytes ANY implementation must move for one eval — the
node-table columns the ranking reads, once; the per-eval columns the
host ships; the placements written back — over the chip's peak
bandwidth. It is a function of shapes and never of the arm that ran: an
arm that re-reads the table every step moves more than this and reads a
lower share, which is the point.
"""

from __future__ import annotations

import json
import os

F32 = I32 = 4
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def pad_n(n: int) -> int:
    """The node axis as the kernels pad it: powers of two from 8."""
    p = 8
    while p < n:
        p *= 2
    return p


def select_floor_bytes(n_nodes: int, dims: int, count: int,
                       spreads: int = 0, affinities: int = 0,
                       ports: int = 0, preempt_candidates: int = 0) -> int:
    """Least bytes one eval of `count` placements moves on the device.
    `preempt_candidates`: the resident allocations a victim selection
    may take (0 where the eval cannot preempt) — each one's resources,
    priority and node row read once, and a score per node written."""
    n = pad_n(n_nodes)
    table = 2 * n * dims * F32            # capacity and used, read once
    per_eval = n * 1                      # feasibility mask
    per_eval += 2 * n * I32               # job / task-group collisions
    per_eval += n * F32 if affinities else 0
    per_eval += spreads * n * I32         # a value id per node and spread
    per_eval += n * I32 if ports else 0   # free dynamic ports per node
    shipped = dims * F32 + I32            # the ask and the count
    out = count * (I32 + F32)             # node row and score, per placement
    victims = 0
    if preempt_candidates:
        victims = preempt_candidates * (dims * F32 + 2 * I32) + n * F32
    return table + per_eval + shipped + out + victims


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {_PEAKS} with its source")
    return table[device_kind]


def roofline_share_pct(total_bytes: float, device_seconds: float,
                       device_kind: str) -> float:
    """Least time at peak bandwidth over the kernels' device time, %."""
    least_s = total_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / device_seconds
