"""The reduction from a profiler trace to device metrics.

Works on a neutral list of events — dicts with plane, line, name,
start_s, dur_s — so that it can be checked on a small recorded trace
(tests/benchmark/recorded_trace.json); load_xplane() makes that list
from the .xplane.pb the JAX profiler writes.

  busy      per device plane, the union of the intervals in which an op
            ran; averaged over the planes
  idle      1 - busy / window
  kernels   the device time of the placement programs: the events of
            the "XLA Modules" line whose name matches lib/kernels.json
  gaps      the idle intervals, longest first, each named by the host
            stage that covers most of it (a wait only where nothing
            else covers any)
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "benchmark_window_mark"
_KERNELS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernels.json")

Interval = Tuple[float, float]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load_xplane(path: str) -> List[dict]:
    """Device-plane events, and the harness's own mark from the host
    planes, as neutral dicts (times in seconds on the profiler's clock)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name == MARK:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_s": ev.start_ns / 1e9,
                                "dur_s": ev.duration_ns / 1e9})
    return out


def clock_offset(events: Sequence[dict], mark_host_s: float) -> Optional[float]:
    """Seconds to ADD to a profiler time to get the host's clock, from
    the mark the harness wrote at a host time it knows."""
    marks = [e for e in events if e["name"] == MARK]
    if not marks:
        return None
    return mark_host_s - min(e["start_s"] for e in marks)


def _device_events(events: Iterable[dict], line: str) -> Dict[str, List[dict]]:
    by_plane: Dict[str, List[dict]] = {}
    for e in events:
        if is_device_plane(e["plane"]) and e["line"] == line:
            by_plane.setdefault(e["plane"], []).append(e)
    return by_plane


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def busy_s(events: Sequence[dict], t0: float, t1: float) -> float:
    """Seconds in [t0, t1) in which an op ran on the device, averaged
    over the device planes that show any."""
    per_plane = []
    for evs in _device_events(events, OPS_LINE).values():
        merged = union(clip(((e["start_s"], e["start_s"] + e["dur_s"])
                             for e in evs), t0, t1))
        per_plane.append(sum(b - a for a, b in merged))
    return sum(per_plane) / len(per_plane) if per_plane else 0.0


def idle_share(busy: float, window_s: float) -> float:
    return 1.0 - busy / window_s


def kernel_patterns() -> dict:
    with open(_KERNELS) as f:
        return json.load(f)


def _matches(name: str, patterns: Sequence[str]) -> bool:
    return any(re.search(p, name) for p in patterns)


def is_kernel(module: dict, ops: Sequence[dict], starts: Sequence[float],
              patterns: dict) -> bool:
    """Is this run of a program a placement kernel? By its name; or,
    for a program with no name of its own (jit_fn), by what runs inside
    it: `ops` are the plane's op events sorted by start, `starts` their
    start times."""
    if _matches(module["name"], patterns["placement_programs"]):
        return True
    if not _matches(module["name"], patterns.get("anonymous_programs", [])):
        return False
    lo = bisect.bisect_left(starts, module["start_s"])
    hi = bisect.bisect_left(starts, module["start_s"] + module["dur_s"])
    return any(_matches(o["name"], patterns["anonymous_kernel_ops"])
               for o in ops[lo:hi])


def kernel_s(events: Sequence[dict], t0: float, t1: float,
             patterns: dict) -> Tuple[float, int]:
    """(device seconds, runs) of the placement programs that START in
    [t0, t1), from the modules line, averaged over device planes."""
    per_plane = []
    all_ops = _device_events(events, OPS_LINE)
    for plane, evs in _device_events(events, MODULES_LINE).items():
        ops = sorted(all_ops.get(plane, []), key=lambda e: e["start_s"])
        starts = [o["start_s"] for o in ops]
        mine = [e for e in evs if t0 <= e["start_s"] < t1
                and is_kernel(e, ops, starts, patterns)]
        per_plane.append((sum(e["dur_s"] for e in mine), len(mine)))
    if not per_plane:
        return 0.0, 0
    return (sum(s for s, _n in per_plane) / len(per_plane),
            max(n for _s, n in per_plane))


def top_ops(events: Sequence[dict], t0: float, t1: float,
            n: int = 10) -> List[list]:
    """The ops that took most device time, as [module/op, seconds]."""
    total: Dict[str, float] = {}
    for plane, ops in _device_events(events, OPS_LINE).items():
        modules = sorted(_device_events(events, MODULES_LINE)
                         .get(plane, []), key=lambda e: e["start_s"])
        starts = [m["start_s"] for m in modules]
        for e in ops:
            if not t0 <= e["start_s"] < t1:
                continue
            i = bisect.bisect_right(starts, e["start_s"]) - 1
            module = ""
            if i >= 0 and e["start_s"] < modules[i]["start_s"] \
                    + modules[i]["dur_s"]:
                module = modules[i]["name"].split("(")[0]
            op = e["name"].split(" = ")[0].lstrip("%")
            key = f"{module}/{op}" if module else op
            total[key] = total.get(key, 0.0) + e["dur_s"]
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def idle_gaps(events: Sequence[dict], t0: float, t1: float) -> List[Interval]:
    """The intervals of [t0, t1) in which no op ran on any device."""
    busy = union(clip(((e["start_s"], e["start_s"] + e["dur_s"])
                       for evs in _device_events(events, OPS_LINE).values()
                       for e in evs), t0, t1))
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


# an eval (or a plan) waiting is not the host working: a wait names a
# gap only where no working stage covers any of it
WAITS = ("queue_wait", "gateway_wait", "fence_wait", "plan_queue_wait")


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host stage that covers most of `gap` ("idle" if none does);
    `spans` are (stage, start, end) on the gap's clock. A stage that
    wraps others (sched_host) only names what its children leave, and a
    wait (WAITS) only a gap that nothing else covers."""
    cover: Dict[str, float] = {}
    for stage, a, b in spans:
        lo, hi = max(a, gap[0]), min(b, gap[1])
        if hi > lo:
            cover[stage] = cover.get(stage, 0.0) + hi - lo
    if not cover:
        return "idle"
    working = {s: v for s, v in cover.items() if s not in WAITS}
    if working:
        cover = working
    inner = {s: v for s, v in cover.items() if s != "sched_host"}
    if inner and max(inner.values()) >= 0.5 * cover.get("sched_host", 0.0):
        cover = inner
    return max(cover.items(), key=lambda kv: kv[1])[0]


def longest_gaps(events: Sequence[dict], t0: float, t1: float,
                 spans: Sequence[Tuple[str, float, float]],
                 n: int = 10) -> List[list]:
    gaps = sorted(idle_gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(g, spans), g[1] - g[0]] for g in gaps]


def reduce(events: Sequence[dict], t0: float, t1: float,
           spans: Sequence[Tuple[str, float, float]] = ()) -> dict:
    """Everything the readers take from a trace of the window [t0, t1)
    (profiler clock; `spans` already moved onto it)."""
    busy = busy_s(events, t0, t1)
    k_s, k_runs = kernel_s(events, t0, t1, kernel_patterns())
    return {
        "window_s": t1 - t0, "busy_s": busy,
        "idle_share": idle_share(busy, t1 - t0),
        "kernel_s": k_s, "kernel_runs": k_runs,
        "device_ops": top_ops(events, t0, t1),
        "idle_gaps": longest_gaps(events, t0, t1, spans),
    }
