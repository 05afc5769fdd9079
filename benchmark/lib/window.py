"""Window arithmetic: what a rate and a percentile are, in one place.

A rate is all the work completed in [0, seconds) over `seconds`; a
percentile is over every request due in the window, the failed ones
among them at the longest wait they were given. No medians of chunks.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile (the smallest value with at least q of the
    sample at or below it); None of an empty sample."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def latencies_ms(due_s: Sequence[float], done_s: Sequence[Optional[float]],
                 give_up_s: float) -> Tuple[List[float], int]:
    """Latency of each request from when it was DUE; one that never
    completed waits until `give_up_s` and counts as failed."""
    out, failed = [], 0
    for due, done in zip(due_s, done_s):
        if done is None:
            failed += 1
            done = give_up_s
        out.append((done - due) * 1000.0)
    return out, failed


def rate_per_s(completions: Iterable[Tuple[float, float]],
               seconds: float) -> float:
    """Work per second: `completions` are (time, amount) pairs, and
    those with 0 <= time < seconds count, over `seconds`."""
    return sum(amount for t, amount in completions
               if 0.0 <= t < seconds) / seconds


def in_window(times: Sequence[float], values: Sequence[float],
              seconds: float) -> List[float]:
    return [v for t, v in zip(times, values) if 0.0 <= t < seconds]
