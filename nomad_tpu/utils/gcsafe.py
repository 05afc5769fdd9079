"""GC safepoints: keep CPython collector pauses out of eval latency.

With a multi-million-object resident state (C2M: 2M allocs), automatic
collections land mid-eval and put 30-60 ms pauses into scheduling
latency. This controller moves them to explicit safe points (between
evals in the worker loop): automatic collection is disabled while any
participant is registered, and participants call `safepoint()` after
each unit of work — a young-generation collect that is process-level
coordinated (one collector at a time, rate-limited) so N workers don't
run N collections per eval. A collect still holds the GIL while
sibling threads run — inherent to CPython — but rare, rate-limited
collections of the young generations are tens of microseconds against
the tens of milliseconds the automatic collector costs when it decides
to walk a C2M-sized heap mid-eval.

Used by server/worker.py (ServerConfig.gc_safepoints, on in the CLI
agent) and mirrored by the C2M benchmark so it measures the regime the
agent actually runs.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from .locks import make_lock

_lock = make_lock()
_participants = 0
_was_enabled = True
_last_collect = 0.0
_last_full_collect = 0.0

# floor between coordinated young-gen collects; more frequent adds no
# latency benefit and multiplies GIL stalls across workers
MIN_COLLECT_INTERVAL_S = 0.05

# gen-2 budget: a FULL collection runs at a safepoint at least this
# often, so unreachable cycles can't accumulate for the lifetime of
# the regime (the young-gen-only policy deferred gen-2 indefinitely
# while workers were busy). After freeze_steady_state() the full pass
# skips the frozen substrate, so it stays cheap even at C2M scale.
FULL_COLLECT_INTERVAL_S = 10.0


# the collections run here, as (start, end) on time.monotonic: every
# thread stands still for one (it holds the GIL), so an eval that
# spans one reads that much longer without the host being any busier.
# The governor's latency gauge takes them out (server/worker.py): a
# 1.6 s full collection every 10 s inside 1-2% of the evals is a p99
# over its one-second watermark, and the shed valve it opens parks
# every new eval for seconds (PR 27)
PAUSES: deque = deque(maxlen=1024)
MIN_PAUSE_S = 0.001


def pause_overlap_s(t0: float, t1: float) -> float:
    """Seconds of [t0, t1] (time.monotonic) spent inside collections
    that safepoint() ran."""
    total = 0.0
    for start, end in reversed(list(PAUSES)):   # newest first, in order
        if end <= t0:
            break
        if start < t1:
            total += min(end, t1) - max(start, t0)
    return total


def enter() -> None:
    """Register a participant; disables automatic collection on the
    first one (remembering whether it was enabled)."""
    global _participants, _was_enabled
    with _lock:
        _participants += 1
        if _participants == 1:
            _was_enabled = gc.isenabled()
            gc.disable()


def exit_() -> None:
    """Deregister; the last one out restores the collector state."""
    global _participants
    with _lock:
        if _participants > 0:
            _participants -= 1
            if _participants == 0 and _was_enabled:
                gc.enable()


def safepoint() -> None:
    """Collect at a safe point — at most one collector at a time,
    rate-limited process-wide. Young generations collect on the fast
    cadence; a FULL collection runs on the FULL_COLLECT_INTERVAL_S
    budget so gen-2 garbage stays bounded over long runs. Callers that
    lose the race simply skip (a sibling just collected)."""
    global _last_collect, _last_full_collect
    now = time.monotonic()
    if now - _last_collect < MIN_COLLECT_INTERVAL_S:
        return
    if not _lock.acquire(blocking=False):
        return
    try:
        if now - _last_collect < MIN_COLLECT_INTERVAL_S:
            return
        _last_collect = now
        if now - _last_full_collect >= FULL_COLLECT_INTERVAL_S:
            _last_full_collect = now
            gc.collect()
        else:
            gc.collect(1)
        end = time.monotonic()
        if end - now >= MIN_PAUSE_S:
            PAUSES.append((now, end))
    finally:
        _lock.release()


def unfreeze_steady_state() -> None:
    """Return the frozen substrate to the collectable heap (gc.unfreeze)
    — pair with freeze_steady_state when the substrate's lifetime ends
    (e.g. a benchmark tearing down its server)."""
    gc.unfreeze()


def freeze_steady_state() -> None:
    """Move the current live heap to the permanent generation
    (gc.freeze) after reclaiming what's already dead. For a process
    whose resident state is large and long-lived (a C2M server: 2M
    alloc objects), this takes the substrate out of every future
    collection — the gen-2 budget above then costs microseconds, not
    seconds. Call once the steady-state substrate is loaded."""
    gc.collect()
    gc.freeze()


class safepoints:
    """Context manager: `with gcsafe.safepoints(): ... gcsafe.safepoint()`"""

    def __enter__(self):
        enter()
        return self

    def __exit__(self, *exc):
        exit_()
        return False
