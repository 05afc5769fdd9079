"""GC safepoints: keep CPython collector pauses out of eval latency,
and keep them short by walking only what is new.

A collection stops every thread of the process (it holds the GIL). On
a store of 10,000 nodes and 400,000 allocations, 1.9M gc-tracked
objects, one full collection is 1.8 s; run every 10 s it was 15-19% of
a benchmark window, in every window (PERF_LEDGER.jsonl, PR 24-27).
This controller does two things about that.

**Safepoints.** Automatic collection is disabled while any participant
is registered, and participants call `safepoint()` after each unit of
work (between evals in the worker loop): a young-generation collect
that is process-level coordinated (one collector at a time,
rate-limited) so N workers don't run N collections per eval, and a
FULL collection every FULL_COLLECT_INTERVAL_S.

**Generations over the resident state.** What survives a full
collection is long-lived by observation (nodes, allocations, HAMT
spines, column indexes, compiled programs), whoever loaded it: a
restore, a WAL replay, ten minutes of placements. The full pass moves
its survivors to the permanent generation (`gc.freeze()`), so the next
one walks only what was allocated since: ~0.1 s for 10 s of batch
placements instead of 1.8 s. A frozen object that dies is still freed
by its reference count; only a CYCLE that becomes garbage after it was
frozen is out of the collector's reach, so when the permanent
generation has grown by WHOLE_WALK_GROWTH since everything was last
walked, the full pass is a **whole walk**: `gc.unfreeze()` first, so
it walks every object. The last participant out unfreezes: a process
that leaves the regime is as it was found.

Every full pass reports a stage `gc_full` (attrs `walked`, `frozen`)
and a whole walk also `gc_whole_walk` (attrs `walked`, `reclaimed`)
through utils/stages.py; PAUSES holds every collection's interval.

Used by server/worker.py (ServerConfig.gc_safepoints, on in the CLI
agent and in the benchmark's configurations).
"""

from __future__ import annotations

import gc
import time
from collections import deque
from . import stages
from .locks import make_lock

_lock = make_lock()
_participants = 0
_was_enabled = True
_last_collect = 0.0
_last_full_collect = 0.0
# objects moved to the permanent generation since the last whole walk
# (that walk's survivors included), and that walk's survivors alone.
# Summed as they are frozen: gc.get_freeze_count() walks the whole
# permanent generation (50 ms per million objects), which a pass that
# exists to walk only the new ones cannot afford. One that has since
# been freed by its reference count is still in the sum, so it reads
# high by the churn; it is read back from the collector when it says a
# whole walk is due (_whole_walk_due), before that walk is paid for.
_frozen = 0
_whole_walk_base = 0

# floor between coordinated young-gen collects; more frequent adds no
# latency benefit and multiplies GIL stalls across workers
MIN_COLLECT_INTERVAL_S = 0.05

# gen-2 budget: a FULL collection runs at a safepoint at least this
# often, so unreachable cycles can't accumulate for the lifetime of
# the regime (the young-gen-only policy deferred gen-2 indefinitely
# while workers were busy). Its survivors are frozen, so it walks what
# was allocated since the last one.
FULL_COLLECT_INTERVAL_S = 10.0

# a full pass walks EVERY object again (unfreeze first) when it would
# otherwise leave this many times the survivors of the last whole walk
# frozen. With a factor f the walks fall at heap sizes b, f*b, f^2*b,
# ...: over a process's life they traverse f/(f-1) times its final
# heap, and between two of them at most (f-1) times the heap of the
# first can be cyclic garbage that froze before it died. The two
# multipliers sum to f/(f-1) + (f-1), least at f = 2: every long-lived
# object is walked twice over the life of the process (amortised,
# against once every 10 s), and frozen garbage never exceeds the live
# heap. A property of the rule, not of a heap or a window: not a knob.
WHOLE_WALK_GROWTH = 2


# the collections run here, as (start, end) on time.monotonic: every
# thread stands still for one (it holds the GIL), so an eval that
# spans one reads that much longer without the host being any busier.
# The governor's latency gauge takes them out (server/worker.py): a
# whole walk of seconds inside one eval is a p99 over its one-second
# watermark, and the shed valve it opens parks every new eval for
# seconds (PR 27)
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
    """Deregister; the last one out restores the collector state and
    returns what the regime froze to the collectable heap."""
    global _participants
    with _lock:
        if _participants > 0:
            _participants -= 1
            if _participants == 0:
                _unfreeze()
                if _was_enabled:
                    gc.enable()


def _unfreeze() -> None:
    """Everything back to the collectable heap: the next full pass
    walks it all, and the counts start over from what it finds."""
    global _frozen, _whole_walk_base
    gc.unfreeze()
    _frozen = _whole_walk_base = 0


def _whole_walk_due(young: int) -> bool:
    """Would freezing `young` more objects leave the permanent
    generation WHOLE_WALK_GROWTH times what the last whole walk
    found? Then this pass is the next one (so a fleet loaded after a
    first pass over an empty store is walked once, not as the young
    generation and again ten seconds later). The regime's first full
    pass always is one: nothing is frozen yet."""
    global _frozen
    limit = WHOLE_WALK_GROWTH * _whole_walk_base
    if _frozen + young < limit:
        return False
    _frozen = gc.get_freeze_count()     # less what has been freed since
    return _frozen + young >= limit


def _full_pass(whole: bool = False) -> None:
    """One full collection with its survivors frozen, under _lock: of
    what was allocated since the last one, or, when a whole walk is
    due or asked for (`whole`), of every object, the permanent
    generation unfrozen first."""
    global _frozen, _whole_walk_base
    with stages.span("gc_full") as sp:
        walked = len(gc.get_objects())      # the permanent ones are not in it
        if whole or _whole_walk_due(walked):
            whole = True
            walked += gc.get_freeze_count()
            _unfreeze()
        reclaimed = gc.collect()
        gc.freeze()
        _frozen += walked - reclaimed
        sp.note(walked=walked, frozen=_frozen)
    if whole:
        _whole_walk_base = _frozen
        if stages.enabled:
            stages.add("gc_whole_walk", sp.seconds,
                       {"walked": walked, "reclaimed": reclaimed})


def safepoint() -> None:
    """Collect at a safe point — at most one collector at a time,
    rate-limited process-wide. Young generations collect on the fast
    cadence; a FULL collection runs on the FULL_COLLECT_INTERVAL_S
    budget so gen-2 garbage stays bounded over long runs, freezes its
    survivors, and is a whole walk when one is due. Callers that lose
    the race simply skip (a sibling just collected)."""
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
            _full_pass()
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
    (e.g. a benchmark tearing down its server). Inside the regime the
    next full pass is then a whole walk and freezes it again."""
    with _lock:
        _unfreeze()


def freeze_steady_state() -> None:
    """A whole walk now, without waiting for a safepoint's budget:
    reclaim what is already dead and move the live heap to the
    permanent generation. What safepoint() does by itself one interval
    after a load; for a harness whose first timed eval must not hold
    that walk (tests/test_gcsafe.py is the one caller: ROADMAP D0)."""
    with _lock:
        _full_pass(whole=True)


class safepoints:
    """Context manager: `with gcsafe.safepoints(): ... gcsafe.safepoint()`"""

    def __enter__(self):
        enter()
        return self

    def __exit__(self, *exc):
        exit_()
        return False
