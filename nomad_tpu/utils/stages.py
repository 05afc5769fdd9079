"""Per-stage wall-clock accounting for the eval hot path.

BENCH_r05 showed a 13x gap between in-kernel placement rate (163.8k/s)
and end-to-end (12.3k/s) with no way to say WHERE the host time went —
the gap had to be inferred from side channels. This module gives every
stage of the pipeline a named accumulator. The stages, as the tree the
flight recorder draws them in (trace/tracer.py STAGE_PARENTS is the
same tree; a stage's time lies inside its parent's):

  (no eval)
    restore       cold start: snapshot load + store rebuild
                  (server/persistence.py restore_into)
    wal_replay    cold start: batched WAL tail replay into the FSM
    job_register  Server.register_job / register_jobs_bulk: call ->
                  job committed and eval enqueued (attr jobs)
    snapshot_write  the background snapshot writer: serialize, fsync,
                  publish, truncate the WAL (attrs entries, bytes)
    gc_full       a full collection at a safepoint (utils/gcsafe.py),
                  its survivors frozen: every thread stands still for
                  it (attrs walked, the tracked objects it traversed;
                  frozen, the permanent generation after it)
    gc_whole_walk   the full pass that unfroze first and so walked
                  every object: the same interval as its gc_full
                  (attrs walked, reclaimed)
  eval            enqueue -> ack (the trace's root, not a stage)
    queue_wait    time the eval sat in the broker before a worker
                  dequeued it (dead time, not work)
    fence_wait    wait for the local store to reach the eval's modify
                  index (~0 on a leader; replication lag on a follower)
    table_build   NodeTableCache full builds + delta refreshes: the
                  pipelined worker refreshes BEFORE Process() (inside
                  sched_host only with worker_pipeline off, or when
                  the refresh needs a full rebuild)
      h2d           host->device transfers: the refresh's row scatter
                    (also, once per table: the first upload, inside
                    kernel_pack, and a mask park, inside select_prep)
    broker_ack    eval broker ack bookkeeping
    sched_host    one whole scheduler Process() call as seen by the
                  worker. Its direct children do not overlap, so with
                  sched_host_self they sum to it:
      reconcile     alloc-diff host phase: alloc fetch + tainted split
                    + AllocReconciler.compute + result staging (attr
                    columnar)
      table_build_private  the private full build a snapshot OLDER
                    than the cache pays under the cache lock
      select_prep   select_batch entry -> just before the dispatch:
                    masks, CSI, affinities, spread inputs, the request
        feasibility   constraint-mask production (cached per table)
          mask_build    the combined mask built: the static columns
                        (compiled, or the scalar reference) ANDed over
                        the ready-in-datacenter base, with the filtered
                        counts — only when no cache answered (reported
                        beside feasibility, as the build's seconds)
        spread_inputs the affinity column and the spreads' kernel state
                      (value codes, proposed counts, desired counts);
                      only for a group that has an affinity or a spread
        preempt       victim selection across every candidate node, for
                      the second select of an eval that found no room
                      (scheduler/preemption.py; attrs nodes_scanned,
                      victims, rows_refreshed: the rows of the victims'
                      columns re-derived to reach this table version)
          preempt_gather  the host's share: the resident columns brought
                          up to date, the slots the plan takes out, rows
                          wider than the columns on the per-node path
          preempt_kernel  the victims' program (ops/victims.py)
                          dispatched and its counters fetched
      gateway_wait  the request parked in the micro-batch gateway
                    (attrs trigger, batch, lanes)
      kernel_pack   pack_request / stacking / argument placement: what
                    the kernel window leaves out
      kernel        device dispatch through result availability AND
                    host unpack (attrs arm, n_pad, lanes, fresh)
        d2h           device->host result transfers (device_get; the
                      wall includes remaining device compute)
        kernel_expand host unpack/expand of the fetched result (on
                      the K-way arm, whose program hands back the
                      per-instance sequence: attrs phases, tail_phases
                      — those the overshoot rule held to one node —
                      and placed)
      select_finish dispatch returned -> RankedNodes returned: winner
                    materialization, resources, ports, metrics
        port_assign   the winners' NetworkIndex builds and their port
                      and bandwidth offers (_net_index_for +
                      _assign_resources), summed over the batch's
                      winners and reported once, as select_finish
                      ends (attrs ports, winners)
      plan_build    appending the placements to the plan (attr
                    placements; a placement that found no node runs
                    its fallback select / preemption search in here)
      plan_submit   plan enqueued -> result in hand, the refresh-index
                    wait included (attr refreshed)
        plan_queue_wait  enqueue -> the applier takes the plan
        plan_verify   verification against the freshest snapshot +
                      group overlay (the serialization point's read
                      half; attrs group, demoted, queue_ms)
        plan_commit   raft append/apply + quorum wait + store
                      transaction (the write half; attrs group, index)
          wal_encode    framing the plan's WAL record: wire form of
                        the payload + msgpack (server/persistence.py;
                        attrs objects, shared, rows, consts, table,
                        bytes)
      sched_host_self  the part of sched_host no other span of the
                    eval's trace covers (union, not sum): scheduler
                    set-up, the eval-status write, thread hand-offs —
                    and a collector pause that falls between spans (a
                    worker's safepoint stops its sibling mid-eval: the
                    pause lands in whatever span is open there)

enable() / snapshot() / disable() give a test the summed seconds and
the call count of every stage reported in between. Shares of a whole
are not computed here: the tree above is the flight recorder's
(STAGE_PARENTS), and the benchmark reads its spans.

The eval flight recorder (nomad_tpu/trace/, ISSUE 9) taps the same
report sites: every add() forwards (stage, seconds, attrs) through the
registered trace hook, which feeds the per-stage percentile reservoirs
and — for stages reported on the eval's own thread — emits a span onto
the thread-local current trace. The aggregate sums are untouched.
`enabled` is therefore True whenever EITHER consumer wants reports
(accumulation via enable()/disable(), tracing via set_trace_hook);
with both off the hot path pays one module-global bool check per
report site.

A site that wraps code makes one call, `with stages.span(stage,
**attrs):` — the clock, the report as the interval ends, and, while a
profiler session is live, a jax.profiler.TraceAnnotation
"nomad/<stage>" held open meanwhile, so the session shows the stage on
its own clock above the device ops. A wait measured across threads
(queue_wait, gateway_wait, plan_queue_wait) stays an after-the-fact
add().

The same stage can be reported from overlapping layers (a kernel
dispatch inside a plan-apply verify); accumulators are independent
sums, not a partition of wall clock.
"""

from __future__ import annotations

import sys
import time
from typing import Callable, Dict, Optional
from .locks import make_lock

STAGES = ("restore", "wal_replay", "job_register", "snapshot_write",
          "gc_full", "gc_whole_walk",
          "queue_wait", "fence_wait", "sched_host", "reconcile",
          "preempt", "preempt_gather", "preempt_kernel",
          "table_build", "h2d", "table_build_private",
          "select_prep", "feasibility", "mask_build", "spread_inputs",
          "gateway_wait", "kernel_pack", "kernel", "d2h",
          "kernel_expand", "select_finish", "port_assign",
          "plan_build", "plan_submit", "plan_queue_wait", "plan_verify",
          "plan_commit", "wal_encode", "sched_host_self", "broker_ack")

enabled = False

_l = make_lock()
_acc: Dict[str, list] = {s: [0.0, 0] for s in STAGES}

# the flight recorder's tap (nomad_tpu/trace/ installs it at import):
# called as hook(stage, seconds, attrs) AFTER the accumulator update
_collecting = False
_trace_hook: Optional[Callable] = None
_trace_on = False


def set_trace_hook(hook: Optional[Callable], on: bool = True) -> None:
    """Register (or disarm) the flight recorder's report tap. Arms the
    module-global `enabled` flag so the `if stages.enabled:` guards at
    every report site fire for the tracer even while accumulation is
    off."""
    global _trace_hook, _trace_on, enabled
    _trace_hook = hook
    _trace_on = bool(on and hook is not None)
    enabled = _collecting or _trace_on


def enable(reset: bool = True) -> None:
    global _collecting, enabled
    with _l:
        if reset:
            for v in _acc.values():
                v[0] = 0.0
                v[1] = 0
        _collecting = True
        enabled = True


def disable() -> None:
    global _collecting, enabled
    _collecting = False
    enabled = _collecting or _trace_on


def add(stage: str, seconds: float,
        attrs: Optional[dict] = None) -> None:
    """Report `seconds` of wall clock spent in `stage`. Callers guard
    with `if stages.enabled:` so the disabled cost is one bool read.
    `attrs` ride through to the flight recorder's span (never into the
    aggregate sums)."""
    if _collecting:
        with _l:
            ent = _acc.get(stage)
            if ent is None:             # unknown stage: count it anyway
                ent = _acc.setdefault(stage, [0.0, 0])
            ent[0] += seconds
            ent[1] += 1
    hook = _trace_hook
    if _trace_on and hook is not None:
        try:
            hook(stage, seconds, attrs)
        except Exception:       # pragma: no cover — defensive
            pass


class Span:
    """One stage's interval as a context manager: the clock is read on
    entry and exit and the report (add) is made as the interval ends —
    on an exception too. While a profiler session is live a
    jax.profiler.TraceAnnotation "nomad/<stage>" is held open for the
    interval, so the stage sits in the same xplane, on the profiler's
    clock, above the device ops it waited for."""

    __slots__ = ("stage", "attrs", "seconds", "_t0", "_ann", "_live")

    def __init__(self, stage: str, attrs: dict):
        self.stage = stage
        self.attrs = attrs
        self._live = True

    def note(self, **attrs) -> None:
        """Attributes known only once the work has run."""
        self.attrs.update(attrs)

    def cancel(self) -> None:
        """This interval turned out not to be an occurrence of the
        stage (a table refresh with nothing to apply): no report."""
        self._live = False

    def __enter__(self) -> "Span":
        live = _profiling
        if live is not None and live():
            self._ann = _trace_annotation("nomad/" + self.stage)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if self._live:
            add(self.stage, self.seconds, self.attrs or None)
        return False


class _NullSpan:
    """What span()/annotate() hand out when nothing listens."""

    __slots__ = ()
    seconds = 0.0

    def note(self, **attrs) -> None:
        pass

    def cancel(self) -> None:
        pass

    def onto(self, tr, **extra) -> None:     # trace.span's
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, et, ev, tb) -> bool:
        return False


NULL_SPAN = _NullSpan()
# jax.profiler.TraceAnnotation and its is_enabled (a profiler session
# is live), bound once jax is loaded. jax is never imported from here:
# a process that has not loaded it has no session to be seen in
_trace_annotation = None
_profiling: Optional[Callable] = None


def _bind_profiler() -> None:
    global _trace_annotation, _profiling
    if "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
        _profiling = TraceAnnotation.is_enabled


def annotate(name: str, **attrs):
    """A jax.profiler.TraceAnnotation "nomad/<name>" while a report
    site is live and a profiler session runs, else a no-op."""
    if not enabled:
        return NULL_SPAN
    if _profiling is None:
        _bind_profiler()
    if _profiling is None or not _profiling():
        return NULL_SPAN
    return _trace_annotation("nomad/" + name, **attrs)


def span(stage: str, **attrs):
    """`with stages.span("kernel", arm=...):` — the one call a report
    site that wraps code makes. With nothing listening it costs the
    one bool read the `if stages.enabled:` guards cost."""
    if not enabled:
        return NULL_SPAN
    if _profiling is None:
        _bind_profiler()
    return Span(stage, attrs)


def snapshot() -> Dict[str, dict]:
    """{stage: {seconds, calls}} over every stage of STAGES, and any
    other name reported, since enable()."""
    with _l:
        return {s: {"seconds": round(v[0], 4), "calls": v[1]}
                for s, v in _acc.items() if v[1] > 0 or s in STAGES}
