"""Per-stage wall-clock and thread-CPU accounting for the eval hot path.

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
          raft_lock_wait  raft_apply_async / apply_replicated called
                        -> the server's raft lock held (an
                        after-the-fact report: a wait, no CPU clock)
          wal_encode    framing the plan's WAL record: wire form of
                        the payload + msgpack (server/persistence.py;
                        attrs objects, shared, rows, consts, table,
                        bytes)
          wal_write     the framed record's write + flush under the
                        log's lock, and the commit barrier that covers
                        it (attr synced: an fsync ran)
          fsm_apply     the FSM's _apply_<msg_type>: the store's
                        transaction (attr kind)
          event_publish the change events of the entry built and
                        published (attr events)
      sched_host_self  the part of sched_host no other span of the
                    eval's trace covers (union, not sum): scheduler
                    set-up, the eval-status write, thread hand-offs —
                    and a collector pause that falls between spans (a
                    worker's safepoint stops its sibling mid-eval: the
                    pause lands in whatever span is open there)

Two clocks. A span of CPU_STAGES reads the calling thread's CPU clock
(time.thread_time, CLOCK_THREAD_CPUTIME_ID) where it reads the wall
clock. Thread CPU is time ON a core, the GIL held or not: numpy, the
native codec and XLA's dispatch on the calling thread count too, so it
is an upper bound on the time the span held the GIL. wall - cpu is
time OFF the core: waiting for the GIL, a lock, the device, the disk.
A span's CPU never exceeds its wall by more than the clocks' grain
(microseconds here; a kernel that samples the thread clock at its tick
gives multiples of 10 ms, true over a window's sum and coarse for one
span). The reading rides the span's attrs as `cpu_ms`, so every tap of
the hook, the flight recorder's span, /v1/operator/trace and the
Chrome export carry it with no field of their own. add() also takes it
as `cpu=` and forwards it as ONE companion report under the name
<stage>_cpu (seconds = the CPU seconds, no attrs), which is how the
percentile reservoirs, the telemetry ring and a tap that keeps
(stage, end, seconds) alone hear it; a companion is never a span and
is in no tree.

Every other report on the hook is an INTERVAL that ends as it is
reported, and a tap may draw it as (end - seconds, end): the benchmark
names the device's idle gaps that way. A companion drawn so is the
tail of its own span (cpu <= wall), so it can stand where its stage
would have and nowhere else: an amount that is no part of a span (the
process's CPU over a sample, telemetry/collector.py) does not belong
on the hook, and sched_host, whose interval wraps every other stage's,
sends no companion (a tap that lets a wrapper name only what its
children leave knows it by its name).

Only the stages a reader asks for are in CPU_STAGES: a read of the
thread clock costs a syscall where the kernel has no vDSO for it
(5.8 us on the benchmark's machine, PERF.md). A wait measured after
the fact (queue_wait, gateway_wait, plan_queue_wait, raft_lock_wait)
and a summed report (port_assign, mask_build) have no interval on a
thread and no CPU; where the platform has no thread clock none is
read.

enable() / snapshot() / disable() give a test the summed seconds, the
summed CPU seconds and the call count of every stage reported in
between. Shares of a whole
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
          "plan_commit", "raft_lock_wait", "wal_encode", "wal_write",
          "fsm_apply", "event_publish", "sched_host_self", "broker_ack")

# the spans that read their thread's CPU clock beside the wall clock:
# the stages whose CPU a reader asks for, and not sched_host (docstring)
CPU_STAGES = frozenset({
    "job_register", "table_build", "select_prep", "kernel_pack",
    "kernel", "plan_build", "plan_verify", "plan_commit", "fsm_apply"})
# the companion report of a span's CPU seconds: <stage>_cpu
CPU_SUFFIX = "_cpu"
# the calling thread's CPU clock; None where the platform has none
thread_time: Optional[Callable[[], float]] = getattr(
    time, "thread_time", None)


def cpu_now() -> Optional[float]:
    """The calling thread's CPU clock, or None without one."""
    return thread_time() if thread_time is not None else None


def cpu_since(c0: Optional[float]) -> Optional[float]:
    """CPU seconds of the calling thread since its cpu_now() gave c0."""
    return None if c0 is None else thread_time() - c0


def cpu_ms(cpu: float) -> float:
    """CPU seconds as the `cpu_ms` a span's attrs carry."""
    return round(max(cpu, 0.0) * 1000.0, 3)


enabled = False

_l = make_lock()
_acc: Dict[str, list] = {s: [0.0, 0, 0.0] for s in STAGES}

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
                v[2] = 0.0
        _collecting = True
        enabled = True


def disable() -> None:
    global _collecting, enabled
    _collecting = False
    enabled = _collecting or _trace_on


def add(stage: str, seconds: float,
        attrs: Optional[dict] = None,
        cpu: Optional[float] = None) -> None:
    """Report `seconds` of wall clock spent in `stage`. Callers guard
    with `if stages.enabled:` so the disabled cost is one bool read.
    `attrs` ride through to the flight recorder's span (never into the
    aggregate sums). `cpu`: the reporting thread's CPU seconds over the
    same interval, where the site read them (Span does, for
    CPU_STAGES); it goes out through the hook as one more report,
    <stage>_cpu, after the stage's own."""
    if _collecting:
        with _l:
            ent = _acc.get(stage)
            if ent is None:             # unknown stage: count it anyway
                ent = _acc.setdefault(stage, [0.0, 0, 0.0])
            ent[0] += seconds
            ent[1] += 1
            if cpu is not None:
                ent[2] += cpu
    hook = _trace_hook
    if _trace_on and hook is not None:
        try:
            hook(stage, seconds, attrs)
            if cpu is not None:
                hook(stage + CPU_SUFFIX, cpu, None)
        except Exception:       # pragma: no cover — defensive
            pass


class Span:
    """One stage's interval as a context manager: the clock is read on
    entry and exit and the report (add) is made as the interval ends —
    on an exception too. A stage of CPU_STAGES reads its thread's CPU
    clock inside the wall clock's interval, so `cpu` <= `seconds` but
    for the clocks' grain, and notes it as attr `cpu_ms`; `cpu` is None
    for any other stage and where there is no thread clock. While a
    profiler session is live a jax.profiler.TraceAnnotation
    "nomad/<stage>" is held open for the interval, so the stage sits in
    the same xplane, on the profiler's clock, above the device ops it
    waited for."""

    __slots__ = ("stage", "attrs", "seconds", "cpu", "_t0", "_c0",
                 "_ann", "_live")

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
        self._c0 = cpu_now() if self.stage in CPU_STAGES else None
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self.cpu = cpu_since(self._c0)
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if self._live:
            if self.cpu is not None:
                self.attrs["cpu_ms"] = cpu_ms(self.cpu)
            add(self.stage, self.seconds, self.attrs or None, self.cpu)
        return False


class _NullSpan:
    """What span()/annotate() hand out when nothing listens."""

    __slots__ = ()
    seconds = 0.0
    cpu = None

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
    """{stage: {seconds, calls, cpu_seconds}} over every stage of
    STAGES, and any other name reported, since enable(). cpu_seconds
    sums the reports that came with a CPU reading (spans), so it is 0
    for a stage that only waits."""
    with _l:
        return {s: {"seconds": round(v[0], 4), "calls": v[1],
                    "cpu_seconds": round(v[2], 4)}
                for s, v in _acc.items() if v[1] > 0 or s in STAGES}
