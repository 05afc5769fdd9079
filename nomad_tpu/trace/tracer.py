"""Eval flight recorder: always-on per-eval span tracing (ISSUE 9).

Every ROADMAP validation item is a "re-run on real TPU and confirm X"
task, but the only attribution surfaces were aggregate sums
(`utils/stages.py` stage_breakdown) and governor gauges — neither can
answer *why a specific p99 eval was slow* (gateway park? group-commit
conflict retry? cold table rebuild? fresh XLA trace?). This module is
the Dapper-style answer: one span tree per eval, always on, cheap
enough for the C2M soak.

  EvalTrace   one eval's span tree: broker enqueue -> dequeue
              (queue_wait) -> gateway park/fire (batch id + lanes +
              trigger) -> reconcile -> kernel dispatch (arm, n_pad,
              fresh-trace flag) -> plan verify (group size, conflict /
              demotion) -> group commit -> broker ack. Spans are plain
              dicts (JSON-ready); the tree is encoded by a static
              parent map (sched_host wraps the per-dispatch stages,
              everything else hangs off the eval root).
  Tracer      the per-server recorder: a byte-bounded ring of
              completed traces (`trace_ring_bytes`), a pinned
              tail-exemplar set (`trace_exemplar_slots`, promotion at
              `trace_exemplar_threshold_pct` percent of the
              governor-tracked full-latency p99), and per-stage
              duration reservoirs behind stage_percentiles() — the
              p50/p95/p99 breakdown the bench artifact records.

Collection paths:

  ambient     utils/stages.py report sites forward every (stage,
              seconds, attrs) through set_trace_hook — the aggregate
              sums stay identical, and sites that run on the EVAL's
              own thread (AMBIENT_STAGES: sched_host and everything
              the worker does inside it) land as spans on the
              thread-local current trace(s). A batched gateway fire
              installs the union of its lanes' traces, so the one
              shared kernel / d2h span fans out to every lane. The
              hook also feeds the percentile reservoirs for EVERY
              stage, traced context or not.
  explicit    stages measured across threads name their traces: the
              gateway reports each parked request's wait onto the
              request's CAPTURED trace (report(): the firing thread is
              some other eval's), and the plan applier / committer
              time queue wait, verify and commit onto the trace the
              submitting worker stamped onto the plan (span()).

While a profiler session is live every span is also a
jax.profiler.TraceAnnotation "nomad/<stage>" for as long as it is open
(utils/stages.py span), and the worker holds "nomad/eval" round each
eval: the same tree sits in the xplane, on the profiler's clock, above
the device ops.

Exports three ways: `/v1/operator/trace` (JSON), `nomad operator
trace [-exemplars] [-o chrome]` (Chrome trace-event JSON, loadable in
Perfetto/chrome://tracing — one track per worker / gateway / applier
so overlap is visible), and the `operator debug` capture bundle.

`NOMAD_TPU_TRACE=0` is the kill switch: begin() returns None, the
stages hook disarms, and the report sites degenerate to the pre-trace
one-bool-read cost.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

from ..utils import stages
from ..utils.locks import make_lock

TRACE_ENV = "NOMAD_TPU_TRACE"

DEFAULT_RING_BYTES = 4 << 20
DEFAULT_EXEMPLAR_SLOTS = 8
DEFAULT_THRESHOLD_PCT = 100.0

# ring accounting is an ESTIMATE (sizing every dict would cost more
# than the spans being sized): per-trace overhead + per-span cost,
# calibrated generously so the configured byte budget is a ceiling
TRACE_EST_BYTES = 256
SPAN_EST_BYTES = 176
# a runaway eval (retry loop) must not grow one trace without bound
MAX_SPANS_PER_TRACE = 512
# per-stage duration reservoir behind stage_percentiles()
STAGE_RESERVOIR = 2048
# the tracer's own full-latency reservoir: the promotion fallback when
# no governor threshold_fn is wired (standalone benches, tests);
# its p99 is re-sorted only every OWN_P99_EVERY completions
OWN_LATENCY_RESERVOIR = 512
OWN_P99_EVERY = 32

# static span-tree encoding, the same tree utils/stages.py's docstring
# draws: deterministic (testable) without runtime stack bookkeeping.
# The map has to tell the truth — a span lies inside its parent's
# interval, and the direct children of sched_host do not overlap, so
# that per eval they and sched_host_self sum to sched_host
# (tests/test_trace.py checks both on a served eval). Where a stage has
# more than one site the map gives the served path's steady state:
# table_build under eval (the pipelined worker refreshes the table
# BEFORE Process(); only worker_pipeline=False or a refused full
# rebuild runs it inside sched_host), h2d under table_build (the
# refresh's row scatter; a first upload, attr upload, sits in
# kernel_pack and a mask park, attr mask_park, in select_prep, once per
# table). Two things the sum assumes: solo dispatches (a batched
# gateway fire reports kernel_pack / kernel once and lands them whole
# on every lane's trace), and every placement finding a node (the
# fallback select and the preemption search of one that did not run
# inside plan_build's interval, and count twice)
STAGE_PARENTS: Dict[str, Optional[str]] = {
    "restore": None, "wal_replay": None, "job_register": None,
    "snapshot_write": None, "gc_full": None, "gc_whole_walk": None,
    "queue_wait": "eval", "fence_wait": "eval", "table_build": "eval",
    "h2d": "table_build", "sched_host": "eval", "broker_ack": "eval",
    "reconcile": "sched_host", "preempt": "select_prep",
    "preempt_gather": "preempt", "preempt_kernel": "preempt",
    "table_build_private": "sched_host",
    "select_prep": "sched_host", "feasibility": "select_prep",
    "mask_build": "feasibility", "spread_inputs": "select_prep",
    "gateway_wait": "sched_host", "kernel_pack": "sched_host",
    "kernel": "sched_host", "d2h": "kernel", "kernel_expand": "kernel",
    "select_finish": "sched_host", "port_assign": "select_finish",
    "plan_build": "sched_host",
    "plan_submit": "sched_host", "plan_queue_wait": "plan_submit",
    "plan_verify": "plan_submit", "plan_commit": "plan_submit",
    "raft_lock_wait": "plan_commit", "wal_encode": "plan_commit",
    "wal_write": "plan_commit", "fsm_apply": "plan_commit",
    "event_publish": "plan_commit", "sched_host_self": "sched_host",
}

# stages whose report site runs on the eval's OWN thread — or, for the
# device stages of a batched gateway fire, under the union context of
# the fire's lanes — so the thread-local context attributes them. The
# rest (queue_wait, gateway_wait, plan_queue_wait, plan_verify,
# plan_commit) are measured across threads and name their traces
# through span()/report() below; the ambient hook emitting them too
# would double-count or mis-attribute them. plan_commit's children
# (raft_lock_wait, wal_encode, wal_write, fsm_apply, event_publish) are
# reported deep under the applier's raft append (server/core.py,
# server/persistence.py), which knows no plan: the applier installs the
# committing plans' traces as its own thread's context round that call.
# A span's CPU companion (<stage>_cpu, utils/stages.py) is in neither
# map: it feeds the reservoirs and is never a span.
AMBIENT_STAGES = frozenset({
    "restore", "wal_replay", "fence_wait", "sched_host", "reconcile",
    "preempt", "preempt_gather", "preempt_kernel",
    "table_build", "h2d", "table_build_private",
    "select_prep", "feasibility", "mask_build", "spread_inputs",
    "kernel_pack", "kernel", "d2h", "kernel_expand", "select_finish",
    "port_assign", "plan_build", "plan_submit",
    "raft_lock_wait", "wal_encode", "wal_write", "fsm_apply",
    "event_publish", "sched_host_self", "broker_ack",
})


def _env_enabled() -> bool:
    return os.environ.get(TRACE_ENV, "1") not in ("0", "off", "no")


# -- thread-local span context ----------------------------------------
# holds (traces tuple, track override): the worker loop installs its
# eval's trace around Process(); the gateway installs the UNION of a
# batched fire's lane traces (with track "gateway") so shared device
# spans fan out to every lane
_tls = threading.local()
_NO_CTX: Tuple[Tuple, Optional[str]] = ((), None)


def current_all() -> Tuple:
    return getattr(_tls, "ctx", _NO_CTX)[0]


def current():
    traces = current_all()
    return traces[0] if traces else None


@contextmanager
def use(trace, track: Optional[str] = None):
    """Install one trace (or None for a no-op) as this thread's span
    context for the duration of the block."""
    with use_many((trace,), track):
        yield


@contextmanager
def use_many(traces, track: Optional[str] = None):
    """The same for several traces (None among them counts for
    nothing): one report lands on each."""
    prev = getattr(_tls, "ctx", _NO_CTX)
    _tls.ctx = (tuple(t for t in traces if t is not None), track)
    try:
        yield
    finally:
        _tls.ctx = prev


class EvalTrace:
    """One eval's span tree. Recording a span is one tuple append
    (lock-free: CPython list append is atomic, and the concurrent
    emitters — worker thread, gateway firing thread, applier, committer
    — only ever append); the span dicts are built on reading."""

    __slots__ = ("eval_id", "job_id", "namespace", "eval_type", "track",
                 "wall0", "mono0", "_raw", "total_ms", "status",
                 "gauges", "truncated")

    def __init__(self, eval_id: str, job_id: str, namespace: str,
                 eval_type: str, track: str, mono0: float, wall0: float):
        self.eval_id = eval_id
        self.job_id = job_id
        self.namespace = namespace
        self.eval_type = eval_type
        self.track = track
        self.mono0 = mono0          # monotonic anchor (broker enqueue)
        self.wall0 = wall0          # wall anchor for export timestamps
        # (name, end_mono, dur_s, track, attrs)
        self._raw: List[tuple] = []
        self.total_ms = 0.0
        self.status = "open"
        self.gauges: Optional[dict] = None   # set on exemplar promotion
        self.truncated = 0

    def add_span(self, name: str, dur_s: float,
                 end_mono: Optional[float] = None,
                 track: Optional[str] = None,
                 attrs: Optional[dict] = None) -> None:
        raw = self._raw
        if len(raw) >= MAX_SPANS_PER_TRACE:
            self.truncated += 1
            return
        raw.append((name, time.monotonic() if end_mono is None
                    else end_mono, dur_s, track, attrs))
        tracer.stats["spans"] += 1      # racy inc; stats, not billing

    @property
    def spans(self) -> List[dict]:
        out = []
        for name, end, dur_s, track, attrs in list(self._raw):
            dur_s = max(dur_s, 0.0)
            span = {"name": name,
                    "t0_ms": round(max(0.0, end - dur_s - self.mono0)
                                   * 1000.0, 3),
                    "dur_ms": round(dur_s * 1000.0, 3),
                    "track": track or self.track,
                    "parent": STAGE_PARENTS.get(name, "eval")}
            if attrs:
                span["attrs"] = attrs
            out.append(span)
        return out

    def to_dict(self) -> dict:
        out = {"eval_id": self.eval_id, "job_id": self.job_id,
               "namespace": self.namespace, "type": self.eval_type,
               "track": self.track, "start": round(self.wall0, 6),
               "total_ms": round(self.total_ms, 3),
               "status": self.status, "spans": self.spans}
        if self.gauges is not None:
            out["gauges"] = self.gauges
        if self.truncated:
            out["truncated_spans"] = self.truncated
        return out

    def est_bytes(self) -> int:
        return TRACE_EST_BYTES + SPAN_EST_BYTES * len(self._raw)


class Tracer:
    """The flight recorder: bounded ring + pinned exemplars + stage
    percentile reservoirs. One module-global instance (`tracer`) is
    shared the way utils/stages is — kernels and gateways have
    no server handle — and each Server configures it from its
    ServerConfig knobs and wires threshold_fn/gauge_fn to its
    governor."""

    def __init__(self, ring_bytes: int = DEFAULT_RING_BYTES,
                 exemplar_slots: int = DEFAULT_EXEMPLAR_SLOTS,
                 threshold_pct: float = DEFAULT_THRESHOLD_PCT):
        self._l = make_lock()
        self.ring_bytes = int(ring_bytes)
        self.exemplar_slots = int(exemplar_slots)
        self.threshold_pct = float(threshold_pct)
        # adaptive promotion threshold: the governor's FULL-latency
        # p99 (queue wait included — what the eval experienced); the
        # tracer's own reservoir is the standalone fallback
        self.threshold_fn: Optional[Callable[[], float]] = None
        # compact governor gauge snapshot captured onto each exemplar
        # at completion (the anatomy plus the weather it happened in)
        self.gauge_fn: Optional[Callable[[], dict]] = None
        # tests pin the threshold to a known value (0.0 == promote all)
        self.force_threshold_ms: Optional[float] = None
        self._enabled = _env_enabled()
        self._ring: deque = deque()             # (trace, est_bytes)
        self._ring_used = 0
        # rolling worst-K tail set; a pin MOVES entries to _pinned
        # (bounded) so the rolling slots stay open — one drift event
        # must never blind the recorder to every later tail eval
        self._exemplars: List[dict] = []        # {trace, pinned, reason}
        self._pinned: List[dict] = []
        self._own_lat: deque = deque(maxlen=OWN_LATENCY_RESERVOIR)
        # cached fallback p99 over _own_lat, recomputed every
        # OWN_P99_EVERY completions: sorting the 512-entry reservoir
        # on EVERY finish() was measurable against millisecond evals
        # (the promotion threshold tolerates a slightly stale p99)
        self._own_p99 = 0.0
        self._own_since_p99 = 0
        self._stage_res: Dict[str, deque] = {}
        self._stage_l = make_lock()
        self.stats = {"traces": 0, "spans": 0, "dropped": 0,
                      "exemplar_promotions": 0, "exemplar_pins": 0}

    # -- lifecycle -----------------------------------------------------
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, on: bool) -> None:
        self._enabled = bool(on)
        stages.set_trace_hook(self._on_stage, on=self._enabled)

    def refresh(self) -> None:
        """Re-read the NOMAD_TPU_TRACE kill switch (tests/operators
        toggle the env var; Server construction calls this)."""
        self.set_enabled(_env_enabled())

    def configure(self, ring_bytes: Optional[int] = None,
                  exemplar_slots: Optional[int] = None,
                  threshold_pct: Optional[float] = None) -> None:
        if ring_bytes is not None:
            self.ring_bytes = int(ring_bytes)
        if exemplar_slots is not None:
            self.exemplar_slots = int(exemplar_slots)
        if threshold_pct is not None:
            self.threshold_pct = float(threshold_pct)
        self.refresh()

    def reset(self) -> None:
        """Forget recorded state (tests); configuration survives."""
        with self._l:
            self._ring.clear()
            self._ring_used = 0
            self._exemplars = []
            self._pinned = []
            self._own_lat.clear()
            self._own_p99 = 0.0
            self._own_since_p99 = 0
        with self._stage_l:
            self._stage_res.clear()
        for k in self.stats:
            self.stats[k] = 0
        self.threshold_fn = None
        self.gauge_fn = None
        self.force_threshold_ms = None

    # -- recording -----------------------------------------------------
    def begin(self, ev, track: str) -> Optional[EvalTrace]:
        """Open a trace for a dequeued eval. The anchor is BACKDATED
        to broker enqueue (ev.broker_wait_s covers blocked/delayed
        heap time too, ev.queue_wait_s the READY-queue slice), so the
        root span is the full enqueue->ack latency and the queue_wait
        span is visible even though nothing ran yet."""
        if not self._enabled or not _env_enabled():
            return None
        now = time.monotonic()
        qw = max(float(getattr(ev, "queue_wait_s", 0.0) or 0.0), 0.0)
        bw = max(float(getattr(ev, "broker_wait_s", qw) or 0.0), qw)
        tr = EvalTrace(
            eval_id=getattr(ev, "id", ""),
            job_id=getattr(ev, "job_id", ""),
            namespace=getattr(ev, "namespace", ""),
            eval_type=getattr(ev, "type", ""),
            track=track, mono0=now - bw, wall0=time.time() - bw)
        attrs = {"ready_ms": round(qw * 1000.0, 3)}
        if bw > qw + 1e-9:
            # time parked on the per-job blocked / delayed heaps
            # before the eval even became READY
            attrs["held_ms"] = round((bw - qw) * 1000.0, 3)
        tr.add_span("queue_wait", bw, end_mono=now, track="broker",
                    attrs=attrs)
        return tr

    def finish(self, tr: Optional[EvalTrace],
               status: str = "acked") -> None:
        """Close and record a trace. Defensive end to end: tracing
        runs inside the worker's ack path, and a recorder bug must
        fail a span, never an eval."""
        if tr is None:
            return
        try:
            tr.total_ms = max(time.monotonic() - tr.mono0, 0.0) * 1000.0
            tr.status = status
            with self._l:
                # the reservoir lock matters: list() elsewhere
                # iterates this deque, and CPython raises on
                # iterate-during-append
                self._own_lat.append(tr.total_ms)
                self._own_since_p99 += 1
                if len(self._own_lat) >= 16 and (
                        self._own_since_p99 >= OWN_P99_EVERY
                        or self._own_p99 <= 0.0):
                    self._own_since_p99 = 0
                    lat = sorted(self._own_lat)
                    self._own_p99 = lat[min(len(lat) - 1,
                                            int(0.99 * len(lat)))]
            self._maybe_promote(tr)
            est = tr.est_bytes()
            with self._l:
                self.stats["traces"] += 1
                self._ring.append((tr, est))
                self._ring_used += est
                while self._ring_used > self.ring_bytes \
                        and len(self._ring) > 1:
                    _old, old_est = self._ring.popleft()
                    self._ring_used -= old_est
                    self.stats["dropped"] += 1
        except Exception:       # pragma: no cover — defensive
            pass

    # -- tail exemplars ------------------------------------------------
    def threshold_ms(self) -> float:
        """Promotion threshold: threshold_pct percent of the tracked
        full-latency p99. 0.0 (no signal yet — cold reservoirs) means
        promote-everything: the worst-K retention below still keeps
        only the slowest traces, so early exemplars are exactly the
        cold-start anatomy a first TPU run wants to see."""
        if self.force_threshold_ms is not None:
            return self.force_threshold_ms
        base = 0.0
        fn = self.threshold_fn
        if fn is not None:
            try:
                base = float(fn())
            except Exception:       # pragma: no cover — defensive
                base = 0.0
        if base <= 0.0:
            base = self._own_p99    # cached; recomputed in finish()
        return base * (self.threshold_pct / 100.0)

    def _maybe_promote(self, tr: EvalTrace) -> None:
        if self.exemplar_slots <= 0 or tr.total_ms < self.threshold_ms():
            return
        gauges = None
        fn = self.gauge_fn
        if fn is not None:
            try:
                gauges = fn()
            except Exception:       # pragma: no cover — defensive
                gauges = None
        with self._l:
            if len(self._exemplars) < self.exemplar_slots:
                tr.gauges = gauges
                self._exemplars.append(
                    {"trace": tr, "pinned": False, "reason": "tail"})
                self.stats["exemplar_promotions"] += 1
                return
            # full: displace the FASTEST rolling exemplar, keeping
            # the set "the worst evals seen" (pinned captures live in
            # _pinned and never occupy rolling slots)
            victim = None
            for e in self._exemplars:
                if victim is None or \
                        e["trace"].total_ms < victim["trace"].total_ms:
                    victim = e
            if victim is not None and \
                    tr.total_ms > victim["trace"].total_ms:
                tr.gauges = gauges
                victim["trace"] = tr
                victim["reason"] = "tail"
                self.stats["exemplar_promotions"] += 1

    def pin_exemplars(self, reason: str = "pinned") -> int:
        """Pin the CURRENT exemplar set (drift auto-pin satellite):
        the captures that existed when the drift detector named a
        suspect are MOVED to a bounded pinned store (2x slots; once
        it is full further pins are dropped — the onset-of-drift
        evidence is the interesting capture) so they survive any
        later, slower tail WITHOUT occupying the rolling slots — a
        pin must never blind the recorder to the tails that develop
        after it. Returns how many were pinned."""
        n = 0
        cap = max(2 * self.exemplar_slots, self.exemplar_slots)
        with self._l:
            for e in self._exemplars:
                if len(self._pinned) >= cap:
                    break
                e["pinned"] = True
                e["reason"] = reason
                self._pinned.append(e)
                n += 1
            del self._exemplars[:n]
        if n:
            self.stats["exemplar_pins"] += n
        return n

    def exemplars(self) -> List[dict]:
        with self._l:
            entries = list(self._pinned) + list(self._exemplars)
        out = []
        for e in sorted(entries, key=lambda e: -e["trace"].total_ms):
            d = e["trace"].to_dict()
            d["pinned"] = e["pinned"]
            d["reason"] = e["reason"]
            out.append(d)
        return out

    def exemplar_count(self) -> int:
        return len(self._pinned) + len(self._exemplars)

    def recent(self, limit: int = 32) -> List[dict]:
        with self._l:
            traces = [t for t, _e in self._ring][-max(limit, 0):]
        return [t.to_dict() for t in traces]

    def ring_len(self) -> int:
        return len(self._ring)

    # -- stage percentiles ---------------------------------------------
    def _on_stage(self, stage: str, seconds: float,
                  attrs: Optional[dict] = None) -> None:
        """The stages.add hook: every report feeds its stage's
        reservoir (a span's CPU companion, <stage>_cpu, among them), an
        ambient one also lands as a span on the thread context's
        trace(s); where the span read its thread's CPU clock `attrs`
        say so (cpu_ms: on a core for that much of the wall)."""
        if stage in AMBIENT_STAGES:
            traces, track = getattr(_tls, "ctx", _NO_CTX)
            if traces:
                end = time.monotonic()  # stamped before any other work
                for tr in traces:
                    tr.add_span(stage, seconds, end, track, attrs)
        # append under the lock: stage_percentiles() copies these
        # deques for sorting, and CPython raises on a deque mutated
        # mid-iteration — one short lock per report, the same cost
        # class as the stages accumulator's own lock
        with self._stage_l:
            res = self._stage_res.get(stage)
            if res is None:
                res = self._stage_res.setdefault(
                    stage, deque(maxlen=STAGE_RESERVOIR))
            res.append(seconds * 1000.0)

    def stage_percentiles(self) -> Dict[str, dict]:
        """{stage: {p50_ms, p95_ms, p99_ms, count}} over the most
        recent STAGE_RESERVOIR reports per stage — the distributional
        complement to stage_breakdown's sums (a sum can't say whether
        plan_commit is uniformly slow or bimodal behind group
        conflicts)."""
        with self._stage_l:     # copy while appends are paused
            items = [(stage, list(res))
                     for stage, res in self._stage_res.items()]
        out = {}
        for stage, vals in sorted(items):
            vals.sort()
            if not vals:
                continue

            def pct(p, _v=vals):
                return _v[min(len(_v) - 1, int(p / 100.0 * len(_v)))]

            out[stage] = {"p50_ms": round(pct(50), 4),
                          "p95_ms": round(pct(95), 4),
                          "p99_ms": round(pct(99), 4),
                          "count": len(vals)}
        return out

    # -- status / export -----------------------------------------------
    def status(self, limit: int = 32,
               exemplars_only: bool = False) -> dict:
        out = {
            "enabled": self._enabled,
            "stats": dict(self.stats),
            "ring": {"traces": len(self._ring),
                     "bytes": self._ring_used,
                     "bytes_max": self.ring_bytes},
            "threshold_ms": round(self.threshold_ms(), 3),
            "exemplar_slots": self.exemplar_slots,
            "exemplars": self.exemplars(),
            "stage_percentiles": self.stage_percentiles(),
        }
        if not exemplars_only:
            out["recent"] = self.recent(limit)
        return out

    def export_chrome(self, limit: int = 32,
                      exemplars_only: bool = False) -> dict:
        seen = set()
        traces: List[dict] = []
        for d in self.exemplars():
            seen.add(d["eval_id"])
            traces.append(d)
        if not exemplars_only:
            for d in self.recent(limit):
                if d["eval_id"] not in seen:
                    traces.append(d)
        return to_chrome(traces)


def to_chrome(traces: List[dict]) -> dict:
    """Chrome trace-event JSON (Perfetto / chrome://tracing loadable)
    from trace dicts: one X (complete) event per span plus the eval
    root, one tid per TRACK (worker-N / broker / gateway / applier /
    committer) so cross-thread overlap is visible on the timeline, and
    M metadata events naming the tracks."""
    events: List[dict] = []
    tids: Dict[str, int] = {}

    def tid(track: str) -> int:
        t = tids.get(track)
        if t is None:
            t = len(tids) + 1
            tids[track] = t
            events.append({"ph": "M", "name": "thread_name", "pid": 1,
                           "tid": t, "args": {"name": track}})
        return t

    for tr in traces:
        base_us = tr.get("start", 0.0) * 1e6
        root_args = {"eval_id": tr.get("eval_id", ""),
                     "job_id": tr.get("job_id", ""),
                     "namespace": tr.get("namespace", ""),
                     "type": tr.get("type", ""),
                     "status": tr.get("status", "")}
        if tr.get("pinned") is not None:
            root_args["pinned"] = tr["pinned"]
            root_args["reason"] = tr.get("reason", "")
        events.append({
            "name": f"eval {tr.get('eval_id', '')[:8]}", "ph": "X",
            "cat": "eval", "pid": 1, "tid": tid(tr.get("track", "eval")),
            "ts": round(base_us, 1),
            "dur": round(max(tr.get("total_ms", 0.0), 0.0) * 1000.0, 1),
            "args": root_args})
        for sp in tr.get("spans", ()):
            args = dict(sp.get("attrs") or {})
            args["eval_id"] = tr.get("eval_id", "")
            events.append({
                "name": sp["name"], "ph": "X", "cat": "eval", "pid": 1,
                "tid": tid(sp.get("track") or tr.get("track", "eval")),
                "ts": round(base_us + sp.get("t0_ms", 0.0) * 1000.0, 1),
                "dur": round(max(sp.get("dur_ms", 0.0), 0.0) * 1000.0, 1),
                "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# process-wide recorder, same idiom as stages / the sanitizer's trace
# counter: kernels and gateways have no server handle;
# Server.configure()s it and wires its governor in
tracer = Tracer()
stages.set_trace_hook(tracer._on_stage, on=tracer.enabled())


# -- module-level conveniences (the call-site API) ---------------------
def begin(ev, track: str) -> Optional[EvalTrace]:
    return tracer.begin(ev, track)


def finish(tr: Optional[EvalTrace], status: str = "acked") -> None:
    tracer.finish(tr, status)


def emit(tr: Optional[EvalTrace], name: str, dur_s: float,
         end_mono: Optional[float] = None,
         track: Optional[str] = None, **attrs) -> None:
    """Attach one span to an explicit trace (the plan applier path:
    the submitting worker stamped the trace onto the plan, and the
    applier/committer threads attribute through it)."""
    if tr is None:
        return
    tr.add_span(name, dur_s, end_mono=end_mono, track=track,
                attrs=attrs or None)


class _TraceSpan(stages.Span):
    """stages.Span whose report also lands, as one span each, on
    explicit traces: the sites that run on another thread than the
    eval's (applier, committer)."""

    __slots__ = ("_targets", "_track")

    def onto(self, tr: Optional[EvalTrace], **extra) -> None:
        """One more trace to land on, with attributes of its own."""
        if tr is not None:
            self._targets.append((tr, extra))

    def __exit__(self, et, ev, tb) -> bool:
        stages.Span.__exit__(self, et, ev, tb)
        if self._live:
            for tr, extra in self._targets:
                attrs = dict(self.attrs, **extra) if extra \
                    else self.attrs
                tr.add_span(self.stage, self.seconds, track=self._track,
                            attrs=attrs or None)
        return False


def span(stage: str, traces=(), track: Optional[str] = None, **attrs):
    """stages.span for a stage that is not ambient: one call times the
    block, reports the stage and attaches the span to each of `traces`
    (more through .onto())."""
    if not stages.enabled:
        return stages.NULL_SPAN
    sp = _TraceSpan(stage, attrs)
    sp._targets = [(tr, None) for tr in traces if tr is not None]
    sp._track = track
    return sp


def report(stage: str, seconds: float, traces=(),
           end_mono: Optional[float] = None,
           track: Optional[str] = None, **attrs) -> None:
    """One after-the-fact report of a wait measured across threads
    (gateway_wait, plan_queue_wait): the stage report and the span on
    each of the traces that waited. Callers guard with `if
    stages.enabled:`, as for stages.add."""
    attrs = attrs or None
    stages.add(stage, seconds, attrs)
    for tr in traces:
        if tr is not None:
            tr.add_span(stage, seconds, end_mono=end_mono, track=track,
                        attrs=attrs)


def uncovered_s(tr: Optional[EvalTrace], name: str) -> Optional[float]:
    """Seconds of the trace's newest `name` span that no other span of
    the trace covers: the union of the others, clipped to the
    interval, taken from its length (never below 0). None without a
    trace or without such a span."""
    if tr is None:
        return None
    raw = list(tr._raw)         # (name, end_mono, dur_s, track, attrs)
    own = next((r for r in reversed(raw) if r[0] == name), None)
    if own is None:
        return None
    b = own[1]
    a = b - max(own[2], 0.0)
    cuts = sorted((max(r[1] - r[2], a), min(r[1], b))
                  for r in raw if r is not own
                  and r[1] - r[2] < b and r[1] > a)
    covered, at = 0.0, a
    for lo, hi in cuts:
        if hi > at:
            covered += hi - max(lo, at)
            at = hi
    return max(b - a - covered, 0.0)
