"""ISSUE 36: the thread's CPU clock beside the wall clock in the spans
a reader asks for (stages.CPU_STAGES), and the process's CPU by thread
role from the telemetry sampler.

A span's wall is a queue for the GIL; what it burned is its thread's
CPU. These tests hold the two clocks to each other (cpu <= wall but for
the clocks' grain; ~0 asleep, ~wall spinning), the companion report
`<stage>_cpu` to one a span and none for a wait or a stage outside
CPU_STAGES, the role ledger to the process's own clock, and the stages
hook to intervals: every report on it but a companion ends as it is
reported, and a companion drawn the same way lies inside its own span.
"""

import threading
import time

import pytest

from nomad_tpu import trace
from nomad_tpu.telemetry import MAX_SERIES, TelemetryCollector
from nomad_tpu.telemetry import collector as ledger
from nomad_tpu.trace import AMBIENT_STAGES, STAGE_PARENTS, to_chrome, tracer
from nomad_tpu.utils import stages

# the two clocks tick apart: what a span's CPU may exceed its wall by
GRAIN_S = 0.002


class _Tap:
    """Every stage report, stamped as it arrives, passed on to the
    recorder that owned the hook before."""

    def __init__(self):
        self.reports = []           # (stage, seconds, attrs, end)
        self._prev, self._prev_on = stages._trace_hook, stages._trace_on
        stages.set_trace_hook(self._on, on=True)

    def _on(self, stage, seconds, attrs=None):
        self.reports.append((stage, seconds, attrs, time.perf_counter()))
        if self._prev is not None and self._prev_on:
            self._prev(stage, seconds, attrs)

    def close(self):
        stages.set_trace_hook(self._prev, on=self._prev_on)

    def of(self, stage):
        return [r for r in self.reports if r[0] == stage]


@pytest.fixture
def tap():
    t = _Tap()
    try:
        yield t
    finally:
        t.close()


def _spin(cpu_s):
    """Burn `cpu_s` seconds of this thread's CPU (not of the wall: on a
    shared machine the thread may stand descheduled meanwhile)."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def _mk_trace(eval_id="ev-cpu"):
    class Ev:
        id = eval_id
        job_id = "job-cpu"
        namespace = "default"
        type = "service"
        queue_wait_s = 0.0
    return tracer.begin(Ev(), track="worker-0")


# -- the two clocks in one span ----------------------------------------

@pytest.mark.parametrize("what", ["sleeps", "spins"])
def test_span_cpu_is_what_the_thread_burned_and_never_above_its_wall(
        tap, what):
    with stages.span("kernel", arm="kway") as sp:
        if what == "sleeps":
            time.sleep(0.05)
        else:
            _spin(0.05)
    assert sp.seconds >= 0.05
    assert 0.0 <= sp.cpu <= sp.seconds + GRAIN_S
    if what == "sleeps":
        assert sp.cpu < 0.01            # off the core for all of it
    else:
        assert sp.cpu >= 0.05           # on it: the wall is no shorter
    # the reading rides the span's attrs, as every tap forwards them
    (stage, seconds, attrs, end), = tap.of("kernel")
    assert seconds == sp.seconds
    assert attrs == {"arm": "kway", "cpu_ms": stages.cpu_ms(sp.cpu)}
    # and goes out once more as an amount under a name of its own,
    # after the span's: drawn as (end - seconds, end) it is the span's
    # tail, inside the span but for the hook's own latency
    (_n, cpu, cpu_attrs, cpu_end), = tap.of("kernel_cpu")
    assert cpu == sp.cpu and cpu_attrs is None
    assert end <= cpu_end <= end + GRAIN_S
    assert cpu_end - cpu >= end - seconds - GRAIN_S


def test_companion_goes_out_once_a_span_and_never_for_a_wait(
        tap, monkeypatch):
    with stages.span("plan_build", placements=3):
        pass
    with stages.span("plan_build") as sp:
        sp.cancel()                     # not an occurrence: no report
    stages.add("queue_wait", 0.25)      # measured across threads
    stages.add("port_assign", 0.01, {"ports": 2})   # a summed report
    trace.report("plan_queue_wait", 0.002, ())
    assert len(tap.of("plan_build")) == len(tap.of("plan_build_cpu")) == 1
    for stage in ("queue_wait", "port_assign", "plan_queue_wait"):
        (_n, _s, attrs, _e), = tap.of(stage)
        assert "cpu_ms" not in (attrs or {})
        assert not tap.of(stage + "_cpu")
    # a stage no reader asks for reads no clock: sched_host least of
    # all (it wraps every other stage, and a tap that draws reports as
    # intervals would let its companion name what its children cover)
    assert "sched_host" not in stages.CPU_STAGES
    for stage in ("sched_host", "wal_encode", "reconcile"):
        with stages.span(stage, n=1) as sp:
            _spin(0.002)
        assert sp.cpu is None and not tap.of(stage + "_cpu")
        assert tap.of(stage)[0][2] == {"n": 1}
    # a caller that has the interval's CPU hands it over itself
    stages.add("fsm_apply", 0.5, None, cpu=0.125)
    assert [r[1] for r in tap.of("fsm_apply_cpu")] == [0.125]
    # no thread clock on the platform: the span still reports, alone
    monkeypatch.setattr(stages, "thread_time", None)
    with stages.span("kernel_pack") as sp:
        pass
    assert sp.cpu is None
    assert len(tap.of("kernel_pack")) == 1 and not tap.of("kernel_pack_cpu")
    assert tap.of("kernel_pack")[0][2] is None


def test_snapshot_carries_the_cpu_sum_beside_the_wall_sum():
    stages.enable()
    try:
        with stages.span("kernel"):
            _spin(0.02)
        with stages.span("kernel"):
            time.sleep(0.02)
        stages.add("queue_wait", 1.0)
        snap = stages.snapshot()
    finally:
        stages.disable()
    kernel = snap["kernel"]
    assert kernel["calls"] == 2 and kernel["seconds"] >= 0.04
    assert 0.02 <= kernel["cpu_seconds"] <= kernel["seconds"] - 0.015
    assert snap["queue_wait"] == {"seconds": 1.0, "calls": 1,
                                  "cpu_seconds": 0.0}
    # a companion is a report on the hook, not a stage of its own
    assert not any(name.endswith("_cpu") for name in snap)


def test_companions_are_in_no_tree_and_the_ledger_is_no_stage():
    assert stages.CPU_STAGES <= set(stages.STAGES)
    # an amount that is no part of a span is not a stage: the ledger's
    # series live in the telemetry ring alone
    assert not any(s.startswith("cpu_") for s in stages.STAGES)
    assert not any(s.startswith("cpu_") for s in STAGE_PARENTS)
    for stage in stages.STAGES:
        assert not stage.endswith(stages.CPU_SUFFIX)
        assert stage + stages.CPU_SUFFIX not in STAGE_PARENTS
        assert stage + stages.CPU_SUFFIX not in AMBIENT_STAGES
    for stage in ("raft_lock_wait", "wal_write", "fsm_apply",
                  "event_publish"):
        assert STAGE_PARENTS[stage] == "plan_commit"
        assert stage in AMBIENT_STAGES  # under the applier's use_many


# -- on the flight recorder --------------------------------------------

def test_recorder_writes_cpu_ms_on_spans_and_feeds_the_companions_reservoir():
    tracer.reset()
    tracer.refresh()
    tr = _mk_trace()
    other = _mk_trace("ev-other")
    with trace.use(tr):
        with stages.span("plan_build", placements=1):       # ambient
            _spin(0.01)
        stages.add("sched_host_self", 0.001)    # after the fact
    with trace.span("plan_commit", (tr,), track="applier", group=2) as sp:
        time.sleep(0.02)                        # explicit targets
        sp.onto(other, committed=True)
    trace.report("plan_queue_wait", 0.003, (tr,), track="applier")
    trace.emit(tr, "plan_verify", 0.004, track="applier", group=2,
               cpu_ms=3.0)
    tracer.finish(tr)
    by = {s["name"]: s for s in tr.spans}

    def cpu_ms(name):
        return (by[name].get("attrs") or {}).get("cpu_ms")

    assert cpu_ms("plan_build") >= 10.0
    assert cpu_ms("plan_build") <= by["plan_build"]["dur_ms"] + 2.0
    assert cpu_ms("plan_commit") < 10.0 <= by["plan_commit"]["dur_ms"]
    assert cpu_ms("plan_verify") == 3.0
    for wait in ("sched_host_self", "plan_queue_wait", "queue_wait"):
        assert cpu_ms(wait) is None
    assert not any(name.endswith("_cpu") for name in by)
    (commit,) = [s for s in other.spans if s["name"] == "plan_commit"]
    assert commit["attrs"] == {"group": 2, "committed": True,
                               "cpu_ms": cpu_ms("plan_commit")}
    # the reservoirs: every stage, and a span's companion beside it
    pct = tracer.stage_percentiles()
    assert pct["plan_build_cpu"]["count"] == pct["plan_build"]["count"] == 1
    assert pct["plan_build_cpu"]["p50_ms"] == pytest.approx(
        cpu_ms("plan_build"), abs=0.01)
    assert pct["plan_commit_cpu"]["p50_ms"] < pct["plan_commit"]["p50_ms"]
    assert "plan_queue_wait_cpu" not in pct
    # the Chrome export says it where an operator looks: the args
    events = {e["name"]: e for e in to_chrome([tr.to_dict()])["traceEvents"]
              if e["ph"] == "X"}
    assert events["plan_build"]["args"]["cpu_ms"] == cpu_ms("plan_build")
    assert "cpu_ms" not in events["plan_queue_wait"]["args"]


# -- the process's CPU by thread role ------------------------------------

@pytest.mark.parametrize("name, role", [
    ("worker-0", "workers"), ("worker-3-finisher", "workers"),
    ("worker-1-lane-2", "workers"), ("worker-0-lane-1f3a9c2e", "workers"),
    ("plan-applier", "applier"), ("plan-committer", "applier"),
    ("raft-fsm", "applier"), ("http-api", "http"),
    ("ingest-gateway", "http"),
    ("Thread-12 (process_request_thread)", "http"),
    ("telemetry", None), ("snapshot-writer", None), ("MainThread", None),
    ("governor", None), ("event-sink-ab12cd34", None),
])
def test_thread_role_goes_by_the_names_the_threads_have(name, role):
    assert ledger.thread_role(name) == role


def test_role_ledger_attributes_a_live_worker_and_a_thread_that_ended(tap):
    """A spinning worker-x is read from outside while it lives; a
    connection's handler that lived shorter than a sample tallied itself
    as it ended; and the roles sum to the process's own clock."""
    if ledger.thread_cpu_by_role() is None:
        pytest.skip("no way to read another thread's CPU clock here")
    tc = TelemetryCollector(interval_s=60.0, slots=8, device_fn=None)
    stop = threading.Event()

    def work():
        while not stop.is_set():
            _spin(0.005)

    def handle():
        try:
            _spin(0.03)
        finally:
            ledger.thread_ended("http")

    worker = threading.Thread(target=work, name="worker-x", daemon=True)
    worker.start()
    try:
        first = tc._cpu_row()
        handler = threading.Thread(
            target=handle, name="Thread-9 (process_request_thread)")
        handler.start()
        handler.join(timeout=30)
        assert not handler.is_alive()
        t_end = time.thread_time() + 0.05
        while time.thread_time() < t_end:       # "other": this thread
            pass
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:      # let worker-x have 50 ms
            row = tc._cpu_row()
            if row["thread_cpu.workers_s"] \
                    - first["thread_cpu.workers_s"] >= 0.05:
                break
            time.sleep(0.01)
    finally:
        stop.set()
        worker.join(timeout=30)
    grew = {k: row[k] - first[k] for k in row}
    assert grew["thread_cpu.workers_s"] >= 0.05
    assert grew["thread_cpu.http_s"] >= 0.03
    assert grew["thread_cpu.other_s"] >= 0.04
    for r in (first, row):
        named = sum(r[f"thread_cpu.{role}_s"]
                    for role in ("workers", "applier", "http", "other"))
        assert named == pytest.approx(r["process.cpu_s"], rel=1e-9)
    # amounts over a sample, no part of any span: the ring has them,
    # the stages hook (whose taps draw every report as an interval
    # that ends as it arrives) heard nothing of them
    tc.sample_once(now=1000.0)
    assert {"process.cpu_s", "thread_cpu.workers_s"} <= set(tc._series)
    assert not tap.reports


def test_a_tallied_thread_is_not_read_twice():
    """Between thread_ended() and the thread's real end the sampler must
    not add its clock to the tally that already holds it."""
    if ledger.thread_cpu_by_role() is None:
        pytest.skip("no way to read another thread's CPU clock here")
    tallied, release = threading.Event(), threading.Event()

    def lane():
        _spin(0.02)
        ledger.thread_ended("workers")
        tallied.set()
        release.wait(30)                # still alive, still enumerated

    before = ledger.thread_cpu_by_role()["workers"]
    t = threading.Thread(target=lane, name="worker-9-lane-0", daemon=True)
    t.start()
    try:
        assert tallied.wait(30)
        grew = ledger.thread_cpu_by_role()["workers"] - before
        assert 0.02 <= grew < 0.035
    finally:
        release.set()
        t.join(timeout=30)
    assert ledger.thread_cpu_by_role()["workers"] - before \
        == pytest.approx(grew, abs=1e-9)


def test_ring_reads_cores_and_keeps_the_companions_lean():
    """process.cpu_s and thread_cpu.* are cumulative seconds, so their
    rate is cores; a companion brings its median alone (a column of its
    stage's row in `operator top`)."""
    pcts = {"p50_ms": 1.0, "p99_ms": 2.0, "count": 3}
    tc = TelemetryCollector(
        interval_s=60.0, slots=8, device_fn=None,
        stage_fn=lambda: {"kernel": pcts, "kernel_cpu": pcts})
    row = tc._collect_row()
    assert {k for k in row if k.startswith("stage")} == {
        "stage.kernel.p50_ms", "stage.kernel.p99_ms",
        "stage_count.kernel", "stage.kernel_cpu.p50_ms"}
    tc.sample_once(now=1000.0)
    _spin(0.05)
    tc.sample_once(now=1001.0)
    hist = tc.history()
    cores = hist["rates"]["process.cpu_s"][-1]
    assert cores >= 0.05                # 50 ms of CPU in a 1 s slot
    if "thread_cpu.other_s" in hist["rates"]:
        assert hist["rates"]["thread_cpu.other_s"][-1] >= 0.05


def test_a_served_agents_ring_has_room_for_every_companion(tmp_path):
    """MAX_SERIES is a hard cap and a series past it is dropped without
    a word: the ring of an agent that has served evals (every stage,
    every companion, every gauge family of a dev agent) fits with room
    to spare, so the PR that fills it fails here and not in `operator
    top`'s missing column."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server, ServerConfig
    tracer.reset()
    srv = Server(ServerConfig(num_schedulers=2, heartbeat_ttl_s=3600.0,
                              telemetry_sample_interval_s=3600.0,
                              data_dir=str(tmp_path)))
    srv.start()
    try:
        for i in range(4):
            node = mock.node()
            node.name = f"ring-n{i}"
            node.compute_class()
            srv.register_node(node)
        job = mock.job()
        job.id = "ring-job"
        job.task_groups[0].count = 2
        srv.register_job(job)
        deadline = time.time() + 30
        while time.time() < deadline and \
                len(srv.store.allocs_by_job("default", job.id)) < 2:
            time.sleep(0.005)
        srv.telemetry.sample_once()
        status = srv.telemetry.status()
        names = set(srv.telemetry._series)
    finally:
        srv.shutdown()
        tracer.reset()
    reported = {s for s in stages.CPU_STAGES
                if f"stage.{s}.p50_ms" in names}
    assert {"plan_build", "plan_commit", "fsm_apply", "kernel",
            "job_register"} <= reported
    for stage in reported:
        assert f"stage.{stage}_cpu.p50_ms" in names
    assert status["series_dropped"] == 0
    # every stage of the tree three series and every companion one,
    # whether this run reported it or not, beside what is there now
    stage_series = 3 * len(stages.STAGES) + len(stages.CPU_STAGES)
    others = len([n for n in names if not n.startswith("stage")])
    assert others + stage_series <= MAX_SERIES - 8, (others, stage_series)
