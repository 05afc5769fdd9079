"""Eval flight recorder (ISSUE 9 tentpole + satellites).

Covers: span-tree completeness per eval path (solo / gateway-dispatched
/ group-committed / demoted-retry), ring bounding + exemplar
worst-K retention and pinning under churn, drift auto-pin, the
NOMAD_TPU_TRACE kill switch, Chrome trace-event JSON schema validity,
the HTTP/CLI surface, stages.snapshot(), what the recorder does per
eval as counts, and (slow, by hand on a quiet machine) an overhead
smoke asserting tracing-on e2e placements/s within 5% of tracing-off.
"""

import collections
import json
import threading
import time

import pytest

from nomad_tpu import mock, trace
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.trace import (STAGE_PARENTS, EvalTrace, Tracer, to_chrome,
                             tracer)
from nomad_tpu.utils import stages


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer.reset()
    tracer.refresh()
    yield
    tracer.reset()
    tracer.refresh()


def _mk_eval_trace(eid="ev-test", track="test"):
    class Ev:
        id = eid
        job_id = "j"
        namespace = "default"
        type = "service"
        queue_wait_s = 0.0

    tr = tracer.begin(Ev(), track=track)
    assert tr is not None
    return tr


def _run_jobs(n_jobs=3, count=2, prefix="trace", **cfg):
    """Drive n_jobs service jobs through a real Server; returns
    (jobs, placements/s). Workers paused during registration so the
    broker has depth (the gateway-coalescing shape)."""
    s = Server(ServerConfig(num_schedulers=2, heartbeat_ttl_s=3600.0,
                            **cfg))
    s.start()
    try:
        for w in s.workers:
            w.set_pause(True)
        for i in range(12):
            node = mock.node()
            node.name = f"{prefix}-n{i}"
            node.compute_class()
            s.register_node(node)
        jobs = []
        for i in range(n_jobs):
            job = mock.job()
            job.id = f"{prefix}-{i}"
            tg = job.task_groups[0]
            tg.count = count
            for t in tg.tasks:
                t.resources.networks = []
            tg.networks = []
            jobs.append(job)
            s.register_job(job)
        t0 = time.perf_counter()
        for w in s.workers:
            w.set_pause(False)
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(len(s.store.allocs_by_job("default", j.id)) == count
                   for j in jobs):
                break
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        placed = sum(len(s.store.allocs_by_job("default", j.id))
                     for j in jobs)
        assert placed == n_jobs * count
    finally:
        s.shutdown()     # drains the deferred-finish queues
    return jobs, placed / max(wall, 1e-9)


def _traces_for(prefix):
    return [t for t in tracer.recent(200)
            if t["job_id"].startswith(prefix + "-")]


# -- span-tree completeness --------------------------------------------

REQUIRED_SOLO = {"queue_wait", "sched_host", "reconcile",
                 "plan_verify", "plan_commit", "broker_ack"}


def test_solo_path_span_tree_complete():
    """Gateway off (window=0): every placing eval's trace carries the
    full enqueue->ack tree with commit attrs, and the static parent
    encoding holds."""
    _run_jobs(prefix="solo", gateway_window_us=0)
    ts = _traces_for("solo")
    assert len(ts) >= 3
    placing = [t for t in ts
               if any(s["name"] == "plan_commit" for s in t["spans"])]
    assert len(placing) >= 3
    for t in placing:
        names = {s["name"] for s in t["spans"]}
        assert REQUIRED_SOLO <= names, names
        assert t["status"] == "acked"
        assert t["total_ms"] > 0
        for sp in t["spans"]:
            assert sp["parent"] in (None, "eval") \
                or sp["parent"] in STAGE_PARENTS
            assert sp["t0_ms"] >= 0.0 and sp["dur_ms"] >= 0.0
            # spans sit inside the eval window (small slack for the
            # finish-side bookkeeping racing the deferred ack)
            assert sp["t0_ms"] <= t["total_ms"] + 50.0
        qw = next(s for s in t["spans"] if s["name"] == "queue_wait")
        assert qw["track"] == "broker"
        assert "ready_ms" in qw["attrs"]
        pv = next(s for s in t["spans"] if s["name"] == "plan_verify")
        assert pv["attrs"]["group"] >= 1
        assert pv["track"] == "applier"
        pc = next(s for s in t["spans"] if s["name"] == "plan_commit")
        assert pc["attrs"]["group"] >= 1
        rc = next(s for s in t["spans"] if s["name"] == "reconcile")
        assert rc["attrs"]["columnar"] in (True, False)


def test_gateway_path_records_batch_attrs_and_kernel_arms():
    """Gateway on (default): every dispatched eval gets a
    gateway_wait span with the fire anatomy (trigger/batch/lanes) on
    the gateway track, and kernel spans carry (arm, n_pad, fresh)."""
    _run_jobs(prefix="gw")
    ts = _traces_for("gw")
    assert ts
    gws = [s for t in ts for s in t["spans"]
           if s["name"] == "gateway_wait"]
    assert gws, "no gateway spans recorded"
    for s in gws:
        assert s["track"] == "gateway"
        assert s["attrs"]["trigger"] in (
            "occupancy", "immediate", "drain", "deadline")
        assert s["attrs"]["batch"] >= 1
        assert s["attrs"]["lanes"] >= 1
    kernels = [s for t in ts for s in t["spans"]
               if s["name"] == "kernel"]
    assert kernels, "no kernel spans recorded"
    for s in kernels:
        assert isinstance(s["attrs"]["arm"], str) and s["attrs"]["arm"]
        assert s["attrs"]["n_pad"] >= 1
        assert s["attrs"]["fresh"] in (True, False)

    # Chrome export over the real ring: valid trace-event JSON, every
    # X event on a named track
    out = tracer.export_chrome(limit=100)
    json.loads(json.dumps(out))     # round-trips
    assert out["displayTimeUnit"] == "ms"
    evs = out["traceEvents"]
    assert evs
    named, used = set(), set()
    for e in evs:
        assert e["ph"] in ("X", "M")
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        if e["ph"] == "M":
            assert e["name"] == "thread_name"
            assert e["args"]["name"]
            named.add(e["tid"])
        else:
            assert e["name"]
            assert e["ts"] >= 0 and e["dur"] >= 0
            used.add(e["tid"])
    assert used <= named
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "gateway" in tracks and "applier" in tracks


def _conflict_fixture():
    """Two plans overfilling one node (the test_plan_group shape):
    grouped, the second demotes exactly like a stale-snapshot retry."""
    from nomad_tpu.models import ALLOC_CLIENT_RUNNING, Plan
    from nomad_tpu.utils.ids import generate_uuid

    job = mock.batch_job()
    node = mock.node()

    def make_plan():
        a = mock.batch_alloc()
        a.id = generate_uuid()
        a.eval_id = ""
        a.job = None
        a.job_id = job.id
        a.task_group = job.task_groups[0].name
        a.node_id = node.id
        a.client_status = ALLOC_CLIENT_RUNNING
        res = a.allocated_resources.tasks["worker"]
        res.cpu.cpu_shares = 3000
        res.memory.memory_mb = 6000
        p = Plan(priority=50)
        p.job = job
        p.node_allocation = {node.id: [a]}
        return p

    return job, node, make_plan(), make_plan()


def test_group_commit_and_demotion_span_attrs():
    """Grouped plans: each member's trace gets a per-plan verify span
    with the group width, the loser's is marked conflicted+demoted,
    and the shared commit span carries the group size + raft index."""
    from nomad_tpu.server.plan_queue import PendingPlan

    job, node, p1, p2 = _conflict_fixture()
    t1 = _mk_eval_trace("ev-winner")
    t2 = _mk_eval_trace("ev-loser")
    p1._trace = t1
    p2._trace = t2
    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=3600.0))
    srv.store.upsert_node(100, node)
    srv.store.upsert_job(101, job)
    srv._raft_index = 101
    pairs, waiter, gidx = srv.plan_applier.apply_group(
        [PendingPlan(p1), PendingPlan(p2)])
    assert waiter is None and len(pairs) == 2 and gidx > 0

    v1 = next(s for s in t1.spans if s["name"] == "plan_verify")
    assert v1["attrs"]["group"] == 2
    assert v1["attrs"]["conflicted"] is False
    assert v1["attrs"]["demoted"] is False
    assert v1["attrs"]["queue_ms"] >= 0.0
    v2 = next(s for s in t2.spans if s["name"] == "plan_verify")
    assert v2["attrs"]["conflicted"] is True
    assert v2["attrs"]["demoted"] is True

    c1 = next(s for s in t1.spans if s["name"] == "plan_commit")
    assert c1["attrs"]["group"] == 2
    assert c1["attrs"]["index"] == gidx
    assert c1["attrs"]["committed"] is True
    # the fully rejected plan had nothing to commit but still learns
    # the group's commit index from its span
    c2 = next(s for s in t2.spans if s["name"] == "plan_commit")
    assert c2["attrs"]["committed"] is False


def test_kernel_span_fans_out_to_every_lane():
    """A batched fire's ONE device dispatch must land on each lane's
    trace (the gateway installs the union context around _run)."""
    from nomad_tpu.ops.select import kernel_span

    t1 = _mk_eval_trace("lane-1")
    t2 = _mk_eval_trace("lane-2")
    with trace.use_many([t1, t2], track="gateway"):
        with kernel_span("kway_batched", 128, lanes=2):
            pass
    for tr in (t1, t2):
        ks = [s for s in tr.spans if s["name"] == "kernel"]
        assert len(ks) == 1
        # (cpu_ms: `kernel` reads its thread's CPU clock, ISSUE 36)
        assert ks[0]["attrs"] == {"arm": "kway_batched", "n_pad": 128,
                                  "lanes": 2, "fresh": False,
                                  "cpu_ms": ks[0]["attrs"]["cpu_ms"]}
        assert ks[0]["track"] == "gateway"
    # compile walls are flagged, not hidden
    with trace.use(t1):
        with kernel_span("chunked", 64, fresh=True):
            pass
    fresh = [s for s in t1.spans
             if s["name"] == "kernel" and s["attrs"]["fresh"]]
    assert len(fresh) == 1


# -- the closed span tree (ISSUE 25) -----------------------------------

class _Tap:
    """Every stage report, kept, and passed on to the recorder."""

    def __init__(self):
        self.reports = []               # (stage, seconds, attrs)
        self._prev, self._prev_on = stages._trace_hook, stages._trace_on
        stages.set_trace_hook(self._on, on=True)

    def _on(self, stage, seconds, attrs=None):
        self.reports.append((stage, seconds, attrs))
        if self._prev is not None and self._prev_on:
            self._prev(stage, seconds, attrs)

    def close(self):
        stages.set_trace_hook(self._prev, on=self._prev_on)

    def of(self, stage):
        return [r for r in self.reports if r[0] == stage]


class _SpanLog:
    """Every stages.Span's opening and closing as (thread, stage,
    "open" | "close"), each written by the thread that did it: per
    thread the list is the order of events, with no clock in it."""

    def __init__(self):
        self.events = []
        self._enter = stages.Span.__enter__
        self._exit = stages.Span.__exit__
        log = self

        def enter(sp):
            log.events.append((threading.get_ident(), sp.stage, "open"))
            return log._enter(sp)

        def exit_(sp, et, ev, tb):
            log.events.append((threading.get_ident(), sp.stage, "close"))
            return log._exit(sp, et, ev, tb)

        stages.Span.__enter__, stages.Span.__exit__ = enter, exit_

    def close(self):
        stages.Span.__enter__, stages.Span.__exit__ = \
            self._enter, self._exit

    def opened_inside(self, child, parent):
        """How many `child` spans there were, each opened while a
        `parent` was open on the same thread (so closed before it:
        with-blocks nest)."""
        n, stacks = 0, collections.defaultdict(list)
        for thread, stage, what in self.events:
            stack = stacks[thread]
            if what == "close":
                assert stack.pop() == stage
                continue
            if stage == child:
                assert parent in stack, (child, stack)
                n += 1
            stack.append(stage)
        return n


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three jobs by register_job and two by one bulk register through
    a real Server with a data_dir; then one forced snapshot and one
    forced private table build. What each stage test reads: the stage
    reports and the placing evals' traces."""
    tracer.reset()
    srv = Server(ServerConfig(
        num_schedulers=2, heartbeat_ttl_s=3600.0,
        data_dir=str(tmp_path_factory.mktemp("served"))))
    srv.start()
    tap = _Tap()        # a Server's construction re-arms the recorder
    log = _SpanLog()
    try:
        for i in range(12):
            node = mock.node()
            node.name = f"served-n{i}"
            node.compute_class()
            srv.register_node(node)

        def mk(i):
            job = mock.job()
            job.id = f"served-{i}"
            tg = job.task_groups[0]
            tg.count = 2
            for t in tg.tasks:
                t.resources.networks = []
            tg.networks = []
            return job

        jobs = [mk(i) for i in range(5)]
        for job in jobs[:3]:
            srv.register_job(job)
        bulk = srv.register_jobs_bulk(jobs[3:])
        assert not any(isinstance(r, Exception) for r in bulk)
        deadline = time.time() + 30
        while time.time() < deadline and not all(
                len(srv.store.allocs_by_job("default", j.id)) == 2
                for j in jobs):
            time.sleep(0.005)
        coalesced = srv.ingest is not None

        srv.persistence.snapshot(srv.store)
        snapshot_s = srv.persistence.stats["last_snapshot_s"]

        # a snapshot the cache has moved past pays a private build
        old = srv.store.snapshot()
        extra = mock.node()
        extra.compute_class()
        srv.register_node(extra)
        srv.store.snapshot().node_table()
        private = _mk_eval_trace("ev-private")
        before = len(tap.of("table_build_private"))
        with trace.use(private):
            assert old.node_table() is not None
        private_reports = len(tap.of("table_build_private")) - before
    finally:
        srv.shutdown()
        log.close()
        tap.close()
    traces = [t for t in _traces_for("served")
              if any(s["name"] == "plan_commit" for s in t["spans"])]
    assert len(traces) == 5
    return {"tap": tap, "log": log, "traces": traces,
            "coalesced": coalesced,
            "snapshot_s": snapshot_s, "private": private.to_dict(),
            "private_reports": private_reports}


def _count(t, name):
    return sum(1 for s in t["spans"] if s["name"] == name)


# per placing eval: the stage occurs as often as the span it pairs with
ONCE_PER = {"select_prep": "select_finish", "feasibility": "select_prep",
            "select_finish": "plan_build", "plan_build": "select_prep",
            "kernel_pack": "kernel", "kernel_expand": "kernel",
            "plan_submit": "plan_queue_wait",
            "plan_queue_wait": "plan_verify",
            "wal_encode": "plan_commit", "raft_lock_wait": "plan_commit",
            "wal_write": "plan_commit", "fsm_apply": "plan_commit",
            "event_publish": "plan_commit"}
# plan_commit's children (ISSUE 36), in the order the commit runs them
COMMIT_CHILDREN = ("raft_lock_wait", "wal_encode", "wal_write",
                   "fsm_apply", "event_publish")


@pytest.mark.parametrize("stage", [
    "job_register", "select_prep", "feasibility", "kernel_pack",
    "kernel_expand", "select_finish", "plan_build", "plan_submit",
    "plan_queue_wait", "wal_encode", "table_build_private",
    "snapshot_write", "sched_host_self", "raft_lock_wait", "wal_write",
    "fsm_apply", "event_publish"])
def test_stage_is_reported_once_per_occurrence_under_its_parent(
        served, stage):
    assert stage in stages.STAGES and stage in STAGE_PARENTS
    tap, traces = served["tap"], served["traces"]
    reports = tap.of(stage)
    assert reports and all(s >= 0.0 for _n, s, _a in reports)
    if stage == "job_register":
        sizes = sorted(a["jobs"] for _n, _s, a in reports)
        assert sizes == ([1, 1, 1, 2] if served["coalesced"]
                         else [1] * 5)
        assert STAGE_PARENTS[stage] is None
        assert not any(_count(t, stage) for t in traces)
        return
    if stage == "snapshot_write":
        (_n, seconds, attrs), = reports
        assert attrs["bytes"] > 0 and attrs["entries"] > 0
        assert seconds == pytest.approx(served["snapshot_s"], abs=0.005)
        return
    if stage == "table_build_private":
        assert served["private_reports"] == 1
        assert _count(served["private"], stage) == 1
        return
    for t in traces:
        spans = [s for s in t["spans"] if s["name"] == stage]
        assert spans, (stage, [s["name"] for s in t["spans"]])
        if stage == "sched_host_self":
            assert len(spans) == 1
        else:
            assert len(spans) == _count(t, ONCE_PER[stage])
        parents = [s for s in t["spans"]
                   if s["name"] == STAGE_PARENTS[stage]]
        for sp in spans:
            if stage == "sched_host_self":
                continue        # scattered time, drawn at the end
            if stage in COMMIT_CHILDREN:
                # a span is drawn back from a stamp taken when its
                # report reaches the trace, and plan_commit's reaches
                # it after the hook has taken the reservoirs' lock,
                # which both workers' reports take too: a late stamp
                # draws the parent's start after the child's. So the
                # order of events instead of a width: the applier
                # stamps the child's end before the parent's and its
                # clock gave the child no more time (rounding to a us
                # apart), and it opened the one inside the other (below)
                assert any(sp["t0_ms"] + sp["dur_ms"]
                           <= p["t0_ms"] + p["dur_ms"] + 0.002
                           and sp["dur_ms"] <= p["dur_ms"] + 0.001
                           for p in parents), (sp, parents)
                continue
            # inside one span of the parent's name (0.2 ms of slack for
            # the report's own latency and the rounding to a us)
            assert any(p["t0_ms"] - 0.2 <= sp["t0_ms"] and
                       sp["t0_ms"] + sp["dur_ms"]
                       <= p["t0_ms"] + p["dur_ms"] + 0.2
                       for p in parents), (sp, parents)
        if stage == "plan_submit":
            assert all(sp["attrs"]["refreshed"] in (True, False)
                       for sp in spans)
        if stage == "wal_encode":
            # two placements off one flyweight: two rows, and what
            # they share written once for both (ISSUE 35)
            for sp in spans:
                assert sp["track"] == "applier"
                assert sp["attrs"]["rows"] >= 2
                assert sp["attrs"]["consts"] >= 3
                assert sp["attrs"]["objects"] >= 3
                assert {"shared", "table"} <= set(sp["attrs"])
                assert sp["attrs"]["bytes"] > 0
        if stage in COMMIT_CHILDREN:
            assert all(sp["track"] == "applier" for sp in spans)
        if stage == "wal_write":
            # no fsync in this configuration: the write alone, once
            assert all(sp["attrs"] == {"synced": False} for sp in spans)
        if stage == "fsm_apply":
            assert {sp["attrs"]["kind"] for sp in spans} \
                <= {"plan_results", "plan_group_results"}
        if stage == "event_publish":
            assert all(sp["attrs"]["events"] >= 2 for sp in spans)
    if stage in COMMIT_CHILDREN and stage != "raft_lock_wait":
        # (raft_lock_wait is a wait reported after the fact: no Span)
        assert served["log"].opened_inside(stage, "plan_commit") \
            == len(reports)
    if stage in COMMIT_CHILDREN:
        # for the plan applier's entries alone (one a commit, which two
        # plans may share): the five job registers and the eval updates
        # took the same lock and wrote the same log
        assert len(reports) == len(tap.of("plan_commit"))


def test_plan_commits_children_follow_each_other_inside_it(served):
    """What plan_commit waited for, named (ISSUE 36): the raft lock, the
    record's framing, its write, the store's transaction, the change
    events, in that order and none inside another, so that with what
    they leave (the follow-up evals, the futures) they account for the
    commit's wall."""
    for t in served["traces"]:
        commit = next(s for s in t["spans"] if s["name"] == "plan_commit")
        kids = [next(s for s in t["spans"] if s["name"] == name)
                for name in COMMIT_CHILDREN]
        assert all(k["parent"] == "plan_commit" for k in kids)
        ends = [k["t0_ms"] + k["dur_ms"] for k in kids]
        assert ends == sorted(ends)
        # drawn back from stamps taken as each report arrives: 0.2 ms
        # of slack for a report's own latency, as above
        for before, after in zip(kids, kids[1:]):
            assert before["t0_ms"] + before["dur_ms"] \
                <= after["t0_ms"] + 0.2, (before, after)
        assert sum(k["dur_ms"] for k in kids) <= commit["dur_ms"] + 0.2


def test_a_served_evals_spans_carry_their_cpu(served):
    """The spans of stages.CPU_STAGES say how long they were on a core
    (attr cpu_ms <= dur_ms but for the clocks' grain); a wait reported
    after the fact, and a stage no reader asks for, say nothing."""
    for t in served["traces"]:
        for sp in t["spans"]:
            cpu_ms = (sp.get("attrs") or {}).get("cpu_ms")
            if sp["name"] in stages.CPU_STAGES:
                assert 0.0 <= cpu_ms <= sp["dur_ms"] + 0.05, sp
            else:
                assert cpu_ms is None, sp
        # the applier's thread worked through plan_commit, most of it
        # inside the store's transaction
        by = {sp["name"]: sp for sp in t["spans"]}
        assert by["fsm_apply"]["attrs"]["cpu_ms"] \
            <= by["plan_commit"]["attrs"]["cpu_ms"] + 0.05
    # the companions are reports, never spans, and the tap heard one
    # for every span report of the stage, after it
    tap = served["tap"]
    assert not any(sp["name"].endswith("_cpu")
                   for t in served["traces"] for sp in t["spans"])
    for stage in sorted(stages.CPU_STAGES):
        assert len(tap.of(stage + "_cpu")) == len(tap.of(stage)) > 0, stage
        assert all(a is None for _n, _s, a in tap.of(stage + "_cpu"))
        for (_n, wall, attrs), (_c, cpu, _a) in zip(
                tap.of(stage), tap.of(stage + "_cpu")):
            assert attrs["cpu_ms"] == stages.cpu_ms(cpu)
            assert cpu <= wall + 0.00005
    heard = {n for n, _s, _a in tap.reports}
    assert {n for n in heard if n.endswith("_cpu")} \
        == {s + "_cpu" for s in stages.CPU_STAGES}
    assert heard - set(stages.STAGES) \
        == {s + "_cpu" for s in stages.CPU_STAGES}


def test_children_of_sched_host_and_self_sum_to_it(served):
    """The check the parent map is held to: per eval, the spans whose
    parent is sched_host (sched_host_self among them) add up to the
    sched_host span — a stage that really nests in a sibling would
    count twice and break it. The one stage the map files elsewhere
    that can run in there: table_build, when the worker's refresh
    before Process() was refused a full build (the first eval on a
    cold cache) and the scheduler builds the table itself."""
    for t in served["traces"]:
        host = next(s for s in t["spans"] if s["name"] == "sched_host")
        end = host["t0_ms"] + host["dur_ms"]
        kids = sum(s["dur_ms"] for s in t["spans"]
                   if s["parent"] == "sched_host"
                   or (s["name"] == "table_build"
                       and host["t0_ms"] <= s["t0_ms"]
                       and s["t0_ms"] + s["dur_ms"] <= end))
        assert kids == pytest.approx(host["dur_ms"], rel=0.02, abs=0.05)
        me = next(s for s in t["spans"]
                  if s["name"] == "sched_host_self")
        assert 0.0 <= me["dur_ms"] <= host["dur_ms"]


def test_uncovered_is_a_union_clipped_and_never_negative():
    now = time.monotonic()
    tr = EvalTrace("ev", "job", "default", "batch", "w",
                   mono0=now - 1.0, wall0=time.time() - 1.0)

    def span(name, a_ms, b_ms):
        tr.add_span(name, (b_ms - a_ms) / 1000.0,
                    end_mono=tr.mono0 + b_ms / 1000.0)

    assert trace.uncovered_s(tr, "sched_host") is None
    span("queue_wait", 0.0, 10.0)       # ends where the interval starts
    span("table_build", 5.0, 20.0)      # straddles the start: 10 inside
    span("sched_host", 10.0, 110.0)
    span("kernel", 30.0, 60.0)
    span("d2h", 40.0, 50.0)             # nested: adds nothing
    span("plan_build", 55.0, 70.0)      # overlaps kernel: 10 more
    span("plan_submit", 100.0, 130.0)   # straddles the end: 10 inside
    # covered: 10 + (30..70 = 40) + 10 = 60 of 100
    assert trace.uncovered_s(tr, "sched_host") == pytest.approx(0.040)
    span("reconcile", 0.0, 200.0)       # covers everything
    assert trace.uncovered_s(tr, "sched_host") == 0.0


def test_span_reports_on_an_exception_and_is_free_when_off(monkeypatch):
    tap = _Tap()
    try:
        with pytest.raises(ValueError):
            with stages.span("select_prep", why="test") as sp:
                sp.note(more=1)
                raise ValueError("boom")
        (_n, seconds, attrs), = tap.of("select_prep")
        # (cpu_ms: `select_prep` reads its thread's CPU clock, ISSUE 36)
        assert seconds >= 0.0 and attrs == {"why": "test", "more": 1,
                                            "cpu_ms": attrs["cpu_ms"]}
        with stages.span("table_build") as sp:
            sp.cancel()
        assert not tap.of("table_build")
    finally:
        tap.close()
    # nothing listens: one bool read, then the shared no-op object —
    # no Span, no clock, no TraceAnnotation
    stages.disable()
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    tracer.refresh()
    assert not stages.enabled
    assert stages.span("kernel", arm="x") is stages.NULL_SPAN
    assert stages.annotate("eval", eval_id="x") is stages.NULL_SPAN
    assert trace.span("plan_commit", (None,)) is stages.NULL_SPAN
    with stages.span("kernel") as sp:
        sp.note(a=1)
        sp.cancel()
        sp.onto(None)
    assert sp.seconds == 0.0
    monkeypatch.delenv("NOMAD_TPU_TRACE")
    tracer.refresh()


def test_stages_sit_on_the_profilers_clock(tmp_path):
    """Under a live jax.profiler session the eval and its stages are
    TraceAnnotations in the host planes: nested in time, and within
    1 ms of the spans of the eval's own trace."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    srv = Server(ServerConfig(num_schedulers=1, heartbeat_ttl_s=3600.0))
    srv.start()
    try:
        for i in range(8):
            node = mock.node()
            node.name = f"prof-n{i}"
            node.compute_class()
            srv.register_node(node)

        def run(job_id):
            job = mock.job()
            job.id = job_id
            tg = job.task_groups[0]
            tg.count = 2
            for t in tg.tasks:
                t.resources.networks = []
            tg.networks = []
            srv.register_job(job)
            deadline = time.time() + 30
            while time.time() < deadline and len(
                    srv.store.allocs_by_job("default", job_id)) < 2:
                time.sleep(0.005)
            time.sleep(0.2)             # the deferred ack

        run("prof-warm")                # compiles stay out of the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            wall = time.time()
            with TraceAnnotation("test_clock_mark"):
                time.sleep(0.002)
            run("prof-traced")
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.shutdown()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nomad/") \
                        or ev.name == "test_clock_mark":
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns / 1e9, ev.duration_ns / 1e9))
    # seconds to add to a profiler time to get this process's wall clock
    offset = wall - events["test_clock_mark"][0][0]
    tr, = [t for t in tracer.recent(10) if t["job_id"] == "prof-traced"]
    (e0, e_dur), = events["nomad/eval"]
    for stage in ("sched_host", "kernel", "plan_submit"):
        (p0, p_dur), = events["nomad/" + stage]
        sp, = [s for s in tr["spans"] if s["name"] == stage]
        at = tr["start"] + sp["t0_ms"] / 1000.0
        assert p0 + offset == pytest.approx(at, abs=0.001)
        assert p_dur == pytest.approx(sp["dur_ms"] / 1000.0, abs=0.001)
        assert e0 <= p0 and p0 + p_dur <= e0 + e_dur
    (h0, h_dur), = events["nomad/sched_host"]
    for stage in ("kernel", "plan_submit"):
        (p0, p_dur), = events["nomad/" + stage]
        assert h0 <= p0 and p0 + p_dur <= h0 + h_dur


# -- ring bounding / exemplars under churn -----------------------------

def _complete_synthetic(t, ms, eid, spans=5):
    now = time.monotonic()
    tr = EvalTrace(eid, "job", "default", "service", "w",
                   mono0=now - ms / 1000.0, wall0=time.time())
    for _ in range(spans):
        tr.add_span("reconcile", 0.0005)
    t.finish(tr)
    return tr


def test_ring_stays_within_byte_budget_under_churn():
    t = Tracer(ring_bytes=6000, exemplar_slots=0)
    for i in range(200):
        _complete_synthetic(t, 5.0, f"churn-{i}")
    assert t._ring_used <= 6000
    assert t.ring_len() < 200
    assert t.stats["dropped"] > 0
    assert t.stats["traces"] == 200
    # newest survive, oldest aged out
    ids = [d["eval_id"] for d in t.recent(1000)]
    assert ids[-1] == "churn-199"
    assert "churn-0" not in ids


def test_exemplar_worst_k_retention_and_pinning():
    t = Tracer(exemplar_slots=2)
    t.force_threshold_ms = 0.0          # promote everything offered
    _complete_synthetic(t, 10.0, "a")
    _complete_synthetic(t, 20.0, "b")
    _complete_synthetic(t, 30.0, "c")   # displaces a (the fastest)
    ids = {e["eval_id"] for e in t.exemplars()}
    assert ids == {"b", "c"}
    # exemplars sorted worst-first
    assert t.exemplars()[0]["eval_id"] == "c"

    # a pin MOVES the current set to the pinned store, freeing the
    # rolling slots — a drift event must not blind the recorder to
    # tails that develop after it
    assert t.pin_exemplars("drift:service.p99_ms->broker.ready") == 2
    _complete_synthetic(t, 500.0, "d")  # still captured post-pin
    by_id = {e["eval_id"]: e for e in t.exemplars()}
    assert set(by_id) == {"b", "c", "d"}
    assert by_id["b"]["pinned"] and by_id["c"]["pinned"]
    assert "broker.ready" in by_id["b"]["reason"]
    assert not by_id["d"]["pinned"]
    # pinned captures survive slower arrivals indefinitely
    _complete_synthetic(t, 900.0, "e")
    _complete_synthetic(t, 950.0, "f")  # rolling = worst-2 of d/e/f
    ids = {x["eval_id"] for x in t.exemplars()}
    assert {"b", "c", "e", "f"} <= ids and "d" not in ids
    assert t.stats["exemplar_pins"] == 2
    # the pinned store is bounded at 2x slots: pinning the rolling
    # pair fills it (4); further pins are dropped
    assert t.pin_exemplars("again") == 2
    _complete_synthetic(t, 990.0, "g")
    assert t.pin_exemplars("overflow") == 0
    assert t.exemplar_count() == 5      # 4 pinned + 1 rolling


def test_threshold_adapts_to_governor_p99():
    t = Tracer(exemplar_slots=4)
    t.threshold_fn = lambda: 50.0
    t.threshold_pct = 200.0
    assert t.threshold_ms() == 100.0
    _complete_synthetic(t, 40.0, "fast")    # below threshold: dropped
    assert t.exemplar_count() == 0
    _complete_synthetic(t, 150.0, "slow")   # above: promoted
    assert t.exemplar_count() == 1
    assert t.exemplars()[0]["eval_id"] == "slow"
    # forced override wins (the test hook)
    t.force_threshold_ms = 5.0
    assert t.threshold_ms() == 5.0


def test_exemplar_gauge_snapshot_taken_at_completion():
    t = Tracer(exemplar_slots=2)
    t.force_threshold_ms = 0.0
    t.gauge_fn = lambda: {"broker.ready": 7.0}
    _complete_synthetic(t, 10.0, "g")
    ex = t.exemplars()
    assert ex[0]["gauges"] == {"broker.ready": 7.0}


def test_drift_finding_auto_pins_via_server_hook():
    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=3600.0))
    assert srv.governor is not None
    assert srv._auto_pin_exemplars in srv.governor.drift_hooks
    tracer.force_threshold_ms = 0.0
    tracer.finish(_mk_eval_trace("pin-me"))
    assert tracer.exemplar_count() == 1
    finding = {"kind": "drift", "metric": "service.p99_ms",
               "ratio": 2.0, "suspect_structure": "broker.ready"}
    for hook in list(srv.governor.drift_hooks):
        hook(finding)
    ex = tracer.exemplars()
    assert ex and all(e["pinned"] for e in ex)
    assert "broker.ready" in ex[0]["reason"]
    assert any(e.get("kind") == "trace_pin"
               for e in srv.governor.events())
    # findings without a suspect pin nothing
    before = tracer.stats["exemplar_pins"]
    srv._auto_pin_exemplars({"kind": "drift", "metric": "x"})
    assert tracer.stats["exemplar_pins"] == before


def test_sample_once_invokes_drift_hooks(monkeypatch):
    from nomad_tpu.governor import Governor
    gov = Governor(drift_check_every=1)
    seen = []
    gov.drift_hooks.append(seen.append)
    monkeypatch.setattr(
        gov.drift, "check",
        lambda: [{"kind": "drift", "metric": "m",
                  "suspect_structure": "s"}])
    gov.sample_once()
    assert seen and seen[0]["suspect_structure"] == "s"


# -- kill switch / context plumbing ------------------------------------

def test_env_kill_switch_disarms_everything(monkeypatch):
    stages.disable()
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    tracer.refresh()
    assert not tracer.enabled()
    # no bench collection + no tracing => report sites see one False
    assert not stages.enabled
    class Ev:
        id = "x"
        job_id = "j"
        namespace = "d"
        type = "service"
        queue_wait_s = 0.0
    assert tracer.begin(Ev(), track="w") is None
    # a Server constructed under the kill switch stays dark
    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=3600.0))
    assert not srv.tracer.enabled()
    monkeypatch.delenv("NOMAD_TPU_TRACE")
    tracer.refresh()
    assert tracer.enabled()
    assert stages.enabled       # trace hook re-arms the report sites


def test_use_context_nests_and_restores():
    t1 = _mk_eval_trace("outer")
    t2 = _mk_eval_trace("inner")
    assert trace.current() is None
    with trace.use(t1):
        assert trace.current() is t1
        with trace.use_many([t1, t2], track="gateway"):
            assert set(trace.current_all()) == {t1, t2}
        assert trace.current() is t1
    assert trace.current() is None


def test_span_cap_bounds_a_runaway_eval():
    from nomad_tpu.trace.tracer import MAX_SPANS_PER_TRACE
    tr = _mk_eval_trace("runaway")
    for _ in range(MAX_SPANS_PER_TRACE + 50):
        tr.add_span("reconcile", 0.001)
    assert len(tr.spans) == MAX_SPANS_PER_TRACE
    # begin() spent one slot on queue_wait: 51 appends bounced
    assert tr.truncated == 51
    d = tr.to_dict()
    assert d["truncated_spans"] == tr.truncated


# -- stages.snapshot() -------------------------------------------------

def test_stages_snapshot_is_seconds_and_calls_for_every_stage():
    """What the accumulators give a test: the summed seconds and the
    call count of every stage of STAGES (and of any other name that
    was reported), and nothing derived from them."""
    stages.enable()
    try:
        stages.add("restore", 3.0)
        stages.add("kernel", 1.0)
        stages.add("kernel", 0.5)
        stages.add("not_a_stage", 0.25)
        snap = stages.snapshot()
    finally:
        stages.disable()
    assert set(snap) == set(stages.STAGES) | {"not_a_stage"}
    assert all(set(row) == {"seconds", "calls", "cpu_seconds"}
               for row in snap.values())
    # after-the-fact reports bring no CPU reading (ISSUE 36)
    assert snap["restore"] == {"seconds": 3.0, "calls": 1,
                               "cpu_seconds": 0.0}
    assert snap["kernel"] == {"seconds": 1.5, "calls": 2,
                              "cpu_seconds": 0.0}
    assert snap["not_a_stage"] == {"seconds": 0.25, "calls": 1,
                                   "cpu_seconds": 0.0}
    assert snap["queue_wait"] == {"seconds": 0.0, "calls": 0,
                                  "cpu_seconds": 0.0}
    stages.enable()                     # enable() starts from nothing
    try:
        assert not any(row["calls"] for row in stages.snapshot().values())
    finally:
        stages.disable()


# -- HTTP / CLI surface ------------------------------------------------

def test_http_route_and_cli_surface(tmp_path):
    from nomad_tpu.api import ApiClient, HTTPApiServer

    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=3600.0))
    tracer.force_threshold_ms = 0.0
    tr = _mk_eval_trace("http-ev")
    tr.add_span("reconcile", 0.001)
    tracer.finish(tr)
    api = HTTPApiServer(srv, port=0)
    api.start()
    try:
        c = ApiClient(f"http://127.0.0.1:{api.port}")
        out = c.trace()
        assert out["enabled"] is True
        assert out["ring"]["traces"] >= 1
        assert out["ring"]["bytes_max"] == srv.config.trace_ring_bytes
        assert any(t["eval_id"] == "http-ev" for t in out["recent"])
        assert out["exemplars"] and "stage_percentiles" in out
        only_ex = c.trace({"exemplars": "true"})
        assert "recent" not in only_ex
        chrome = c.trace({"format": "chrome"})
        assert chrome["traceEvents"]
        assert {e["ph"] for e in chrome["traceEvents"]} <= {"X", "M"}

        # governor carries the recorder gauges
        names = [g["name"] for g in c.governor()["gauges"]]
        assert "trace.ring_traces" in names
        assert "trace.exemplars" in names

        # the CLI renders both forms
        from nomad_tpu.cli.main import main as cli_main
        rc = cli_main(["-address", f"http://127.0.0.1:{api.port}",
                       "operator", "trace"])
        assert rc == 0
        out_file = str(tmp_path / "trace.json")
        rc = cli_main(["-address", f"http://127.0.0.1:{api.port}",
                       "operator", "trace", "-exemplars",
                       "-o", "chrome", "-output", out_file])
        assert rc == 0
        with open(out_file) as f:
            payload = json.load(f)
        assert payload["traceEvents"]
    finally:
        api.shutdown()


def test_to_chrome_handles_empty_and_minimal():
    assert to_chrome([]) == {"traceEvents": [],
                             "displayTimeUnit": "ms"}
    out = to_chrome([{"eval_id": "e", "track": "w", "start": 1.0,
                      "total_ms": 2.0, "spans": []}])
    assert len(out["traceEvents"]) == 2     # thread_name + root


# -- what the recorder does per eval, as counts ------------------------

# a placing eval of one task group that is not retried reports every
# stage under the eval at most once, h2d up to three times (the
# refresh's scatter, a first upload, a mask park)
SPANS_PER_EVAL_MAX = sum(
    1 for parent in STAGE_PARENTS.values() if parent is not None) + 2


def test_recorder_appends_a_bounded_count_of_tuples_per_eval(
        served, monkeypatch):
    """The recorder's cost per eval, in what repeats exactly: how many
    spans a served eval appends and the ring bytes that stands for;
    that add_span appends one tuple and builds nothing (the dicts are
    made on reading); that warm evals of one shape append the same
    spans every time, and with the switch off none. (The wall-clock
    twin below is marked slow.)"""
    from nomad_tpu.trace.tracer import SPAN_EST_BYTES, TRACE_EST_BYTES

    # 31 until plan_commit's four children (ISSUE 36: raft_lock_wait,
    # wal_write, fsm_apply, event_publish)
    assert SPANS_PER_EVAL_MAX == 35
    for t in served["traces"]:
        names = collections.Counter(s["name"] for s in t["spans"])
        assert set(names) <= {n for n, p in STAGE_PARENTS.items()
                              if p is not None}
        assert all(c <= (3 if n == "h2d" else 1)
                   for n, c in names.items()), names
        assert 20 <= len(t["spans"]) <= SPANS_PER_EVAL_MAX
        assert "truncated_spans" not in t
    # what such an eval holds of the ring (4 MiB: 653 of the largest)
    assert TRACE_EST_BYTES + SPAN_EST_BYTES * SPANS_PER_EVAL_MAX == 6416

    tr = _mk_eval_trace("ev-count")
    before, n0 = tracer.stats["spans"], len(tr._raw)
    attrs = {"arm": "kway"}
    tr.add_span("kernel", 0.004, attrs=attrs)
    tr.add_span("d2h", 0.001)
    assert len(tr._raw) == n0 + 2
    assert tracer.stats["spans"] == before + 2
    kernel, d2h = tr._raw[-2:]
    assert type(kernel) is tuple and type(d2h) is tuple
    assert kernel[4] is attrs and d2h[4] is None    # no copy, no dict
    assert tr.est_bytes() == TRACE_EST_BYTES + SPAN_EST_BYTES * (n0 + 2)
    assert tr.spans[-2]["attrs"] is attrs and "attrs" not in tr.spans[-1]

    from nomad_tpu.bench.ladder import _eval_for, _seed_nodes
    from nomad_tpu.scheduler.harness import Harness
    h = Harness()
    _seed_nodes(h, 64, dcs=1)

    def one_eval(i):
        job = mock.job()
        job.id = f"count-{i}"
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = 10
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        h.store.upsert_job(h.next_index(), job)
        ev = _eval_for(job)
        before = tracer.stats["spans"]
        tr = tracer.begin(ev, track="count")
        with trace.use(tr):
            h.process("service", ev)
        tracer.finish(tr)
        return tr, tracer.stats["spans"] - before

    one_eval(0)                         # the cold table, the mask
    warm = [one_eval(i) for i in (1, 2, 3)]
    shapes = [collections.Counter(r[0] for r in tr._raw)
              for tr, _n in warm]
    assert shapes[0] == shapes[1] == shapes[2]
    assert all(n == len(tr._raw) == sum(shapes[0].values())
               for tr, n in warm)
    # the harness is no worker: no sched_host, plan_submit or ack
    assert sum(shapes[0].values()) == 12

    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    tracer.refresh()
    tr, n = one_eval(4)
    assert tr is None and n == 0


# -- overhead smoke ----------------------------------------------------

@pytest.mark.slow
def test_tracing_overhead_within_5pct(monkeypatch):
    """Tracing-on e2e placements/s within 5% of tracing-off at bench
    quick scale (ISSUE 9 acceptance). Measures the bench's e2e shape —
    full scheduler Process() over a seeded store — single-threaded
    through the Harness with a REAL trace context per eval (begin /
    ambient spans / kernel span / finish+promotion all on the clock),
    so the comparison resolves the recorder's cost instead of the
    worker thread-pool's dequeue jitter: a paused-burst Server wall at
    this scale swings ±20% under CI load, 4000x the actual span
    overhead. Interleaved best-of-3 per mode, bounded retries."""
    from nomad_tpu.bench.ladder import _eval_for, _seed_nodes
    from nomad_tpu.scheduler.harness import Harness

    h = Harness()
    # capacity must survive the retry budget: mock nodes hold 7 allocs
    # each ((4000-100 reserved)/500), and warm + three measured phases
    # can place up to 1480 — 200 nodes (cap 1400) ran dry exactly 8
    # evals into a second noise retry (placed 400/480 under full-suite
    # load). 256 keeps the same _pad_n bucket (256) so the measured
    # kernel shape is unchanged while the ceiling rises to 1792.
    _seed_nodes(h, 256, dcs=1)

    def mk_job(tag, i):
        from nomad_tpu import mock as _mock
        job = _mock.job()
        job.id = f"ovh-{tag}-{i}"
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = 10
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        return job

    from nomad_tpu.utils import gcsafe

    def _set_mode(trace_on):
        if trace_on:
            monkeypatch.delenv("NOMAD_TPU_TRACE", raising=False)
        else:
            monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
        tracer.refresh()

    def run_paired(tag, n_pairs=24):
        """PAIRED design: modes alternate eval-by-eval, so the
        workload's own non-stationarity (the store grows and caches
        warm as evals run — measured drift between sequential phases
        reaches 50%, 15x the recorder's real cost) hits both classes
        identically; medians are outlier-robust (one GC/preemption
        must not decide a 5% verdict) and collector pauses park
        between evals exactly like the bench's timed windows. Returns
        (on_median_s, off_median_s)."""
        placed_before = len(h.plans)
        times = {True: [], False: []}
        with gcsafe.safepoints():
            for i in range(2 * n_pairs):
                trace_on = (i % 2 == 0)
                _set_mode(trace_on)
                job = mk_job(tag, i)
                h.store.upsert_job(h.next_index(), job)
                ev = _eval_for(job)
                t0 = time.perf_counter()
                tr = tracer.begin(ev, track="bench")
                with trace.use(tr):
                    h.process("service", ev)
                tracer.finish(tr)
                times[trace_on].append(time.perf_counter() - t0)
                gcsafe.safepoint()
        placed = sum(
            sum(len(a) for a in p.node_allocation.values())
            for p in h.plans[placed_before:])
        assert placed == 2 * n_pairs * 10

        def median(v):
            v = sorted(v)
            return v[len(v) // 2]

        return median(times[True]), median(times[False])

    _set_mode(True)
    run_paired("warm", n_pairs=2)           # compile + caches

    on, off = run_paired("m0")
    for attempt in range(2):
        if on <= off / 0.95:
            break
        on2, off2 = run_paired(f"m{attempt + 1}")   # noise retry
        on, off = min(on, on2), min(off, off2)
    # placements/s per eval = count/median: within 5% <=> medians
    # within 1/0.95
    assert on <= off / 0.95, (
        f"tracing-on median {on * 1e3:.2f} ms/eval vs off "
        f"{off * 1e3:.2f} ms/eval")
