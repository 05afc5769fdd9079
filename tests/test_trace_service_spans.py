"""The three stages a constrained, spread, affinity-weighted,
port-asking service eval adds to the span tree (ISSUE 27): mask_build
under feasibility, spread_inputs under select_prep, port_assign under
select_finish — each in the parent map, reported once per occurrence
inside its parent, and none of them a direct child of sched_host, whose
children and self still sum to it."""
import json
import os
import time

import pytest

from benchmark.lib import agent as agentlib
from benchmark.lib import client, fleet as fleetlib, traffic
from nomad_tpu.trace import AMBIENT_STAGES, STAGE_PARENTS, tracer
from nomad_tpu.utils import stages

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = {"mask_build": "feasibility", "spread_inputs": "select_prep",
       "port_assign": "select_finish"}
COUNTS = [5, 3, 1]


class _Tap:
    def __init__(self):
        self.reports = []
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


@pytest.fixture(scope="module")
def served():
    """Three jobs of the service-fill template, one after another,
    through an agent with one worker on 64 nodes of svc-10k's fleet."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "svc-10k.json")) as f:
        cfg = json.load(f)
    cfg["server"] = dict(cfg["server"], num_schedulers=1)
    mix = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        "service-fill.json"))
    tracer.reset()
    agent = agentlib.Agent(cfg, lambda _msg: None)
    addr = agent.boot()
    tap = _Tap()        # a Server's construction re-arms the recorder
    try:
        agent.load(fleetlib.build_fleet(cfg, 27, 64))
        http = client.Http(addr)
        for k, count in enumerate(COUNTS):
            job = traffic.plain_job(mix, f"svcspan-{k}", count,
                                    traffic.datacenters_of(cfg))
            sent = client.Sent(traffic.Request([job]))
            client.put_jobs(http, sent)
            assert sent.status == 200, sent.error
            deadline = time.time() + 60.0
            while time.time() < deadline:
                _s, rows = http.request(
                    "GET", f"/v1/job/{job['id']}/allocations")
                if len(rows) == count:
                    break
                time.sleep(0.01)
            assert len(rows) == count
        time.sleep(0.3)                 # the deferred acks
        http.close()
    finally:
        agent.close()
        tap.close()
    traces = sorted((t for t in tracer.recent(50)
                     if t["job_id"].startswith("svcspan-")),
                    key=lambda t: t["job_id"])
    assert len(traces) == len(COUNTS)
    return {"tap": tap, "traces": traces}


def _of(t, name):
    return [s for s in t["spans"] if s["name"] == name]


@pytest.mark.parametrize("stage", sorted(NEW))
def test_stage_is_in_the_map_under_its_parent(stage):
    assert stage in stages.STAGES and stage in AMBIENT_STAGES
    assert STAGE_PARENTS[stage] == NEW[stage]
    assert STAGE_PARENTS[NEW[stage]] in ("sched_host", "select_prep")


@pytest.mark.parametrize("stage", sorted(NEW))
def test_stage_is_reported_once_per_occurrence_inside_its_parent(
        served, stage):
    tap, traces = served["tap"], served["traces"]
    assert all(s >= 0.0 for _n, s, _a in tap.of(stage))
    for k, t in enumerate(traces):
        spans = _of(t, stage)
        if stage == "mask_build":
            # built for the first job; the table's cache answers after
            assert len(spans) == (1 if k == 0 else 0)
            assert len(_of(t, "feasibility")) == len(_of(t, "select_prep"))
        else:
            assert len(spans) == len(_of(t, NEW[stage])) >= 1
        parents = _of(t, NEW[stage])
        for sp in spans:
            assert sp["parent"] == NEW[stage]
            assert any(p["t0_ms"] - 0.2 <= sp["t0_ms"] and
                       sp["t0_ms"] + sp["dur_ms"]
                       <= p["t0_ms"] + p["dur_ms"] + 0.2
                       for p in parents), (sp, parents)
    assert len(tap.of("mask_build")) == 1
    if stage == "port_assign":
        # one report per select_finish: its winners, two ports each
        got = sorted((a["winners"], a["ports"])
                     for _n, _s, a in tap.of(stage))
        assert got == sorted((c, 2 * c) for c in COUNTS)


def test_children_of_sched_host_and_self_still_sum_to_it(served):
    for t in served["traces"]:
        names = {s["name"] for s in t["spans"]}
        assert {"spread_inputs", "port_assign", "kernel",
                "plan_submit"} <= names
        host, = _of(t, "sched_host")
        end = host["t0_ms"] + host["dur_ms"]
        kids = sum(s["dur_ms"] for s in t["spans"]
                   if s["parent"] == "sched_host"
                   or (s["name"] == "table_build"
                       and host["t0_ms"] <= s["t0_ms"]
                       and s["t0_ms"] + s["dur_ms"] <= end))
        assert kids == pytest.approx(host["dur_ms"], rel=0.02, abs=0.05)
        # the new stages are grandchildren: inside select_prep and
        # select_finish, which already count
        assert not any(s["parent"] == "sched_host" for s in t["spans"]
                       if s["name"] in NEW)


def test_a_group_with_no_affinity_and_no_spread_reports_no_spread_inputs():
    tap = _Tap()
    try:
        with stages.span("spread_inputs") as sp:
            sp.cancel()
        assert not tap.of("spread_inputs")
    finally:
        tap.close()
