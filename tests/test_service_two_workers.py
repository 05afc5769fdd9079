"""Two scheduler workers on the svc-10k deployment at toy size (ISSUE
27): both rank the whole fleet — an ask under 256 instances takes no
slice of it — so they choose the same nodes and the applier refuses the
later plan. The committed nodes are held to the plain reference all the
same, plan by plan in commit order: a refused plan of a node-coupling
ask commits nothing and is ranked again, whole, against the state that
refused it. tests/test_service_parity.py has the one-worker cases."""
import json
import os
import threading
import time

import pytest

from benchmark.lib import agent as agentlib
from benchmark.lib import client, fleet as fleetlib, reference, traffic
from nomad_tpu.server.worker import EvalLane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483777
NODES = 640
WAIT_S = 120.0


@pytest.fixture(scope="module")
def served():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "svc-10k.json")) as f:
        cfg = json.load(f)
    assert cfg["server"]["num_schedulers"] == 2
    mix = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        "service-fill.json"))
    fleet = fleetlib.build_fleet(cfg, SEED, NODES)
    agent = agentlib.Agent(cfg, lambda _msg: None)
    addr = agent.boot()
    try:
        assert agent.load(fleet)["nodes"] == NODES
        http = client.Http(addr)
        used = {nid: dict(row) for nid, row in
                fleetlib.backlog_usage(cfg, fleet).items()}
        yield {"cfg": cfg, "mix": mix, "fleet": fleet, "http": http,
               "used": used, "dcs": traffic.datacenters_of(cfg),
               "agent": agent}
        http.close()
    finally:
        agent.close()


def _put_and_read(served, jobs):
    """One bulk PUT; every job's allocations once all are placed, each
    in instance order."""
    http = served["http"]
    sent = client.Sent(traffic.Request(jobs))
    client.put_jobs(http, sent)
    assert sent.status == 200, sent.error
    out = {}
    deadline = time.time() + WAIT_S
    for job in jobs:
        rows = []
        while time.time() < deadline:
            status, rows = http.request(
                "GET", f"/v1/job/{job['id']}/allocations")
            if status == 200 and len(rows) == job["count"]:
                break
            time.sleep(0.02)
        assert len(rows) == job["count"], (job["id"], len(rows))
        assert all(a["desired_status"] == "run" for a in rows)
        out[job["id"]] = sorted(
            rows, key=lambda a: int(a["name"].rsplit("[", 1)[1][:-1]))
    return out


def _widest_gap(served, job, rows):
    """The job's committed nodes in instance order against the fleet as
    `used` has it: the widest gap of a chosen node's coupled score below
    the best node's with room at that step (float64). Carries the job's
    usage into `used`."""
    by_id = {n["id"]: n for n in served["fleet"]}
    scorer = reference.PlainScorer(served["fleet"], job, served["used"])
    widest = 0.0
    for alloc in rows:
        node = by_id[alloc["node_id"]]
        assert scorer.fits(node), (job["id"], node["name"])
        best = max(scorer.score(n) for n in scorer.nodes if scorer.fits(n))
        widest = max(widest, best - scorer.score(node))
        scorer.place(node)
    for alloc in rows:
        for d in fleetlib.DIMS:
            served["used"][alloc["node_id"]][d] += job["ask"][d]
    return widest


def test_the_plan_that_lost_the_race_is_ranked_again_whole(served,
                                                           monkeypatch):
    """Two jobs of 50 in one PUT, a worker each, on a rack that two
    earlier jobs of 50 have part filled (a job's own anti-affinity
    spreads it thin: 1-3 instances a node, of the 7 a 1x node has room
    for). Both rank the same fleet (held at the gate until both have),
    so both want the same nodes; the first plan commits, the second is
    refused on the nodes the first filled — and keeps none of the
    others."""
    for k in "ab":                      # one at a time: nothing races
        fill = traffic.plain_job(served["mix"], f"fill-{k}", 50,
                                 served["dcs"])
        rows = _put_and_read(served, [fill])
        assert _widest_gap(served, fill, rows[fill["id"]]) \
            <= reference.TIE_EPS
    first, second = (traffic.plain_job(served["mix"], f"race-{k}", 50,
                                       served["dcs"]) for k in "ab")
    both_ranked = threading.Barrier(2)
    first_committed = threading.Event()
    lock = threading.Lock()
    order, submits = [], {first["id"]: [], second["id"]: []}
    real = EvalLane.submit_plan

    def gated(lane, plan):
        jid = plan.job.id if plan.job is not None else None
        if jid not in submits:
            return real(lane, plan)
        with lock:
            opening = not submits[jid]
            if opening:
                order.append(jid)
            submits[jid].append(None)
            mine = len(submits[jid]) - 1
        if opening:
            both_ranked.wait(timeout=WAIT_S)
            if jid != order[0]:
                assert first_committed.wait(timeout=WAIT_S)
        result = real(lane, plan)
        _full, expected, actual = result.full_commit(plan)
        submits[jid][mine] = (expected, actual, plan.all_at_once)
        if opening and jid == order[0]:
            first_committed.set()
        return result

    monkeypatch.setattr(EvalLane, "submit_plan", gated)
    rows = _put_and_read(served, [first, second])
    jobs = {j["id"]: j for j in (first, second)}
    won, lost = order
    # the allocations are readable as the applier commits; the worker
    # that submitted notes its result a thread switch later
    deadline = time.time() + WAIT_S
    while None in submits[lost] and time.time() < deadline:
        time.sleep(0.02)

    assert submits[won] == [(50, 50, True)]
    # refused whole, though most of its nodes still had room for it ...
    assert submits[lost][0] == (50, 0, True)
    # ... and ranked again against the state that refused it
    assert submits[lost][-1] == (50, 50, True)
    for jid in (won, lost):
        assert len({a["create_index"] for a in rows[jid]}) == 1
    assert rows[won][0]["create_index"] < rows[lost][0]["create_index"]
    for jid in (won, lost):             # commit order
        gap = _widest_gap(served, jobs[jid], rows[jid])
        assert gap <= reference.TIE_EPS, (jid, gap)
    assert not reference.check_spread(served["fleet"], [first, second], rows)
    assert not reference.check_capacity(served["fleet"], served["used"])


def test_eight_jobs_in_flight_commit_whole_and_none_fails(served):
    """The cell's shape: eight jobs of mixed sizes in flight over two
    workers, nothing held back. Every job lands in ONE plan (a refused
    plan left nothing behind), no eval fails however many races it lost,
    and in commit order no chosen node is a machine class off the best
    (a plan that commits between another's ranking and its commit can
    leave that one a few instances' worth of bin-pack off: read)."""
    sizes = [1, 1, 2, 3, 5, 10, 20, 50] * 2
    jobs = [traffic.plain_job(served["mix"], f"flight-{k}", c, served["dcs"])
            for k, c in enumerate(sizes)]
    rows = {}
    for lo in range(0, len(jobs), 8):
        rows.update(_put_and_read(served, jobs[lo:lo + 8]))
    for job in jobs:
        assert len({a["create_index"] for a in rows[job["id"]]}) == 1, \
            job["id"]
    in_order = sorted(jobs, key=lambda j: rows[j["id"]][0]["create_index"])
    widest = max(_widest_gap(served, j, rows[j["id"]]) for j in in_order)
    assert widest < 0.1, widest         # a machine class is 0.19 and more
    assert not reference.check_spread(served["fleet"], jobs, rows)
    assert not reference.check_capacity(served["fleet"], served["used"])
    full = []
    for job in jobs[:4]:
        for a in rows[job["id"]]:
            status, body = served["http"].request(
                "GET", f"/v1/allocation/{a['id']}")
            assert status == 200
            full.append(body)
    assert not reference.check_ports(
        full, tuple(served["cfg"]["dynamic_port_range"]))
    assert served["agent"].worker_failures() == 0
    srv = served["agent"].srv
    evals = [e for j in jobs for e in srv.store.evals_by_job("default",
                                                             j["id"])]
    assert evals and all(e.status == "complete" for e in evals), \
        [(e.job_id, e.status) for e in evals if e.status != "complete"]
