"""The served path against the plain reference on the svc-10k
deployment at toy size (ISSUE 27): node for node, or equal in coupled
score. The benchmark's judge does not rank jobs that carry a spread or
an affinity (their score couples the nodes), so this is where the
affinity column and the spread term are held to the reference — the
test fails with either left out.

The fleet is benchmark.lib.fleet's, from svc-10k.json, 640 nodes with
their 40 resident allocs; the job is the mix's own template; one
scheduler worker, one job at a time, so nothing is concurrent and the
reference's greedy is the answer."""
import json
import os
import time

import pytest

from benchmark.lib import agent as agentlib
from benchmark.lib import client, fleet as fleetlib, reference, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483777
NODES = 640


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served():
    cfg = _load("configs", "svc-10k.json")
    cfg["server"] = dict(cfg["server"], num_schedulers=1)
    mix = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        "service-fill.json"))
    fleet = fleetlib.build_fleet(cfg, SEED, NODES)
    agent = agentlib.Agent(cfg, lambda _msg: None)
    addr = agent.boot()
    try:
        loaded = agent.load(fleet)
        assert loaded["nodes"] == NODES and loaded["rows_in_id_order"]
        http = client.Http(addr)
        used = {nid: dict(row) for nid, row in
                fleetlib.backlog_usage(cfg, fleet).items()}
        yield {"cfg": cfg, "mix": mix, "fleet": fleet, "http": http,
               "used": used, "dcs": traffic.datacenters_of(cfg)}
        http.close()
    finally:
        agent.close()


def _register_and_read(served, job):
    """PUT the job, wait for every instance, return its allocations in
    instance order (the reconciler names them in placement order)."""
    http = served["http"]
    sent = client.Sent(traffic.Request([job]))
    client.put_jobs(http, sent)
    assert sent.status == 200, sent.error
    deadline = time.time() + 120.0
    rows = []
    while time.time() < deadline:
        status, rows = http.request("GET",
                                    f"/v1/job/{job['id']}/allocations")
        if status == 200 and len(rows) == job["count"]:
            break
        time.sleep(0.02)
    assert len(rows) == job["count"], (job["id"], len(rows))
    assert all(a["desired_status"] == "run" for a in rows)
    return sorted(rows, key=lambda a: int(a["name"].rsplit("[", 1)[1][:-1]))


def _hold_to_the_reference(served, job):
    """Each instance's committed node against the reference's greedy on
    the same fleet and usage: the same node, or one whose coupled score
    (bin-pack, job anti-affinity, affinity, spread; float64) is within
    TIE_EPS of the best node's at that step. Then the job's usage is
    carried to the next."""
    fleet, used = served["fleet"], served["used"]
    by_id = {n["id"]: n for n in fleet}
    rows = _register_and_read(served, job)
    greedy = reference.PlainScorer(fleet, job, used).greedy(job["count"])
    scorer = reference.PlainScorer(fleet, job, used)
    feasible = {n["id"] for n in scorer.nodes}
    same = 0
    for step, (alloc, want) in enumerate(zip(rows, greedy)):
        node = by_id[alloc["node_id"]]
        assert node["id"] in feasible, (job["id"], step, node["name"])
        assert scorer.fits(node)
        best = max(scorer.score(n) for n in scorer.nodes if scorer.fits(n))
        got = scorer.score(node)
        assert got >= best - reference.TIE_EPS, (
            f"{job['id']} instance {step}: {node['name']} "
            f"({node['datacenter']}, {node['meta']['rack']}) scores "
            f"{got:.6f}, the reference's best {best:.6f} "
            f"({want['name']}, {want['datacenter']}, "
            f"{want['meta']['rack']})")
        same += node["id"] == want["id"]
        scorer.place(node)
    for alloc in rows:
        for d in fleetlib.DIMS:
            used[alloc["node_id"]][d] += job["ask"][d]
    assert not reference.check_spread(fleet, [job], {job["id"]: rows})
    return rows, same


@pytest.mark.parametrize("count", [1, 2, 3, 5, 10, 20, 50])
def test_each_deck_size_lands_where_the_reference_puts_it(served, count):
    assert count in served["mix"]["deck"]
    job = traffic.plain_job(served["mix"], f"parity-solo-{count}", count,
                            served["dcs"])
    assert job["spreads"] and job["affinities"] and job["dynamic_ports"] == 2
    rows, same = _hold_to_the_reference(served, job)
    # the affinity's rack and the spread's targets show in the answer
    by_id = {n["id"]: n for n in served["fleet"]}
    in_r3 = sum(by_id[a["node_id"]]["meta"]["rack"] == "r3" for a in rows)
    assert in_r3 == count if count <= 20 else in_r3 >= 20   # r3: 40 nodes
    assert same >= count - 1        # float32 against float64: a tie or so


def test_three_jobs_in_a_row_carry_usage_rack_fill_and_ports(served):
    """Usage, the affinity rack's fill and the ports taken carry from
    job to job: 3 x 20 instances want the 40 nodes of rack r3."""
    jobs = [traffic.plain_job(served["mix"], f"parity-row-{k}", 20,
                              served["dcs"]) for k in range(3)]
    full = []
    for job in jobs:
        rows, _same = _hold_to_the_reference(served, job)
        for a in rows:
            status, body = served["http"].request(
                "GET", f"/v1/allocation/{a['id']}")
            assert status == 200
            full.append(body)
    shared = {a["node_id"] for a in full}
    assert len(shared) < len(full)      # nodes taken by more than one
    assert not reference.check_ports(
        full, tuple(served["cfg"]["dynamic_port_range"]))
    ports = [p["value"] for a in full
             for t in a["allocated_resources"]["tasks"].values()
             for nw in t["networks"] for p in nw["dynamic_ports"]]
    assert len(ports) == 2 * len(full)
    assert not reference.check_capacity(served["fleet"], served["used"])
