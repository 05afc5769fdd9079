"""The plain reference: each guarantee's checker rejects what breaks it,
and the controls come out as not correct."""
import copy
import json
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import fleet as fleetlib  # noqa: E402
from benchmark.lib import reference as ref  # noqa: E402
from benchmark.lib import traffic  # noqa: E402

PORTS = (20000, 32000)


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("configs", "prod-10k.json")


@pytest.fixture(scope="module")
def world(cfg):
    fleet = fleetlib.build_fleet(cfg, 3, 320)
    return fleet, fleetlib.backlog_usage(cfg, fleet)


@pytest.fixture(scope="module")
def mixed(cfg):
    """A fleet that holds every machine class (the first 320 ordinals
    are all of the smallest)."""
    fleet = fleetlib.build_fleet(cfg, 3, 1280)
    return fleet, fleetlib.backlog_usage(cfg, fleet)


def jobs_of(mix_name, counts, seed=1):
    mix = load("traffic", mix_name + ".json")
    dcs = ["dc1", "dc2", "dc3", "dc4"]
    return [traffic.plain_job(mix, f"{mix_name}-{seed}-{i}", c, dcs)
            for i, c in enumerate(counts)]


def answered(world, jobs, broken=None):
    fleet, backlog = world
    plain = ref.PlainScheduler(fleet, backlog, PORTS, broken=broken)
    for j in jobs:
        plain.submit(j)
    return plain


def judged(world, jobs, plain):
    fleet, backlog = world
    return ref.judge(fleet, backlog, jobs, plain.evals, plain.allocs,
                     list(plain.full.values()), [], PORTS, 2)


# -- the fleet -----------------------------------------------------------

def test_fleet_shape_is_the_same_for_every_seed(cfg):
    def shape(seed):
        return sorted((n["name"], n["datacenter"], n["meta"]["rack"],
                       n["class"]) for n in fleetlib.build_fleet(cfg, seed, 640))
    assert shape(1) == shape(2)
    ids1 = [n["id"] for n in fleetlib.build_fleet(cfg, 1, 640)]
    assert ids1 == sorted(ids1)
    assert ids1 != [n["id"] for n in fleetlib.build_fleet(cfg, 2, 640)]


def test_fleet_classes_come_in_the_configured_tenths(cfg):
    fleet = fleetlib.build_fleet(cfg, 1, 6400)
    share = {c: sum(1 for n in fleet if n["class"] == c) / len(fleet)
             for c in ("c1x", "c2x", "c4x")}
    assert share == {"c1x": 0.6, "c2x": 0.3, "c4x": 0.1}
    big = next(n for n in fleet if n["class"] == "c4x")
    assert big["capacity"]["cpu"] == 4 * 4000 - 100
    assert big["capacity"]["memory_mb"] == 4 * 8192 - 256


def test_every_rack_of_every_datacenter_holds_every_class(cfg):
    fleet = fleetlib.build_fleet(cfg, 1, 10000)
    seen = {(n["datacenter"], n["meta"]["rack"], n["class"]) for n in fleet}
    assert len(seen) == 4 * 16 * 3


def test_backlog_leaves_the_room_the_mixes_count_on(cfg, world):
    fleet, backlog = world
    small = next(n for n in fleet if n["class"] == "c1x")
    free_cpu = small["capacity"]["cpu"] - backlog[small["id"]]["cpu"]
    assert free_cpu // 20 == 95 and free_cpu // 250 == 7


# -- the checkers reject what breaks each guarantee ------------------------

def test_whole_reference_is_correct(world):
    jobs = jobs_of("service-stream", [1, 2, 3, 5, 10, 20, 50])
    compared, found = judged(world, jobs, answered(world, jobs))
    assert ref.is_correct(compared), found
    assert set(compared) == set(ref.LIMITS)
    assert all(c["limit"] == 0 for name, c in compared.items()
               if name != "rank_gap")
    assert ref.TIE_EPS < compared["rank_gap"]["limit"] < 0.4


@pytest.mark.parametrize("broken,number,mix,counts", [
    ("capacity", "over_capacity", "service-stream", [50, 50, 50, 50]),
    ("capacity", "over_capacity", "batch-fill", [1000, 1000]),
    ("constraints", "infeasible", "service-stream", [50] * 7),
    ("lose", "lost_or_duplicated", "service-stream", [5, 5, 5, 5]),
    ("lose", "lost_or_duplicated", "batch-fill", [1000]),
    ("ports", "port_conflicts", "service-stream", [50, 50]),
    ("norank", "rank_gap", "batch-fill", [100, 100]),
])
def test_control_is_not_correct(world, mixed, broken, number, mix, counts):
    if broken == "norank":
        world = mixed
    jobs = jobs_of(mix, counts)
    compared, found = judged(world, jobs, answered(world, jobs, broken))
    assert not ref.is_correct(compared)
    assert compared[number]["value"] > compared[number]["limit"], found
    others = {k: v["value"] for k, v in compared.items() if k != number}
    assert not any(others.values()), others


def test_first_fit_stacks_a_job_and_ranks_nothing(mixed):
    world = mixed
    jobs = jobs_of("batch-fill", [100, 100])
    compared, found = judged(world, jobs, answered(world, jobs, "firstfit"))
    assert compared["stacked"]["value"] == 2, found
    assert compared["rank_gap"]["value"] > compared["rank_gap"]["limit"]
    guarantees = {k: v["value"] for k, v in compared.items()
                  if k not in ("stacked", "rank_gap")}
    assert not any(guarantees.values()), guarantees


def test_unknown_control_is_refused(world):
    with pytest.raises(ValueError):
        ref.PlainScheduler(world[0], world[1], PORTS, broken="speed")


def _one(world):
    jobs = jobs_of("service-stream", [5])
    return jobs, answered(world, jobs)


def test_missing_placement_is_rejected(world):
    jobs, plain = _one(world)
    plain.allocs[jobs[0]["id"]].pop()
    assert ref.check_committed(jobs, plain.allocs)


def test_duplicate_placement_is_rejected(world):
    jobs, plain = _one(world)
    rows = plain.allocs[jobs[0]["id"]]
    rows.append(dict(rows[0], id="other-id"))
    assert "duplicated" in ref.check_committed(jobs, plain.allocs)[0]


def test_alloc_id_twice_is_rejected(world):
    jobs, plain = _one(world)
    rows = plain.allocs[jobs[0]["id"]]
    rows[1] = dict(rows[1], id=rows[0]["id"])
    assert any("twice" in b for b in ref.check_committed(jobs, plain.allocs))


def test_stopped_alloc_is_rejected(world):
    jobs, plain = _one(world)
    plain.allocs[jobs[0]["id"]][0]["desired_status"] = "stop"
    assert ref.check_committed(jobs, plain.allocs)


def test_over_commit_is_rejected(world):
    fleet, backlog = world
    jobs = jobs_of("batch-fill", [1000])
    node = next(n for n in fleet if n["class"] == "c1x")
    allocs = {jobs[0]["id"]: [{"id": f"a{i}", "node_id": node["id"],
                               "name": f"x[{i}]"} for i in range(96)]}
    bad = ref.check_capacity(fleet, ref.node_usage(backlog, jobs, allocs))
    assert len(bad) == 1 and "cpu" in bad[0]
    allocs[jobs[0]["id"]].pop()
    assert not ref.check_capacity(fleet,
                                  ref.node_usage(backlog, jobs, allocs))


def test_infeasible_placement_is_rejected(world):
    fleet, _ = world
    jobs = jobs_of("service-stream", [1])
    wrong = next(n for n in fleet if n["meta"]["rack"] == "r12")
    right = next(n for n in fleet if n["meta"]["rack"] == "r3")
    mk = lambda n: {jobs[0]["id"]: [{"id": "a", "node_id": n["id"],  # noqa
                                     "name": "x[0]"}]}
    assert "regexp" in ref.check_feasible(fleet, jobs, mk(wrong))[0]
    assert not ref.check_feasible(fleet, jobs, mk(right))
    assert "unknown node" in ref.check_feasible(
        fleet, jobs, mk({"id": "no-such-node"}))[0]


def test_wrong_datacenter_and_driver_are_rejected(world):
    fleet, _ = world
    job = jobs_of("service-stream", [1])[0]
    node = next(n for n in fleet if n["meta"]["rack"] == "r3")
    assert not ref.node_feasible(node, job)
    assert ref.node_feasible(node, dict(job, datacenters=["dc9"]))
    assert ref.node_feasible(node, dict(job, driver="docker"))


@pytest.mark.parametrize("ports,bad", [
    ([[20000, 20001], [20002, 20003]], 0),
    ([[20000, 20001], [20001, 20003]], 1),
    ([[19999, 20001]], 1),
    ([[32001, 20001]], 1),
])
def test_port_clash_and_range_are_rejected(ports, bad):
    full = [{"name": f"a{i}", "node_id": "n1", "allocated_resources": {
        "tasks": {"web": {"networks": [{"dynamic_ports": [
            {"label": "p", "value": v} for v in vals]}]}}}}
        for i, vals in enumerate(ports)]
    assert len(ref.check_ports(full, PORTS)) == bad


def test_same_port_on_two_nodes_is_fine():
    full = [{"name": f"a{i}", "node_id": f"n{i}", "allocated_resources": {
        "tasks": {"web": {"networks": [{"dynamic_ports": [
            {"label": "p", "value": 20000}]}]}}}} for i in range(2)]
    assert not ref.check_ports(full, PORTS)


@pytest.mark.parametrize("ev,never,unplaced", [
    (None, 1, 0),
    ({"status": "pending"}, 1, 0),
    ({"status": "complete", "failed_tg_allocs": {}}, 0, 0),
    ({"status": "complete", "failed_tg_allocs": {"web": {}}}, 0, 1),
    ({"status": "complete", "blocked_eval": "abc"}, 0, 1),
    ({"status": "failed"}, 0, 1),
])
def test_evals_are_judged_by_what_they_say(ev, never, unplaced):
    jobs = jobs_of("service-stream", [1])
    n, u = ref.check_evals(jobs, {jobs[0]["id"]: ev} if ev else {})
    assert (len(n), len(u)) == (never, unplaced)


def test_port_sample_takes_whole_nodes_within_budget(world):
    jobs = jobs_of("service-stream", [50, 50, 20])
    plain = answered(world, jobs)
    ids = ref.port_sample(world[0], jobs, plain.allocs, 30,
                          random.Random(1))
    assert 0 < len(ids) <= 30
    by_node = {}
    for rows in plain.allocs.values():
        for a in rows:
            by_node.setdefault(a["node_id"], []).append(a["id"])
    picked = set(ids)
    for node_ids in by_node.values():
        assert not picked & set(node_ids) or set(node_ids) <= picked


# -- the ranking -----------------------------------------------------------

def test_greedy_prefers_the_affinity_rack_and_spreads_datacenters(world):
    fleet, backlog = world
    job = jobs_of("service-stream", [10])[0]
    nodes = ref.PlainScorer(fleet, job, backlog).greedy(10)
    assert all(n["meta"]["rack"] == "r3" for n in nodes)
    assert len({n["id"] for n in nodes}) == 10      # job anti-affinity
    per_dc = {}
    for n in nodes:
        per_dc[n["datacenter"]] = per_dc.get(n["datacenter"], 0) + 1
    assert len(per_dc) == 4 and max(per_dc.values()) <= 4


def test_batch_greedy_packs_small_nodes_and_spreads_the_job(world):
    fleet, backlog = world
    job = jobs_of("batch-fill", [200])[0]
    nodes = ref.PlainScorer(fleet, job, backlog).greedy(200)
    # bin-packing prefers the fullest (smallest) class; the job's own
    # anti-affinity sends each placement to a node it is not on yet
    assert nodes[0]["class"] == "c1x"
    assert len({n["id"] for n in nodes}) == 200


def test_heap_greedy_equals_rescoring_every_node(world):
    fleet, backlog = world
    job = jobs_of("batch-fill", [120])[0]
    fast = [n["name"] for n in
            ref.PlainScorer(fleet, job, backlog).greedy(120)]
    slow_scorer = ref.PlainScorer(fleet, job, backlog)
    slow = []
    for _ in range(120):
        best = max((n for n in slow_scorer.nodes if slow_scorer.fits(n)),
                   key=lambda n: (slow_scorer.score(n),
                                  -slow_scorer.nodes.index(n)))
        slow_scorer.place(best)
        slow.append(best["name"])
    assert fast == slow


def test_plain_scheduler_carries_usage_from_job_to_job(world):
    jobs = jobs_of("batch-fill", [1, 1])
    plain = answered(world, jobs)
    a = plain.allocs[jobs[0]["id"]][0]["node_id"]
    b = plain.allocs[jobs[1]["id"]][0]["node_id"]
    # the second job sees the first one's placement: that node is now
    # the fullest, and a new job has no anti-affinity against it
    assert a == b
    assert plain.used[a]["cpu"] == 2000 + 2 * 20


def _batch_answer(world, counts):
    jobs = jobs_of("batch-fill", counts)
    return jobs, answered(world, jobs)


def test_rank_gap_is_nought_for_the_reference_s_own_greedy(world):
    fleet, backlog = world
    jobs, plain = _batch_answer(world, [100, 60, 100])
    stacked, gap, widest = ref.check_rank(fleet, backlog, jobs,
                                          plain.allocs, 1)
    assert (stacked, gap, widest) == ([], 0.0, [])


def test_rank_gap_reads_the_score_a_worse_node_gives_away(mixed):
    fleet, backlog = mixed
    jobs, plain = _batch_answer(mixed, [100])
    taken = {a["node_id"] for a in plain.allocs[jobs[0]["id"]]}
    big = next(n for n in fleet if n["class"] == "c4x"
               and n["id"] not in taken)
    plain.allocs[jobs[0]["id"]][7]["node_id"] = big["id"]
    _stacked, gap, widest = ref.check_rank(fleet, backlog, jobs,
                                           plain.allocs, 1)
    scorer = ref.PlainScorer(fleet, jobs[0], backlog)
    small = next(n for n in fleet if n["class"] == "c1x")
    assert gap == pytest.approx(scorer.score(small) - scorer.score(big))
    assert len(widest) == 1 and jobs[0]["id"] in widest[0]


def test_rank_gap_allows_each_lane_its_own_best(world):
    fleet, backlog = world
    jobs, plain = _batch_answer(world, [50, 50])
    # the second job as a second scheduler would place it: on untouched
    # small nodes instead of the 50 the first job has just raised
    first = {a["node_id"] for a in plain.allocs[jobs[0]["id"]]}
    fresh = [n for n in fleet if n["class"] == "c1x"
             and n["id"] not in first]
    for a, n in zip(plain.allocs[jobs[1]["id"]], fresh):
        a["node_id"] = n["id"]
    one = ref.check_rank(fleet, backlog, jobs, plain.allocs, 1)[1]
    two = ref.check_rank(fleet, backlog, jobs, plain.allocs, 2)[1]
    assert one > ref.TIE_EPS and two == 0.0


def test_rank_follows_the_store_s_commit_order(world):
    fleet, backlog = world
    jobs, plain = _batch_answer(world, [50, 50])
    assert ref.check_rank(fleet, backlog, jobs, plain.allocs, 1)[1] == 0.0
    # read back in the other order, the create_index still says which
    # plan saw which
    assert ref.check_rank(fleet, backlog, jobs[::-1], plain.allocs,
                          1)[1] == 0.0


def test_stacking_is_a_fault_only_where_the_reference_would_not(world):
    fleet, backlog = world
    jobs, plain = _batch_answer(world, [40])
    got = plain.allocs[jobs[0]["id"]]
    got[1]["node_id"] = got[0]["node_id"]
    stacked, _gap, _w = ref.check_rank(fleet, backlog, jobs, plain.allocs, 1)
    assert len(stacked) == 1 and "40 allocs on 39 nodes" in stacked[0]
    # more instances than the fleet has nodes: stacking is the answer
    jobs, plain = _batch_answer(world, [400])
    assert len({a["node_id"] for a in plain.allocs[jobs[0]["id"]]}) < 400
    assert ref.check_rank(fleet, backlog, jobs, plain.allocs, 1)[0] == []


def test_spread_over_its_target_is_rejected(world):
    fleet, backlog = world
    jobs = jobs_of("service-stream", [10])
    plain = answered(world, jobs)
    assert ref.check_spread(fleet, jobs, plain.allocs) == []
    dc1 = next(n for n in fleet if n["datacenter"] == "dc1")
    for a in plain.allocs[jobs[0]["id"]][:5]:
        a["node_id"] = dc1["id"]        # 40% of 10 allows 4 on dc1
    bad = ref.check_spread(fleet, jobs, plain.allocs)
    assert len(bad) == 1 and "on dc1" in bad[0]


def test_binpack_scores_equal_the_plain_scorer_s(world):
    import numpy as np
    fleet, backlog = world
    job = jobs_of("batch-fill", [1])[0]
    scorer = ref.PlainScorer(fleet, job, backlog)
    cap = np.array([[n["capacity"][d] for d in fleetlib.DIMS]
                    for n in fleet], dtype=float)
    used = np.array([[backlog[n["id"]][d] for d in fleetlib.DIMS]
                     for n in fleet], dtype=float)
    ask = np.array([job["ask"][d] for d in fleetlib.DIMS], dtype=float)
    got = ref.binpack_scores(cap, used, ask)
    assert got == pytest.approx([scorer.score(n) for n in fleet], abs=1e-12)


def test_judge_takes_nothing_from_the_program():
    src = open(os.path.join(ROOT, "benchmark", "lib", "reference.py")).read()
    assert "nomad_tpu" not in src.split('"""', 2)[2]
    src = open(os.path.join(ROOT, "benchmark", "lib", "fleet.py")).read()
    assert "import jax" not in src and "from nomad_tpu" not in src


# -- a plan is judged against the share that ranked it ---------------------

LANES = 2


@pytest.fixture(scope="module")
def rule(cfg):
    return cfg["server"]["decorrelation"]


def test_the_stated_rule_gives_the_program_s_lanes(cfg, rule):
    """The configuration's rule against ops/select.decorrelation_slice
    on the cell's padded table: the two cannot drift apart unseen."""
    import types
    import numpy as np
    from nomad_tpu.ops.select import decorrelation_slice
    n = 16384
    req = types.SimpleNamespace(
        feasible=np.ones(n, bool), count=1000,
        capacity=np.full((n, 4), 4000.0), used=np.zeros((n, 4)),
        ask=np.array([20.0, 32.0, 0.0, 0.0]))
    lanes = cfg["server"]["num_schedulers"]
    mine = ref.lane_ids(n, lanes, rule)
    for lane in range(lanes):
        mask, (key, theirs) = decorrelation_slice(req, lane, lanes,
                                                  (None, None))
        assert key == (n, lanes) and np.array_equal(theirs, mine)
        assert np.array_equal(mask, mine == lane)
    assert sorted(np.bincount(mine)) == [8192, 8192]
    assert rule["min_count"] == 256 and rule["headroom_factor"] == 2
    # below the rule's size the program slices nothing (worker._solo,
    # SelectKernel._decorrelate_mask: `req.count < 256`)
    import inspect
    import nomad_tpu.ops.select as sel
    assert f"req.count < {rule['min_count']}" in inspect.getsource(
        sel.SelectKernel._decorrelate_mask)


@pytest.fixture(scope="module")
def exhausted(cfg, rule):
    """5,120 nodes of every class; lane 0 has no 1x node with room left
    (its share's generations are spent), lane 1's are untouched. One
    job of 600 ranked by the program's K-way arm as scheduler 0."""
    fleet = fleetlib.build_fleet(cfg, 11, 5120)
    backlog = fleetlib.backlog_usage(cfg, fleet)
    lane_of = ref.lane_ids(len(fleet), LANES, rule)
    for i, n in enumerate(fleet):
        if n["class"] == "c1x" and lane_of[i] == 0:
            backlog[n["id"]] = dict(backlog[n["id"]],
                                    cpu=n["capacity"]["cpu"])
    job, = jobs_of("batch-fill", [600])
    return fleet, backlog, job, ranked_by_lane_0(fleet, backlog, job), lane_of


def ranked_by_lane_0(fleet, backlog, job):
    """The job's one plan as the program's K-way arm ranks it for
    scheduler 0 of two."""
    import numpy as np
    import nomad_tpu.ops.select as sel
    dims = fleetlib.DIMS
    req = sel.SelectRequest(
        ask=np.array([job["ask"][d] for d in dims], np.float32),
        count=job["count"], feasible=np.ones(len(fleet), bool),
        capacity=np.array([[n["capacity"][d] for d in dims]
                           for n in fleet], np.float32),
        used=np.array([[backlog[n["id"]][d] for d in dims]
                       for n in fleet], np.float32),
        desired_count=float(job["count"]),
        tg_collisions=np.zeros(len(fleet), np.int32),
        job_count=np.zeros(len(fleet), np.int32))
    before = sel.device_stats_snapshot()["dispatches"].get("kway", 0)
    kernel = sel.SelectKernel()
    kernel.decorrelate = (0, LANES)
    res = kernel.select(req)
    assert res.placed == job["count"]
    assert sel.device_stats_snapshot()["dispatches"]["kway"] == before + 1
    rows = [int(r) for r in res.node_idx[:res.placed]]
    return {job["id"]: [
        {"id": f"a{i}", "name": f"{job['id']}.{job['group']}[{i}]",
         "node_id": fleet[r]["id"], "job_id": job["id"],
         "desired_status": "run", "create_index": 7}
        for i, r in enumerate(rows)]}


@pytest.fixture(scope="module")
def overfull(exhausted):
    """A job of 1,200 on the exhausted fleet, ranked by the program:
    more than lane 0 has nodes with room (796 2x, 254 4x), so it
    stacks there, while lane 1 still has 1,562 untouched 1x nodes."""
    fleet, backlog, _job, _allocs, lane_of = exhausted
    job, = jobs_of("batch-fill", [1200])
    allocs = ranked_by_lane_0(fleet, backlog, job)
    row = {n["id"]: i for i, n in enumerate(fleet)}
    rows = [row[a["node_id"]] for a in allocs[job["id"]]]
    assert {int(lane_of[r]) for r in rows} == {0}
    assert len(set(rows)) == 1050
    return job, allocs


def test_a_share_that_ran_out_of_a_class_is_judged_within_the_share(
        exhausted, rule):
    fleet, backlog, job, allocs, lane_of = exhausted
    row = {n["id"]: i for i, n in enumerate(fleet)}
    rows = [row[a["node_id"]] for a in allocs[job["id"]]]
    assert {int(lane_of[r]) for r in rows} == {0}
    assert {fleet[r]["class"] for r in rows} == {"c2x"}
    # the bound of before this rule (every plan against the whole
    # fleet's (lanes x k)-th best: check_rank without a rule) fails the
    # program for what decorrelated shares do: lane 1 still holds
    # untouched 1x nodes
    old = ref.check_rank(fleet, backlog, [job], allocs, LANES)[1]
    stacked, new, widest = ref.check_rank(fleet, backlog, [job], allocs,
                                          LANES, rule)
    assert old > 0.2 and old > ref.RANK_GAP_LIMIT
    assert new <= ref.TIE_EPS and stacked == [] and widest == []


def test_a_plan_that_skips_its_lane_s_best_by_a_class_fails(exhausted, rule):
    import copy
    fleet, backlog, job, allocs, lane_of = exhausted
    taken = {a["node_id"] for a in allocs[job["id"]]}
    big = next(n for i, n in enumerate(fleet) if n["class"] == "c4x"
               and lane_of[i] == 0 and n["id"] not in taken)
    moved = copy.deepcopy(allocs)
    moved[job["id"]][5]["node_id"] = big["id"]
    gap, widest = ref.check_rank(fleet, backlog, [job], moved, LANES,
                                 rule)[1:]
    scorer = ref.PlainScorer(fleet, job, backlog)
    mid = next(n for i, n in enumerate(fleet) if n["class"] == "c2x"
               and lane_of[i] == 0)
    assert gap == pytest.approx(scorer.score(mid) - scorer.score(big))
    assert gap > 3 * ref.RANK_GAP_LIMIT and "lane 0's 600-th" in widest[0]
    # the other lane's better nodes buy it nothing: a row moved ACROSS
    # the lanes makes it a whole-fleet plan, held to the fleet's best
    small = next(n for i, n in enumerate(fleet) if n["class"] == "c1x"
                 and lane_of[i] == 1)
    across = copy.deepcopy(allocs)
    across[job["id"]][5]["node_id"] = small["id"]
    gap = ref.check_rank(fleet, backlog, [job], across, LANES, rule)[1]
    assert gap == pytest.approx(scorer.score(small) - scorer.score(mid))


def _onto(allocs, job, i, node_id):
    moved = copy.deepcopy(allocs)
    moved[job["id"]][i]["node_id"] = node_id
    return moved


@pytest.mark.parametrize("case,pool", [
    ("lane_ran_out", None),
    ("stacked_in_its_lane", "plan 7: lane 0"),
    ("straddles_the_lanes", "plan 7: fleet"),
    ("under_min_count", "plan 7: fleet"),
    ("retry_stacks_in_the_fleet", "plan 8: fleet")])
def test_stacked_is_judged_in_the_share_that_ranked_the_plan(
        cfg, rule, exhausted, overfull, case, pool):
    """`stacked` holds a plan to the share `rank_gap` holds it to.
    lane_ran_out: the program's 1,200 on lane 0, whose nodes above the
    ceiling are all gone (its 1x class is full): the greedy confined to
    the lane stacks too, though lane 1 holds 1,562 such nodes.
    stacked_in_its_lane: the 600-plan with one alloc moved onto a node
    it holds, on the fleet as loaded, where lane 0 still has its 1x
    nodes. straddles_the_lanes: the 1,200-plan with one alloc moved
    onto a lane 1 node. under_min_count: 200 stacked two a node on lane
    0's 2x nodes, an ask the rule never slices. retry_stacks_in_the_fleet:
    the 1,200-plan's last ten committed apart, as a retry of ten (under
    min_count, so ranked over the fleet), two on each of five lane 1 1x
    nodes: the sound first plan reads nothing, the retry reads stacked.
    Without the rule every plan is judged over the fleet and reads
    stacked."""
    fleet, backlog, job, allocs, lane_of = exhausted
    if case == "lane_ran_out":
        job, allocs = overfull
    elif case == "stacked_in_its_lane":
        backlog = fleetlib.backlog_usage(cfg, fleet)
        allocs = _onto(allocs, job, 1, allocs[job["id"]][0]["node_id"])
    elif case == "straddles_the_lanes":
        job, allocs = overfull
        held = {a["node_id"] for a in allocs[job["id"]]}
        other = next(n for i, n in enumerate(fleet) if lane_of[i] == 1
                     and n["class"] == "c1x" and n["id"] not in held)
        allocs = _onto(allocs, job, 0, other["id"])
    elif case == "retry_stacks_in_the_fleet":
        job, allocs = overfull
        allocs = copy.deepcopy(allocs)
        small = [n for i, n in enumerate(fleet) if lane_of[i] == 1
                 and n["class"] == "c1x"][:5]
        for i, a in enumerate(allocs[job["id"]][-10:]):
            a["node_id"], a["create_index"] = small[i // 2]["id"], 8
    else:
        job, = jobs_of("batch-fill", [200])
        mid = [n for i, n in enumerate(fleet) if n["class"] == "c2x"
               and lane_of[i] == 0][:100]
        allocs = {job["id"]: [
            {"id": f"a{i}", "node_id": mid[i // 2]["id"],
             "name": f"{job['id']}.{job['group']}[{i}]", "create_index": 7}
            for i in range(200)]}
    stacked = ref.check_rank(fleet, backlog, [job], allocs, LANES, rule)[0]
    whole = ref.check_rank(fleet, backlog, [job], allocs, LANES)[0]
    assert whole and all(": fleet: " in w for w in whole), whole
    if pool is None:
        assert stacked == []
    else:
        assert len(stacked) == 1 and f" {pool}: " in stacked[0], stacked
        assert "with room above the ceiling" in stacked[0]


def test_small_asks_and_retries_below_the_rule_s_size_rank_the_fleet(
        mixed, rule):
    """An ask under min_count is never sliced: its plan is held to the
    whole fleet's (lanes x k)-th best even where its rows happen to lie
    in one lane; so is the tail of a retry."""
    fleet, backlog = mixed
    lane_of = ref.lane_ids(len(fleet), LANES, rule)
    jobs = jobs_of("batch-fill", [3, 300])
    big = [n for i, n in enumerate(fleet) if n["class"] == "c4x"
           and lane_of[i] == 0]
    small = [n for i, n in enumerate(fleet) if n["class"] == "c1x"
             and lane_of[i] == 0]

    def on(job, nodes, index):
        return [{"id": f"{job['id']}-{index}-{i}", "node_id": n["id"],
                 "name": f"{job['id']}.{job['group']}[{i}]",
                 "create_index": index} for i, n in enumerate(nodes)]
    # three allocs of a 3-ask on lane 0's 4x nodes: one lane, but a
    # small ask, so the fleet's 1x nodes are the bound
    allocs = {jobs[0]["id"]: on(jobs[0], big[:3], 1)}
    assert ref.check_rank(fleet, backlog, jobs[:1], allocs, LANES,
                          rule)[1] > 0.4
    # a 300-ask: 290 committed on lane 0's best, the retry of 10 (under
    # min_count) on lane 0's 4x nodes is a whole-fleet plan and fails;
    # the same ten as part of the sliced first plan would fail too
    allocs = {jobs[1]["id"]: on(jobs[1], small[:290], 2)
              + on(jobs[1], big[:10], 3)}
    assert ref.check_rank(fleet, backlog, jobs[1:], allocs, LANES,
                          rule)[1] > 0.4
    allocs = {jobs[1]["id"]: on(jobs[1], small[:290] + big[:10], 2)}
    assert ref.check_rank(fleet, backlog, jobs[1:], allocs, LANES,
                          rule)[1] > 0.4


@pytest.mark.parametrize("broken", ["firstfit", "norank"])
def test_controls_read_as_far_off_under_the_lane_bound(cfg, rule, broken):
    """A control ranks nothing, lanes least of all: its plans straddle
    them and are held to the fleet's (lanes x k)-th best, as before."""
    fleet = fleetlib.build_fleet(cfg, 3, 2560)
    backlog = fleetlib.backlog_usage(cfg, fleet)
    jobs = jobs_of("batch-fill", [600, 600])
    plain = answered((fleet, backlog), jobs, broken)
    gap = ref.check_rank(fleet, backlog, jobs, plain.allocs, LANES, rule)[1]
    assert gap >= 0.4
    assert gap == ref.check_rank(fleet, backlog, jobs, plain.allocs,
                                 LANES)[1]
    whole = answered((fleet, backlog), jobs)
    assert ref.check_rank(fleet, backlog, jobs, whole.allocs, LANES,
                          rule)[1] == 0.0
