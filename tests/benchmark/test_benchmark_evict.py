"""A preempting deployment through the plain half of the harness (PR 32):
the backlog a configuration describes in tiers, the judge that accounts
for allocations that stopped running, and the plain scheduler that
preempts and breaks four ways. Scenes are made by hand on the toy
configuration's fleet; everything is a count."""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import fleet as fleetlib         # noqa: E402
from benchmark.lib import reference as ref          # noqa: E402
from benchmark.lib import traffic                   # noqa: E402

PORTS = (20000, 32000)
DCS = ["dc1", "dc2", "dc3", "dc4"]
FOUR = list(ref.TIER_LIMITS)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


TOY = load("tests", "benchmark", "preempt-toy.json")
ROOMY = load("tests", "benchmark", "preempt-toy-room.json")
MIX = load("tests", "benchmark", "traffic", "toy-evict.json")


class Scene:
    """A fleet of the toy's with its residents as loaded, a window job
    or two placed by hand, and stubs to alter before the judge reads
    them."""

    def __init__(self, cfg=TOY, nodes=64):
        self.cfg = cfg
        self.fleet = fleetlib.build_fleet(cfg, 3, nodes)
        self.plain = fleetlib.residents(cfg, self.fleet)
        self.now = {}
        for alloc_id, (_t, job_id, node_id) in self.plain["allocs"].items():
            self.now.setdefault(job_id, []).append({
                "id": alloc_id, "node_id": node_id, "job_id": job_id,
                "desired_status": "run", "create_index": 7,
                "modify_index": 7})
        self.jobs, self.allocs, self.evals = [], {}, {}

    def node(self, scale=1, k=0):
        return [n for n in self.fleet if n["scale"] == scale][k]

    def residents_on(self, node, tier_name):
        ti = [t["name"] for t in self.plain["tiers"]].index(tier_name)
        return [s for stubs in self.now.values() for s in stubs
                if s["node_id"] == node["id"]
                and self.plain["allocs"].get(s["id"], (None,))[0] == ti]

    def place(self, node, index, count=1):
        job = traffic.plain_job(MIX, f"w{len(self.jobs)}", count, DCS)
        self.jobs.append(job)
        self.allocs[job["id"]] = [
            {"id": f"{job['id']}-{i}", "name": f"{job['id']}.web[{i}]",
             "node_id": node["id"], "job_id": job["id"],
             "desired_status": "run", "create_index": index,
             "modify_index": index} for i in range(count)]
        self.evals[job["id"]] = {"status": "complete", "job_id": job["id"],
                                 "failed_tg_allocs": None, "blocked_eval": ""}
        return job

    def evict(self, node, tier_name, k, index, skip=0):
        for stub in [s for s in self.residents_on(node, tier_name)
                     if s["desired_status"] == "run"][skip:skip + k]:
            stub["desired_status"] = "evict"
            stub["modify_index"] = index

    def judged(self):
        compared, found = ref.judge(
            self.fleet, self.plain["usage"], self.jobs, self.evals,
            self.allocs, [], [], PORTS, 2, None,
            ref.ResidentState(self.plain, self.now))
        return {k: c["value"] for k, c in compared.items()}, found


# -- the backlog as plain data -------------------------------------------

def test_residents_fill_every_class_alike_and_count_400k_at_full_size():
    fleet = fleetlib.build_fleet(TOY, 7, 640)
    plain = fleetlib.residents(TOY, fleet)
    assert [t["priority"] for t in plain["tiers"]] == [20, 40, 65]
    per_node = {}
    for tier, _job, node_id in plain["allocs"].values():
        per_node.setdefault(node_id, [0, 0, 0])[tier] += 1
    for n in fleet:
        assert per_node[n["id"]] == [12 * n["scale"], 8 * n["scale"],
                                     5 * n["scale"]]
        # full within one ask: what is left holds no cpu 600
        left = n["capacity"]["cpu"] - plain["usage"][n["id"]]["cpu"]
        assert 0 <= left < MIX["job"]["ask"]["cpu"]
        assert all(plain["usage"][n["id"]][d] <= n["capacity"][d]
                   for d in fleetlib.DIMS)
    # 6 / 3 / 1 tenths at 1x / 2x / 4x: 25 x 1.6 a node
    assert len(plain["allocs"]) == 25 * (384 + 2 * 192 + 4 * 64)
    assert TOY["nodes"] * 25 * 1.6 == 400_000


def test_residents_jobs_ids_and_names_follow_from_tier_and_ordinal():
    fleet = fleetlib.build_fleet(TOY, 7, 640)
    plain = fleetlib.residents(TOY, fleet)
    assert len(plain["jobs"]) == 8 + 8 + 4
    counts = {}
    for _tier, job_id, _node in plain["allocs"].values():
        counts[job_id] = counts.get(job_id, 0) + 1
    for job_id, job in plain["jobs"].items():
        assert counts[job_id] == job["count"]
    low = [j["count"] for i, j in plain["jobs"].items()
           if i.startswith("batch-low-")]
    assert max(low) - min(low) <= 1 and sum(low) == 12 * 1024
    assert "batch-low-003-000017" in plain["allocs"]
    assert fleetlib.resident_name("batch-low-003-000017") == \
        "batch-low-003.resident[17]"
    # the seed moves the node an ordinal lands on, nothing else
    other = fleetlib.residents(TOY, fleetlib.build_fleet(TOY, 8, 640))
    assert set(other["allocs"]) == set(plain["allocs"])
    assert other["allocs"] != plain["allocs"]


def test_the_usage_of_a_tiered_backlog_sums_the_tiers():
    fleet = fleetlib.build_fleet(TOY, 7, 640)
    usage = fleetlib.residents(TOY, fleet)["usage"]
    one = next(n for n in fleet if n["scale"] == 2)
    assert usage[one["id"]] == {"cpu": 2 * 3850, "memory_mb": 2 * 6144,
                                "disk_mb": 2 * 530, "mbits": 0}


# -- the judge, scene by scene -------------------------------------------

def test_a_fleet_nobody_touched_reads_nought():
    got, _found = Scene().judged()
    assert [got[k] for k in FOUR] == [0, 0, 0, 0]
    assert got["over_capacity"] == 0


def test_an_exact_eviction_reads_nought():
    s = Scene()
    x = s.node()
    s.place(x, 20)
    s.evict(x, "batch-low", 6, 20)      # 50 left + 600 freed - 600 asked
    got, found = s.judged()
    assert [got[k] for k in FOUR] == [0, 0, 0, 0], found
    assert got["over_capacity"] == 0


def test_a_placement_that_evicted_too_little_is_over_capacity():
    s = Scene()
    x = s.node()
    s.place(x, 20)
    s.evict(x, "batch-low", 5, 20)
    got, _found = s.judged()
    assert got["over_capacity"] == 1
    assert [got[k] for k in FOUR] == [0, 0, 0, 0]


@pytest.mark.parametrize("scene,number,count", [
    ("elsewhere", "evicted_wrongly", 1),
    ("peer", "evicted_wrongly", 3),
    ("one-too-many", "evicted_needlessly", 1),
    ("jumped-a-tier", "evicted_needlessly", 3),
    ("stopped", "residents_stopped", 1),
    ("gone", "residents_stopped", 1),
    ("moved", "residents_stopped", 1),
    ("one-more-than-evicted", "residents_stopped", 1),
])
def test_each_fault_is_counted_by_its_own_number(scene, number, count):
    s = Scene()
    x, y = s.node(k=0), s.node(k=1)
    s.place(x, 20)
    if scene == "peer":
        s.evict(x, "service-peer", 3, 20)       # 630 >= 550, priority 65
    elif scene == "jumped-a-tier":
        s.evict(x, "batch-mid", 3, 20)          # while batch-low runs there
    else:
        s.evict(x, "batch-low", 7 if scene == "one-too-many" else 6, 20)
    if scene == "elsewhere":
        s.evict(y, "batch-low", 1, 20)          # y took no placement
    if scene == "stopped":
        s.residents_on(y, "batch-mid")[0]["desired_status"] = "stop"
    if scene == "gone":
        gone = s.residents_on(y, "batch-mid")[0]
        s.now[gone["job_id"]].remove(gone)
    if scene == "moved":
        s.residents_on(y, "service-peer")[0]["node_id"] = x["id"]
    if scene == "one-more-than-evicted":
        job_id = s.residents_on(y, "service-peer")[0]["job_id"]
        s.now[job_id].append({"id": "who-made-this", "node_id": y["id"],
                              "job_id": job_id, "desired_status": "run",
                              "create_index": 30, "modify_index": 30})
    got, found = s.judged()
    assert got[number] == count, found
    assert [got[k] for k in FOUR if k != number] == [0, 0, 0], found


def test_a_replacement_of_an_evicted_allocation_is_no_fault_and_is_counted():
    s = Scene(nodes=640)
    x, big = s.node(), s.node(scale=4)
    s.place(x, 20)
    s.evict(x, "batch-low", 6, 20)
    victim = next(v for v in s.residents_on(x, "batch-low")
                  if v["desired_status"] == "evict")
    # its job's follow-up eval placed one more where 500 cpu were left
    fresh = {"id": "0b5c-replacement", "node_id": big["id"],
             "job_id": victim["job_id"], "desired_status": "run",
             "create_index": 24, "modify_index": 24}
    s.now[victim["job_id"]].append(fresh)
    got, found = s.judged()
    assert [got[k] for k in FOUR] == [0, 0, 0, 0], found
    state = ref.ResidentState(s.plain, s.now)
    assert state.usage()[big["id"]]["cpu"] == \
        s.plain["usage"][big["id"]]["cpu"] + 100
    assert state.usage()[x["id"]]["cpu"] == \
        s.plain["usage"][x["id"]]["cpu"] - 600
    # five more of them overfill the node: the capacity check sees them
    for i in range(5):
        s.now[victim["job_id"]].append(dict(fresh, id=f"more-{i}"))
    got, _found = s.judged()
    assert got["over_capacity"] == 1 and got["residents_stopped"] == 1


def test_evictions_are_judged_commit_by_commit_not_at_the_end():
    # a 1x node, 50 cpu left: two placements take six of the lowest tier
    # each; the third finds none left and takes three of the next (600
    # freed for 550 short): 100 are left at the end, which would hold
    # one of the first commit's victims - no fault of that commit's
    s = Scene()
    x = s.node()
    for index in (20, 30):
        s.place(x, index)
        s.evict(x, "batch-low", 6, index)
    s.place(x, 40)
    s.evict(x, "batch-mid", 3, 40)
    got, found = s.judged()
    assert [got[k] for k in FOUR] == [0, 0, 0, 0], found
    assert got["over_capacity"] == 0


def test_an_eviction_while_a_node_has_room_is_counted_per_placement():
    s = Scene(ROOMY, nodes=640)
    full, roomy = s.node(scale=1), s.node(scale=2)
    assert roomy["capacity"]["cpu"] - s.plain["usage"][roomy["id"]]["cpu"] \
        == 620
    s.place(roomy, 20)                      # fits as the fleet stands
    s.place(full, 30, count=2)              # 260 left: 940 short
    s.evict(full, "batch-low", 10, 30)
    got, found = s.judged()
    assert got["evicted_with_room"] == 2, found
    assert [got[k] for k in FOUR if k != "evicted_with_room"] == [0, 0, 0]


def test_a_plan_that_evicted_is_not_ranked_and_one_that_did_not_is():
    # a 4x node with 1,340 cpu left scores 0.011 under a 2x node with
    # 620: a plan that went there without evicting is ranked ...
    s = Scene(ROOMY, nodes=640)
    s.place(s.node(scale=4), 20)
    got, found = s.judged()
    assert 0.005 < got["rank_gap"] < ref.RANK_GAP_LIMIT
    assert found["rank_gap"] and got["evicted_with_room"] == 0
    # ... and one that evicted there is the preemption score's to rank
    s = Scene(ROOMY, nodes=640)
    x = s.node(scale=4)
    s.place(x, 20)
    s.evict(x, "batch-low", 1, 20)
    got, found = s.judged()
    assert got["rank_gap"] == 0.0 and not found["rank_gap"]
    assert got["evicted_needlessly"] == 1


# -- the plain scheduler preempts, and breaks four ways -------------------

def answered(cfg, broken, n_jobs=40, nodes=640):
    fleet = fleetlib.build_fleet(cfg, 5, nodes)
    plain = fleetlib.residents(cfg, fleet)
    sched = ref.PlainScheduler(
        fleet, plain["usage"], PORTS, broken=broken, residents=plain,
        scheduler_configuration=cfg.get("scheduler_configuration"))
    deck = MIX["deck"]
    jobs = [traffic.plain_job(MIX, f"c{i}", deck[i % len(deck)], DCS)
            for i in range(n_jobs)]
    for job in jobs:
        sched.submit(job)
    compared, found = ref.judge(
        fleet, plain["usage"], jobs, sched.evals, sched.allocs, [], [],
        PORTS, 2, None, ref.ResidentState(plain, sched.resident_allocs()))
    evicted = sum(s["desired_status"] == "evict"
                  for s in sched.resident_stubs.values())
    return compared, found, evicted, sched


def test_the_plain_scheduler_evicts_exactly_and_is_correct():
    compared, found, evicted, sched = answered(TOY, None)
    assert ref.is_correct(compared), found
    # fewest victims first: a 4x node is 100 short, one victim of the
    # lowest tier a placement; 64 such nodes, then 2x nodes at four each
    assert sum(len(a) for a in sched.allocs.values()) == 84
    assert evicted == 64 + 4 * 20
    assert all(len(a) == j for a, j in zip(
        sched.allocs.values(), [MIX["deck"][i % 10] for i in range(40)]))


def test_without_the_scheduler_configuration_nothing_is_evicted():
    cfg = copy.deepcopy(TOY)
    cfg["scheduler_configuration"]["preemption_config"][
        "service_scheduler_enabled"] = False
    compared, _found, evicted, _sched = answered(cfg, None, n_jobs=4)
    assert evicted == 0
    assert compared["unplaced_evals"]["value"] == 4
    assert ref.preemption_enabled(None, "system")
    assert not ref.preemption_enabled(None, "service")
    assert not ref.preemption_enabled(TOY["scheduler_configuration"],
                                      "batch")


@pytest.mark.parametrize("control,cfg,number", [
    ("noevict", TOY, "over_capacity"),
    ("evictpeer", TOY, "evicted_wrongly"),
    ("evictall", TOY, "evicted_needlessly"),
    ("evictearly", ROOMY, "evicted_with_room"),
])
def test_each_preemption_control_trips_its_own_number_and_no_other(
        control, cfg, number):
    compared, found, evicted, _sched = answered(cfg, control)
    assert not ref.is_correct(compared)
    assert compared[number]["value"] > 0, found
    others = [k for k in FOUR + ["over_capacity"] if k != number]
    assert [compared[k]["value"] for k in others] == [0] * 4, found
    assert (evicted == 0) == (control == "noevict")


def test_the_reference_fills_room_before_it_evicts():
    compared, found, evicted, sched = answered(ROOMY, None, n_jobs=40)
    assert ref.is_correct(compared), found
    assert evicted == 0                       # 320 slots for 84 instances
    compared, found, evicted, _s = answered(ROOMY, None, n_jobs=200)
    assert ref.is_correct(compared), found
    assert evicted > 0                        # 420 instances: room ran out


# -- the harness's own halves: the roofline's floor and the wait ---------

def _toy_run(cell, tmp_path):
    import argparse
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchrun_helper import evict_manifest
    import benchmark.run as run
    with open(evict_manifest(str(tmp_path))) as f:
        manifest = json.load(f)
    args = argparse.Namespace(rate=0.0, seconds=3.0, seed=7, trace=1,
                              nodes=640, rehearse_cpu=True)
    return run.Run(args, run.plan_cell(manifest, cell))


def test_the_floor_charges_the_candidates_to_an_eval_that_evicted_alone(
        tmp_path):
    # REVIEW 32: an eval that found room reads today's floor; only one
    # whose own commit evicted reads the victim selection's candidates
    from benchmark.lib import kernelcost as kc
    run = _toy_run("preempt-toy-room_toy-evict", tmp_path)
    assert run.mix["name"] == "toy-evict" and run.residents
    jobs = [traffic.plain_job(run.mix, name, 1, run.dcs)
            for name in ("found-room", "evicted")]
    allocs = {"found-room": [{"create_index": 20}],
              "evicted": [{"create_index": 30}]}
    victim, (_tier, job_id, node_id) = next(
        (i, a) for i, a in run.residents["allocs"].items() if a[0] == 0)
    now = {job_id: [{"id": victim, "node_id": node_id, "job_id": job_id,
                     "desired_status": "evict", "create_index": 0,
                     "modify_index": 30}]}
    today = kc.select_floor_bytes(640, 4, 1)
    assert today == kc.select_floor_bytes(640, 4, 1, preempt_candidates=0)
    assert run.floor_bytes(jobs[:1], allocs, now) == today
    # the two batch tiers are eligible against priority 70, the service
    # tier (65) is not; one of them is gone
    eligible = (12 + 8) * 1024 - 1
    assert run.floor_bytes(jobs[1:], allocs, now) == \
        today + eligible * (4 * kc.F32 + 2 * kc.I32) + kc.pad_n(640) * kc.F32
    assert run.floor_bytes(jobs, allocs, now) == \
        run.floor_bytes(jobs[:1], allocs, now) \
        + run.floor_bytes(jobs[1:], allocs, now)
    # nothing evicted, or no tiers read back: today's floor for all
    assert run.floor_bytes(jobs, allocs, {}) == 2 * today
    assert run.floor_bytes(jobs, allocs, None) == 2 * today


class _Broker:
    def __init__(self, busy_for_s):
        self.until = busy_for_s
        import time
        self.t0 = time.perf_counter()

    @property
    def stats(self):
        import time
        import types
        busy = time.perf_counter() - self.t0 < self.until
        return types.SimpleNamespace(total_ready=0, total_blocked=0,
                                     total_unacked=int(busy))


def test_quiesce_says_whether_the_broker_went_calm():
    import types
    from benchmark.lib import agent as agentlib
    def agent(busy_for_s):
        return types.SimpleNamespace(
            srv=types.SimpleNamespace(eval_broker=_Broker(busy_for_s)))
    calm, took = agentlib.Agent.quiesce(agent(0.0), 5.0)
    assert calm is True and took < 1.0
    calm, took = agentlib.Agent.quiesce(agent(0.2), 5.0)
    assert calm is True and 0.2 <= took < 1.5
    calm, took = agentlib.Agent.quiesce(agent(60.0), 0.3)
    assert calm is False and took >= 0.3
