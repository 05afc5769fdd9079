"""The judge's device vocabulary (upstream's device stanza, DeviceChecker,
AssignDevice) on scenes made by hand: the units table and its operands,
the three forms of an ask's name, the capability check, the device
dimension of over_capacity, device_conflicts, the ranking of a job that
asks for devices, and the fleet and traffic that carry them. Everything
here is plain Python and a count."""
import copy
import json
import os
import random
import sys

import numpy as np
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
V100 = "Tesla V100-SXM2-32GB"
MEMORY = ("${device.attr.memory}", ">=", "16 GiB")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CFG = load("tests", "benchmark", "gpu-toy.json")
MIX = load("tests", "benchmark", "traffic", "toy-gpu.json")


def group(**attributes):
    return {"vendor": "nvidia", "type": "gpu", "model": "Tesla T4",
            "attributes": attributes, "ids": ["a", "b", "c", "d"]}


# -- the units table and the operands -----------------------------------

@pytest.mark.parametrize("left,op,right,want", [
    ("16384 MiB", ">=", "16 GiB", True),
    ("16384 MiB", "=", "16 GiB", True),
    ("16384 MiB", "!=", "16 GiB", False),
    ("16383 MiB", "<", "16 GiB", True),
    ("16 GiB", "<=", "17 GB", False),          # 17.18e9 bytes
    ("1 KiB", ">", "1 kB", True),
    ("1 KB", "=", "1000 B", True),
    ("2 TB", "<", "2 TiB", True),
    ("1530 MHz", ">", "1.5 GHz", True),
    ("300 W", "<=", "0.3 kW", True),
    ("250 mW", "<", "1 W", True),
    ("1.5 GHz", "=", "1500 MHz", True),
], ids=lambda v: str(v))
def test_units_compare_within_a_dimension(left, op, right, want):
    assert ref.device_constraint_ok(group(x=left),
                                    ("${device.attr.x}", op, right)) is want


@pytest.mark.parametrize("left,right", [
    ("16 GiB", "1 GHz"), ("300 W", "300 MHz"), ("1 TB", "2 kW")])
@pytest.mark.parametrize("op", ref.DEVICE_OPERANDS)
def test_mismatched_units_satisfy_no_operand(left, right, op):
    assert ref.compare_device_values(left, right) is None
    assert not ref.device_constraint_ok(group(x=left),
                                        ("${device.attr.x}", op, right))


@pytest.mark.parametrize("left", ["16", 16, 17179869184, "16.0"])
@pytest.mark.parametrize("op", ref.DEVICE_OPERANDS)
def test_a_bare_number_against_a_unit_satisfies_nothing(left, op):
    assert not ref.device_constraint_ok(group(x=left),
                                        ("${device.attr.x}", op, "16 GiB"))


@pytest.mark.parametrize("left,op,right,want", [
    (8, ">", "4", True), ("4.5", "<", "5", True), (3584, "=", "3584", True),
    ("Tesla T4", "=", "Tesla T4", True), ("Tesla T4", "!=", V100, True),
    ("450.51.06", "=", "450.51.06", True), ("Tesla T4", "<", "5", False),
    (True, "=", "true", True), (float("inf"), "=", "inf", True),
])
def test_bare_numbers_and_strings(left, op, right, want):
    assert ref.device_constraint_ok(group(x=left),
                                    ("${device.attr.x}", op, right)) is want


def test_a_missing_attribute_satisfies_only_not_equal():
    g = group(memory="16384 MiB")
    for op in ref.DEVICE_OPERANDS:
        got = ref.device_constraint_ok(g, ("${device.attr.nope}", op, "1"))
        assert got is (op == "!=")


def test_device_targets_resolve_and_others_raise():
    g = group(memory="16384 MiB")
    assert ref.device_constraint_ok(g, ("${device.vendor}", "=", "nvidia"))
    assert ref.device_constraint_ok(g, ("${device.type}", "=", "gpu"))
    assert ref.device_constraint_ok(g, ("${device.model}", "=", "Tesla T4"))
    assert not ref.device_constraint_ok(g, ("${device.model}", "=", V100))
    with pytest.raises(ValueError, match="outside"):
        ref.device_constraint_ok(g, ("${device.ids}", "=", "a"))
    for op in ("regexp", "version", "is_set", "=="):
        with pytest.raises(ValueError, match="operand"):
            ref.device_constraint_ok(g, ("${device.model}", op, "T4"))
    # a node's targets stay the node constraints' own
    with pytest.raises(ValueError, match="outside"):
        ref.constraint_ok({"attributes": {}, "meta": {}, "datacenter": "dc1"},
                          ("${device.model}", "=", "T4"))


# -- an ask's name, and DeviceChecker ------------------------------------

@pytest.mark.parametrize("name,want", [
    ("gpu", True), ("nvidia/gpu", True), ("nvidia/gpu/Tesla T4", True),
    ("fpga", False), ("amd/gpu", False), ("nvidia/gpu/Tesla P100", False),
    ("nvidia/fpga", False)])
def test_the_three_name_forms(name, want):
    assert ref.device_name_matches(group(), name) is want


def ask(name="nvidia/gpu", count=1, constraints=(MEMORY,), affinities=()):
    return {"name": name, "count": count, "constraints": list(constraints),
            "affinities": list(affinities)}


def job_of(asks, job_id="j", count=1, cpu=500):
    job = traffic.plain_job(MIX, job_id, count, DCS, [])
    job["ask"] = dict(job["ask"], cpu=cpu)
    if asks:
        job["devices"] = asks
    return job


@pytest.fixture(scope="module")
def fleet():
    """640 nodes of the toy: ordinals 320-383 carry 4 T4 (16 GiB), 512-575
    8 V100 (32 GiB)."""
    return fleetlib.build_fleet(CFG, 5, 640)


def of_class(fleet, name):
    return [n for n in fleet if n["class"] == name]


@pytest.mark.parametrize("asks,classes", [
    ([], {"c1x", "c1x-t4", "c2x", "c2x-v100", "c4x"}),
    ([ask(count=4)], {"c1x-t4", "c2x-v100"}),          # every T4 healthy
    ([ask(count=5)], {"c2x-v100"}),                    # a T4 has four
    ([ask(count=9)], set()),
    ([ask(constraints=[("${device.attr.memory}", ">=", "32 GiB")])],
     {"c2x-v100"}),
    ([ask(name="amd/gpu")], set()),                    # no matching group
    ([ask(name="gpu/" + V100)], set()),                # gpu is no vendor
    ([ask(name="nvidia/gpu/" + V100)], {"c2x-v100"}),
    ([ask(count=4), ask(name="gpu", count=4)], {"c1x-t4", "c2x-v100"}),
])
def test_device_checker(fleet, asks, classes):
    job = job_of(asks)
    ok = {n["class"] for n in fleet if ref.devices_ok(n, job)}
    assert ok == classes
    assert {n["class"] for n in fleet if not ref.node_feasible(n, job)} \
        == classes


def test_an_instance_short_is_not_enough(fleet):
    t4 = copy.deepcopy(of_class(fleet, "c1x-t4")[0])
    assert ref.devices_ok(t4, job_of([ask(count=4)]))
    t4["devices"][0]["ids"].pop()
    assert not ref.devices_ok(t4, job_of([ask(count=4)]))


# -- the fleet and the traffic -------------------------------------------

def test_device_ids_come_from_a_stream_of_their_own(fleet):
    bare = copy.deepcopy(CFG)
    for cls in bare["machine_classes"]:
        cls.pop("devices", None)
    plain = fleetlib.build_fleet(bare, 5, 640)
    assert [n["id"] for n in plain] == [n["id"] for n in fleet]
    assert not any("devices" in n for n in plain)
    t4, v100 = of_class(fleet, "c1x-t4"), of_class(fleet, "c2x-v100")
    assert len(t4) == len(v100) == 64
    assert all(len(n["devices"][0]["ids"]) == 4 for n in t4)
    assert all(len(n["devices"][0]["ids"]) == 8 for n in v100)
    ids = [i for n in t4 + v100 for i in n["devices"][0]["ids"]]
    assert len(set(ids)) == len(ids) == 768
    assert fleetlib.build_fleet(CFG, 5, 640) == fleet
    assert fleetlib.build_fleet(CFG, 6, 640)[0]["id"] != fleet[0]["id"]


def test_a_device_deck_deals_the_same_pairs_on_every_seed():
    def pairs(seed):
        reqs = traffic.closed_loop(MIX, seed, 10.0, DCS)
        return sorted((j["count"], json.dumps(j["devices"]))
                      for r in reqs for j in r.jobs)
    assert pairs(7) == pairs(2 ** 31 + 12345)
    got = pairs(7)
    # every size meets every card of the deck
    assert {(c, d) for c, d in got} == {
        (c, json.dumps(traffic.device_asks(card)))
        for c in MIX["deck"] for card in MIX["device_deck"]}
    warm = [j for rnd in traffic.warmup_requests(MIX, 7, DCS)
            for r in rnd for j in r.jobs]
    assert {json.dumps(j["devices"]) for j in warm} == {
        json.dumps(traffic.device_asks(card)) for card in MIX["device_deck"]}


def test_the_wire_form_carries_the_asks_as_the_tasks_devices():
    card = [{"name": "nvidia/gpu/Tesla T4", "count": 2,
             "constraints": [list(MEMORY)],
             "affinities": [["${device.model}", "=", V100, 50]]}]
    job = traffic.plain_job(MIX, "w", 3, DCS, card)
    wire = traffic.wire_job(job)
    assert wire["task_groups"][0]["tasks"][0]["resources"]["devices"] == [{
        "name": "nvidia/gpu/Tesla T4", "count": 2,
        "constraints": [{"ltarget": "${device.attr.memory}", "operand": ">=",
                         "rtarget": "16 GiB"}],
        "affinities": [{"ltarget": "${device.model}", "operand": "=",
                        "rtarget": V100, "weight": 50}]}]
    # a job dealt no card asks for nothing
    job = traffic.plain_job(MIX, "w", 3, DCS)
    assert "devices" not in job
    assert "devices" not in traffic.wire_job(job)["task_groups"][0][
        "tasks"][0]["resources"]
    # a mix without a device deck deals none
    bare = {k: v for k, v in MIX.items() if k != "device_deck"}
    assert not any("devices" in j for r in traffic.closed_loop(
        bare, 7, 3.0, DCS) for j in r.jobs)
    with pytest.raises(ValueError, match="under one"):
        traffic.device_asks([{"name": "gpu", "count": 0}])


# -- scenes: capacity, conflicts, ranking ---------------------------------

class Scene:
    """Placements made by hand on the toy's fleet: stubs as
    GET /v1/job/<id>/allocations lists them, full allocations as
    GET /v1/allocation/<id> returns them."""

    def __init__(self, fleet):
        self.fleet = fleet
        self.backlog = fleetlib.backlog_usage(CFG, fleet)
        self.jobs, self.allocs, self.full, self.evals = [], {}, [], {}
        self.index = 100

    def place(self, job, nodes, grants=None, index=None, names=None):
        """`index`: the commit index, else the next (two plans that the
        applier committed together share one); `names`: the allocations'
        name indexes, else the next free ones."""
        self.index = index or self.index + 1
        if job not in self.jobs:
            self.jobs.append(job)
            self.evals[job["id"]] = {"status": "complete",
                                     "failed_tg_allocs": None}
        stubs = self.allocs.setdefault(job["id"], [])
        for k, node in enumerate(nodes):
            name = len(stubs) if names is None else names[k]
            stub = {"id": f"{job['id']}-{len(stubs)}",
                    "name": f"{job['id']}.{job['group']}[{name}]",
                    "node_id": node["id"], "job_id": job["id"],
                    "desired_status": "run", "create_index": self.index}
            stubs.append(stub)
            if grants is None:
                continue            # not read in full
            self.full.append(dict(stub, allocated_resources={"tasks": {
                job["task"]: {"devices": [
                    {"vendor": g["vendor"], "type": g["type"],
                     "name": g["model"], "device_ids": ids}
                    for g, ids in grants[k]]}}}))
        job["count"] = len(stubs)

    def judge(self, lanes=1):
        return ref.judge(self.fleet, self.backlog, self.jobs, self.evals,
                         self.allocs, [], [], PORTS, lanes,
                         device_full=self.full)

    def broke(self, lanes=1):
        compared, found = self.judge(lanes)
        return {k: c["value"] for k, c in compared.items()
                if c["value"] > c["limit"]}, found


def grant(node, *ids):
    return [(node["devices"][0], list(ids))]


def test_a_sound_scene_reads_correct_and_names_device_conflicts(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")
    job = job_of([ask(count=2)], "g")
    g0, g1 = (n["devices"][0]["ids"] for n in t4[:2])
    s.place(job, t4[:2], [grant(t4[0], *g0[:2]), grant(t4[1], *g1[:2])])
    compared, found = s.judge()
    assert ref.is_correct(compared), found
    assert list(compared) == list(ref.LIMITS) + ["device_conflicts"]
    # a fleet without devices compares the ten it did
    bare = Scene(fleetlib.build_fleet(load("benchmark", "configs",
                                           "prod-10k.json"), 5, 64))
    assert list(bare.judge()[0]) == list(ref.LIMITS)


def test_over_capacity_counts_instances_from_the_stubs(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")[0]
    s.place(job_of([ask(count=2)], "g", cpu=10), [t4])
    s.place(job_of([ask(count=2)], "h", cpu=10), [t4])
    assert s.broke()[0] == {}
    s.place(job_of([ask(count=1)], "i", cpu=10), [t4])     # 5 of 4
    broke, found = s.broke()
    assert broke == {"over_capacity": 1}
    assert "5 instances of nvidia/gpu/Tesla T4 granted, it has 4" \
        in found["over_capacity"][0]
    # a job without asks takes no instance
    s = Scene(fleet)
    for k in range(2):
        s.place(job_of([], f"c{k}", cpu=10), [t4])
    s.place(job_of([ask(count=4)], "g", cpu=10), [t4])
    assert s.broke()[0] == {}


def test_over_capacity_on_a_node_of_two_groups_reads_the_grants(fleet):
    two = copy.deepcopy(fleet)
    t4 = of_class(two, "c1x-t4")[0]
    t4["devices"].append(dict(t4["devices"][0], model="Tesla P100",
                              ids=["p0", "p1"]))
    s = Scene(two)
    a, b = t4["devices"][0]["ids"], ["p0", "p1"]
    for name, grants in (("g", grant(t4, *a[:2])), ("h", grant(t4, *a[2:])),
                         ("i", [(t4["devices"][1], b)])):
        s.place(job_of([ask(count=2)], name, cpu=10), [t4], [grants])
    assert s.broke()[0] == {}
    # which group a grant took only the grant says: 4 + 2 of each group
    # is no fault, two more of the P100's are (and granted twice)
    s.place(job_of([ask(count=2)], "j", cpu=10), [t4],
            [[(t4["devices"][1], b)]])
    broke, found = s.broke()
    assert broke == {"over_capacity": 1, "device_conflicts": 2}
    assert "of nvidia/gpu/Tesla P100 granted, it has 2" \
        in found["over_capacity"][0]


@pytest.mark.parametrize("fault,why", [
    ("twice", "granted to"), ("foreign", "no instances of its group"),
    ("short", "for a count of 2"), ("repeat", "1 distinct"),
    ("model", "no group there that satisfies"), ("none", "0 device grants"),
])
def test_device_conflicts_on_hand_made_scenes(fleet, fault, why):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")[:2]
    ids = t4[0]["devices"][0]["ids"]
    s.place(job_of([ask(count=2)], "g", cpu=10), [t4[0]],
            [grant(t4[0], ids[0], ids[1])])
    second = {"twice": grant(t4[0], ids[1], ids[2]),
              "foreign": grant(t4[0], ids[2], "not-an-instance"),
              "short": grant(t4[0], ids[2]),
              "repeat": grant(t4[0], ids[2], ids[2]),
              "model": [(dict(t4[0]["devices"][0], model="Tesla P100"),
                         ids[2:])],
              "none": []}[fault]
    s.place(job_of([ask(count=2)], "h", cpu=10), [t4[0]], [second])
    broke, found = s.broke()
    assert set(broke) == {"device_conflicts"}, found
    assert any(why in line for line in found["device_conflicts"])


def test_an_allocation_of_a_job_without_asks_holds_no_device(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")[0]
    s.place(job_of([], "c", cpu=10), [t4], [grant(t4, "x")])
    assert s.broke()[0] == {"device_conflicts": 1}


def test_the_device_sample_reads_device_nodes_fullest_first(fleet):
    t4, c1x = of_class(fleet, "c1x-t4"), of_class(fleet, "c1x")
    job = job_of([ask()], "g", cpu=10)
    allocs = {"g": [{"id": f"a{i}", "node_id": n["id"]}
                    for i, n in enumerate([t4[0]] * 3 + t4[1:6] + c1x[:4])]}
    got = ref.device_sample(fleet, [job], allocs, 2, random.Random(1))
    assert got[:3] == ["a0", "a1", "a2"] and len(got) == 4
    assert ref.device_sample(fleet, [job], allocs, 0, random.Random(1)) == []
    assert len(ref.device_sample(fleet, [job], allocs, 99,
                                 random.Random(1))) == 8


def fuller(s, nodes):
    """A job without asks, one alloc on each of `nodes`: they score above
    their peers by bin-pack."""
    s.place(job_of([], f"filler{s.index}", cpu=500), nodes)


def test_a_gpu_plan_on_the_best_gpu_nodes_reads_no_gap(fleet):
    """CPU-only nodes that score higher by bin-pack have no instance to
    grant: the plan that took the best GPU nodes with room is right."""
    s = Scene(fleet)
    c1x, t4 = of_class(fleet, "c1x"), of_class(fleet, "c1x-t4")
    fuller(s, c1x[:8])
    s.place(job_of([ask()], "g", count=2), t4[:2])
    stacked, gap, widest = ref.check_rank(fleet, s.backlog, s.jobs,
                                          s.allocs, 2)
    assert (stacked, gap, widest) == ([], 0.0, [])
    # the same plan for a job that asks for nothing is held to the
    # fuller CPU-only nodes
    s = Scene(fleet)
    fuller(s, c1x[:8])
    s.place(job_of([], "c", count=2), t4[:2])
    _st, gap, _w = ref.check_rank(fleet, s.backlog, s.jobs, s.allocs, 2)
    assert gap > ref.RANK_GAP_LIMIT


def test_a_gpu_plan_that_skips_the_best_gpu_node_reads_a_gap(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")
    fuller(s, t4[:1])                       # the best T4, 4 free
    s.place(job_of([ask(count=2)], "g"), t4[1:2])
    assert s.broke()[0] == {"rank_gap": pytest.approx(0.0829, abs=1e-3)}
    # where its instances are all granted it has no room: no gap
    s = Scene(fleet)
    fuller(s, t4[:1])
    s.place(job_of([ask(count=4)], "h", cpu=10), t4[:1])
    s.place(job_of([ask(count=2)], "g"), t4[1:2])
    assert s.broke()[0] == {}


def test_a_stacking_gpu_plan_is_stacked_only_with_free_instances(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")
    s.place(job_of([ask()], "g", cpu=10), [t4[0], t4[0]])
    assert s.broke()[0] == {"stacked": 1}
    # one GPU node in a fleet of ten: nowhere else to go
    few = [n for n in fleet if "devices" not in n][:9] + [t4[0]]
    s = Scene(few)
    s.place(job_of([ask()], "g", cpu=10), [t4[0], t4[0]])
    assert s.broke()[0] == {}


def test_device_affinities_and_two_groups_are_not_ranked(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")
    fuller(s, t4[:1])
    aff = [("${device.model}", "=", V100, 50)]
    s.place(job_of([ask(affinities=aff)], "g"), t4[1:2])
    assert s.broke()[0] == {}
    two = copy.deepcopy(fleet)
    for n in of_class(two, "c1x-t4"):
        n["devices"].append(dict(n["devices"][0], model="Tesla P100",
                                 ids=["p0", "p1"]))
    s = Scene(two)
    t4 = of_class(two, "c1x-t4")
    fuller(s, t4[:1])
    s.place(job_of([ask(count=2)], "g"), t4[1:2])
    assert s.judge()[0]["rank_gap"]["value"] == 0.0


def test_a_plan_is_judged_without_the_room_its_entry_gave_another(fleet):
    """Another scheduler's plan took the best nodes in the same entry
    (one create_index): the applier kept this plan's third choice alone,
    and the rest came in a second plan. The nodes the other took hold no
    room for it, whichever plan the replay takes first."""
    t4 = of_class(fleet, "c1x-t4")
    for first in ("g", "h"):
        s = Scene(fleet)
        fuller(s, t4[:2])
        g = job_of([ask()], "g", count=3)
        h = job_of([ask(count=4)], "h", cpu=10)
        plans = {"g": (g, t4[2:3]), "h": (h, t4[:2])}
        second = "h" if first == "g" else "g"
        s.place(*plans[first])
        s.place(*plans[second], index=s.index)
        g["count"] = 3
        s.place(g, t4[3:5])
        assert len(s.allocs["g"]) == 3
        assert s.broke(lanes=1)[0] == {}, first
    # a plan that came short, with no other in its entry, is held to
    # the k-th best with room, its kept nodes' count
    s = Scene(fleet)
    fuller(s, t4[:2])
    g = job_of([ask()], "g", count=3)
    s.place(g, t4[2:3])
    g["count"] = 3
    s.place(g, t4[3:5])
    assert "rank_gap" in s.broke(lanes=1)[0]
    # the other plan's room goes only where it leaves none: two T4 of 4
    # left free keep its nodes in the pool
    s = Scene(fleet)
    fuller(s, t4[:2])
    s.place(job_of([ask()], "g"), t4[2:3])
    s.place(job_of([ask(count=2)], "h", cpu=10), t4[:2], index=s.index)
    assert "rank_gap" in s.broke(lanes=1)[0]


@pytest.mark.parametrize("kept,gap", [(2, False), (1, True), (0, True)])
def test_a_plan_committed_in_part_is_held_to_the_names_it_lost(
        fleet, kept, gap):
    """The applier kept one placement of three (its instances collided
    with a plan committed since the scheduler's snapshot); the retry
    placed the names it lost on the two best nodes. Names below the kept
    one were ranked above it; names above it say nothing: kept as the
    first or the second, it came too deep."""
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")
    fuller(s, t4[:2])
    g = job_of([ask()], "g", count=3)
    s.place(g, t4[2:3], names=[kept])
    g["count"] = 3
    s.place(g, t4[:2], names=[n for n in range(3) if n != kept])
    assert len(s.allocs["g"]) == 3
    assert s.broke()[0] == ({"rank_gap": pytest.approx(0.0829, abs=1e-3)}
                            if gap else {})


def test_one_allocation_holding_an_instance_in_two_grants_conflicts(fleet):
    s = Scene(fleet)
    t4 = of_class(fleet, "c1x-t4")[0]
    ids = t4["devices"][0]["ids"]
    job = job_of([ask(), ask(name="gpu")], "g", cpu=10)
    s.place(job, [t4], [grant(t4, ids[0]) + grant(t4, ids[1])])
    assert s.broke()[0] == {}
    s = Scene(fleet)
    s.place(job, [t4], [grant(t4, ids[0]) + grant(t4, ids[0])])
    broke, found = s.broke()
    assert broke == {"device_conflicts": 1}
    assert "granted in 2 of its grants" in found["device_conflicts"][0]


# -- the plain scheduler and its two device controls ----------------------

def plain_run(fleet, broken=None, n=24):
    reqs = traffic.closed_loop(MIX, 11, 3.0, DCS)
    jobs = [j for r in reqs for j in r.jobs][:n]
    backlog = fleetlib.backlog_usage(CFG, fleet)
    plain = ref.PlainScheduler(fleet, backlog, PORTS, broken=broken)
    for job in jobs:
        plain.submit(job)
    full = [plain.full[i] for i in ref.device_sample(
        fleet, jobs, plain.allocs, 16, random.Random(1))]
    compared, found = ref.judge(fleet, backlog, jobs, plain.evals,
                                plain.allocs, [], [], PORTS, 2,
                                CFG["server"]["decorrelation"],
                                device_full=full)
    return plain, compared, found


@pytest.mark.parametrize("broken,number", [
    (None, None), ("devblind", "infeasible"),
    ("devtwice", "device_conflicts")])
def test_the_plain_scheduler_and_its_device_controls(fleet, broken, number):
    plain, compared, found = plain_run(fleet, broken)
    broke = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert broke == ([number] if number else []), found
    if broken is None:
        # every grant: first free ids of the node's one group, in order
        held = set()
        for stubs in plain.allocs.values():
            for a in stubs:
                for g in plain.full[a["id"]]["allocated_resources"][
                        "tasks"]["train"]["devices"]:
                    assert not set(g["device_ids"]) & held
                    held |= set(g["device_ids"])
        assert held


def test_the_plain_scheduler_prefers_the_affinity_s_model(fleet):
    aff = [["${device.model}", "=", V100, 50]]
    card = [{"name": "nvidia/gpu", "count": 1,
             "constraints": [list(MEMORY)], "affinities": aff}]
    backlog = fleetlib.backlog_usage(CFG, fleet)
    plain = ref.PlainScheduler(fleet, backlog, PORTS)
    plain.submit(traffic.plain_job(MIX, "a", 4, DCS, card))
    by_id = {n["id"]: n for n in fleet}
    assert {by_id[a["node_id"]]["class"] for a in plain.allocs["a"]} \
        == {"c2x-v100"}
    # without it the fuller 1x T4 nodes win the bin-pack
    del card[0]["affinities"]
    plain.submit(traffic.plain_job(MIX, "b", 4, DCS, card))
    assert {by_id[a["node_id"]]["class"] for a in plain.allocs["b"]} \
        == {"c1x-t4"}


def test_room_is_what_the_free_instances_grant(fleet):
    t4 = of_class(fleet, "c1x-t4")[0]
    devices = ref.DeviceFleet(fleet)
    row = fleet.index(t4)
    granted = devices.instances * 0
    job = job_of([ask(count=2)])
    assert devices.room(job, granted)[row] == 2
    granted[row, 0] = 3
    assert devices.room(job, granted)[row] == 0
    assert devices.room(job_of([]), granted)[row] > 10 ** 9
    need, known = devices.takes(job)
    assert need[row].tolist() == [2] and known.all()
    assert not np.any(need[[i for i, n in enumerate(fleet)
                            if "devices" not in n]])
