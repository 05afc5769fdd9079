"""The svc-10k deployment and its cell (ISSUE 27): the manifest's new
entries and their files, the room the fleet leaves the mix, what a seed
may change of the mix, the toy rehearsal's arms and controls, and the
reader of the scan arm's share."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import fleet as fleetlib        # noqa: E402
from benchmark.lib import reference as ref         # noqa: E402
from benchmark.lib import traffic                  # noqa: E402
from benchrun_helper import MANIFEST, rehearse     # noqa: E402

CELL = "svc-10k_service-fill"
DCS = ["dc1", "dc2", "dc3", "dc4"]
NEW_METRICS = {
    "mask_build_ms_per_eval.batch": ("stage_per_eval", "scheduler host"),
    "mask_builds_per_eval.batch": ("stage_count_per_eval", "scheduler host"),
    "spread_inputs_ms_per_eval.batch": ("stage_per_eval", "scheduler host"),
    "port_assign_ms_per_eval.batch": ("stage_per_eval", "scheduler host"),
    "scan_dispatch_share.batch": ("arm_share", "select kernel")}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("configs", "svc-10k.json")


@pytest.fixture(scope="module")
def mix():
    return traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                         "service-fill.json"))


# -- the manifest's entries and their files -----------------------------

def _one(entries, name):
    entry, = [e for e in entries if e["name"] == name]
    return entry


def test_manifest_holds_the_config_the_cell_and_its_claim_on_the_rate():
    config = _one(MANIFEST["configs"], "svc-10k")      # there, and once
    assert config["file"] == "benchmark/configs/svc-10k.json"
    assert config["reduced"] == []
    assert config["source"] != _one(MANIFEST["configs"],
                                    "prod-10k")["source"]
    cell = _one(MANIFEST["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "svc-10k", "service-fill", 1)
    rate = _one(MANIFEST["end_to_end"], "placements_per_s")
    # both accepted cells report the rate; a later cell may too
    assert {"prod-10k_batch-fill", CELL} <= set(rate["workloads"])
    assert len(rate["workloads"]) == len(set(rate["workloads"]))


PER_LAYER_ORDER = ["mask_build_ms_per_eval.batch",
                   "mask_builds_per_eval.batch",
                   "spread_inputs_ms_per_eval.batch",
                   "port_assign_ms_per_eval.batch",
                   "scan_dispatch_share.batch"]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_this_cell_s_alone_and_names_its_reader(name):
    entry = _one(MANIFEST["per_layer"], name)
    reader, layer = NEW_METRICS[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "placements_per_s" and entry["layer"] == layer
    spec = load("metrics", name + ".json")
    assert spec["reader"] == reader
    # entries are appended, never moved: PR 27's five stand after PR
    # 26's wal_encode_ms_per_eval.batch, in the order they were added;
    # what later PRs append comes after them and is theirs to hold
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert sorted(PER_LAYER_ORDER) == sorted(NEW_METRICS)
    at = [names.index(n) for n in PER_LAYER_ORDER]
    assert at == sorted(at)
    assert names.index("wal_encode_ms_per_eval.batch") < names.index(name)


def test_the_cell_reports_the_new_metrics_and_the_batch_cell_does_not():
    from benchmark.run import plan_cell
    mine = {m["name"] for m in plan_cell(MANIFEST, CELL)["per_layer"]}
    theirs = {m["name"] for m in plan_cell(
        MANIFEST, "prod-10k_batch-fill")["per_layer"]}
    assert set(NEW_METRICS) <= mine and not set(NEW_METRICS) & theirs
    # what has no workloads key is reported in both cells: the scan
    # arm's roofline share and the device's idle share among them
    unkeyed = {m["name"] for m in MANIFEST["per_layer"]
               if "workloads" not in m and m["moves"] == "placements_per_s"}
    assert unkeyed <= mine and unkeyed <= theirs
    assert {"kernel_roofline.batch", "kernel_device_ms_per_eval.batch",
            "device_idle_share.batch", "feasibility_ms_per_eval.batch",
            "plan_submits_per_eval.batch"} <= unkeyed


def test_config_is_prod_10k_s_fleet_with_the_population_and_guarantees(cfg):
    prod = load("configs", "prod-10k.json")
    for key in ("nodes", "datacenters", "racks", "resident_allocs_per_node",
                "resident_alloc", "node", "machine_classes",
                "dynamic_port_range", "server"):
        assert cfg[key] == prod[key], key
    assert cfg["nodes"] == 10000 and cfg["server"]["num_schedulers"] == 2
    assert cfg["guarantees"][:5] == prod["guarantees"]
    assert any("spread target" in g for g in cfg["guarantees"][5:])
    assert any("ports" in g and "unique" in g for g in cfg["guarantees"][5:])
    jobs = cfg["jobs"]
    assert jobs["type"] == "service" and jobs["instances_per_job"] == [1, 50]
    assert (jobs["constraints_per_job"], jobs["affinities_per_job"],
            jobs["spreads_per_job"], jobs["dynamic_ports_per_instance"]) \
        == (2, 1, 1, 2)
    assert cfg["reduced"] == {} and len(cfg["assumed"]) >= 6


def test_mix_is_the_issue_s_letter_for_letter(mix):
    stream = load("traffic", "service-stream.json")
    assert mix["loop"] == "closed" and mix["name"] == "service-fill"
    assert (mix["in_flight_per_scheduler"], mix["bulk"],
            mix["max_jobs_per_s"]) == (4, 4, 60)
    assert mix["deck"] == [1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 5, 5, 5,
                           10, 10, 10, 20, 20, 50] == stream["deck"]
    assert sum(mix["deck"]) / len(mix["deck"]) == 7.8
    assert mix["job"] == stream["job"]
    job = mix["job"]
    assert (job["type"], job["driver"], job["dynamic_ports"]) == (
        "service", "exec", 2)
    assert job["ask"] == {"cpu": 250, "memory_mb": 256, "disk_mb": 150,
                          "mbits": 50}
    assert job["constraints"] == [["${attr.kernel.name}", "=", "linux"],
                                  ["${meta.rack}", "regexp", "^r[0-9]$"]]
    assert job["affinities"] == [["${meta.rack}", "=", "r3", 50]]
    assert job["spreads"] == [["${node.datacenter}", 50,
                               [["dc1", 40], ["dc2", 30]]]]
    assert (mix["rehearse_s"], mix["drain_s"], mix["port_check_allocs"],
            mix["profile_s"]) == (6, 60, 48, 15)
    warm = mix["warmup"]
    assert warm["solo"] == stream["warmup"]["solo"]
    assert warm["bursts"] == stream["warmup"]["bursts"] + [
        [1, 2, 5, 10], [3, 20, 50, 1], [50, 50, 50, 50, 20, 20, 10, 10]]


# -- the room the mix counts on -----------------------------------------

def test_fleet_leaves_the_room_the_mix_counts_on(cfg, mix):
    """10 of 16 racks pass the regexp: 6,256 nodes, with room for about
    100,000 instances beside the backlog — ten windows' worth at the
    mix's ceiling pace; the affinity's rack alone holds a window."""
    fleet = fleetlib.build_fleet(cfg, 5)
    backlog = fleetlib.backlog_usage(cfg, fleet)
    job = traffic.plain_job(mix, "room", 1, DCS)
    feasible = [n for n in fleet if not ref.node_feasible(n, job)]
    assert len(feasible) == 6256

    def room(n):
        return int(min((n["capacity"][d] - backlog[n["id"]][d])
                       // job["ask"][d] for d in fleetlib.DIMS))
    total = sum(room(n) for n in feasible)
    assert 95000 <= total <= 110000
    r3 = [n for n in feasible if n["meta"]["rack"] == "r3"]
    assert len(r3) == 628 and sum(room(n) for n in r3) >= 10000
    by_dc = collections.Counter(n["datacenter"] for n in feasible)
    assert set(by_dc.values()) == {6256 // 4}
    # two ports an instance fit the dynamic range many times over
    lo, hi = cfg["dynamic_port_range"]
    assert 2 * max(room(n) for n in feasible) < (hi - lo) / 10


# -- what a seed may change ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 77, 2**31 + 5])
def test_sizes_and_bulk_layout_are_the_same_for_every_seed(mix, seed):
    base = traffic.closed_loop(mix, 0, 51.0, DCS)
    got = traffic.closed_loop(mix, seed, 51.0, DCS)
    assert len(got) == len(base)
    assert all(len(r.jobs) == mix["bulk"] == 4 for r in got)
    sizes = collections.Counter(j["count"] for r in got for j in r.jobs)
    assert sizes == collections.Counter(
        j["count"] for r in base for j in r.jobs)
    assert set(sizes) == set(mix["deck"])
    assert [j["count"] for r in got for j in r.jobs] != \
        [j["count"] for r in base for j in r.jobs]
    # the request list outlasts the fastest program the mix allows for
    assert len(got) * mix["bulk"] >= 51.0 * mix["max_jobs_per_s"]
    body = json.loads(got[0].body)
    assert isinstance(body, list) and len(body) == 4
    tg = body[0]["Job"]["task_groups"][0]
    assert len(tg["constraints"]) == 2 and len(tg["affinities"]) == 1
    assert len(tg["spreads"]) == 1
    nw, = tg["tasks"][0]["resources"]["networks"]
    assert nw["mbits"] == 50 and len(nw["dynamic_ports"]) == 2


def test_warmup_meets_every_lane_count_the_window_can(mix):
    rounds = traffic.warmup_requests(mix, 1, DCS)
    lanes = {len(r[0].jobs) for r in rounds}
    assert {1, 2, 4, 8} <= lanes        # 8 jobs in flight at most
    mixed = [sorted(j["count"] for j in r[0].jobs) for r in rounds
             if len({j["count"] for j in r[0].jobs}) > 1]
    assert len(mixed) >= 3              # lanes of unequal size too


# -- the toy rehearsal: its arms, and the controls ----------------------

def test_cpu_rehearsal_runs_on_the_scan_arm_alone():
    line, err = rehearse(CELL, "--trace", "0")
    assert line["correct"] is True and line["failed"] == 0, err[-3000:]
    assert set(line["arms"]) <= {"scan", "scan_batched"}, line["arms"]
    assert line["arms"].get("scan", 0) > 0
    for name in ("infeasible", "port_conflicts", "spread_over_target",
                 "over_capacity", "lost_or_duplicated"):
        assert line["compared"][name]["value"] == 0
    assert "placements_per_s" in line["metrics"]


@pytest.mark.parametrize("broken,number", [
    ("none", None), ("constraints", "infeasible"),
    ("ports", "port_conflicts"), ("capacity", "over_capacity")])
def test_control_on_the_cell(broken, number):
    line, err = rehearse(CELL, "--control", broken, "--control-jobs", "160")
    if number is None:
        assert line["correct"] is True, err[-3000:]
        assert all(c["value"] == 0 for c in line["compared"].values())
        return
    assert line["correct"] is False
    assert line["compared"][number]["value"] > 0
    wrong = {n for n, c in line["compared"].items()
             if c["value"] > c["limit"]}
    assert number in wrong


# -- the reader ---------------------------------------------------------

def test_arm_share_reads_a_recorded_obs():
    from benchmark.readers import arm_share as reader
    obs = {"routing": {
        "before": {"scan": 40, "scan_batched": 10, "kway": 3},
        "after": {"scan": 130, "scan_batched": 16, "scan@cpu": 3,
                  "kway": 3, "chunked": 1}}}
    # 90 + 6 of 100 in the window; the host's three count for no one
    assert reader.read(obs, ["scan", "scan_batched"]) == 96.0
    assert reader.read(obs, ["scan"]) == 90.0
    assert reader.read(obs, ["scan@cpu"]) == 0.0
    assert reader.read(obs, ["kway"]) == 0.0
    assert reader.read({}, ["scan"]) is None
    quiet = {"routing": {"before": {"scan": 5}, "after": {"scan": 5}}}
    assert reader.read(quiet, ["scan"]) is None
