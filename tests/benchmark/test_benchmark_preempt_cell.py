"""The preempting cell's shape at toy size (PR 34):
`preempt-10k_service-evict` through `run.py --rehearse-cpu` under a copy
of the manifest, on the program as it stands. Every placement of the
window lands by evicting, the judge reads 0 in the four eviction
numbers, and the metrics the cell brings are in the traced line. Counts
only: nothing a CPU run times is a device number.

At 2,000 nodes and 2 s, not 640 and 3: the program now evicts thousands
of residents in a traced toy run, and on 640 nodes that empties whole
nodes of their priority-20 tier within seconds. On such a node the
judge's two eviction rules cannot both hold (three victims of 100 MHz
and two of 200 for 550: hand one of 100 back and a higher tier was
taken while a lower still runs, keep it and one can be restored; PERF.md
section 7), for upstream's algorithm as for any. The untraced 640-node
rehearsal of every cell stays in test_benchmark_run.py."""
import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchrun_helper import MANIFEST, ROOT, env  # noqa: E402

CELL = "preempt-10k_service-evict"
FOUR = ["evicted_wrongly", "evicted_needlessly", "evicted_with_room",
        "residents_stopped"]
NEW = ["preempt_ms_p50", "preempt_ms_p95", "preempt_share",
       "preempt_rounds_per_eval", "preempt_gather_ms_p50",
       "preempt_kernel_ms_p50"]


@pytest.fixture(scope="module")
def traced():
    with tempfile.TemporaryDirectory() as tmp:
        manifest = os.path.join(tmp, "manifest.json")
        with open(manifest, "w") as f:
            json.dump(MANIFEST, f)
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", CELL, "--manifest", manifest, "--seed",
             "2147483999", "--seconds", "2", "--rehearse-cpu", "--nodes",
             "2000", "--trace", "1"],
            cwd=ROOT, env=env(), capture_output=True, text=True,
            timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_cell_is_in_the_manifest_once_with_its_files():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "preempt-10k",
                    "traffic": "service-evict", "chips": 1,
                    "why": cell["why"]}
    config, = [c for c in MANIFEST["configs"] if c["name"] == "preempt-10k"]
    assert config["reduced"] == [] and len(config["source"]) <= 200
    rate, = [m for m in MANIFEST["end_to_end"]
             if m["name"] == "placements_per_s"]
    assert rate["workloads"][-1] == CELL
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "preempt-toy.json")) as f:
        toy = json.load(f)
    # the toy's fleet as it stands, now with a source
    for key in ("nodes", "datacenters", "racks", "resident_tiers",
                "scheduler_configuration", "node", "machine_classes",
                "server", "guarantees"):
        assert cfg[key] == toy[key], key
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "service-evict.json")) as f:
        mix = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "service-fill.json")) as f:
        fill = json.load(f)
    assert mix["deck"] == fill["deck"]
    assert (mix["in_flight_per_scheduler"], mix["bulk"]) == (2, 2)
    assert mix["job"]["priority"] == 70
    assert mix["job"]["ask"] == {"cpu": 600, "memory_mb": 512,
                                 "disk_mb": 150, "mbits": 0}
    warm = mix["warmup"]
    assert warm["solo"] == sorted(set(mix["deck"]))
    assert len(warm["solo"]) + len(warm["bursts"]) <= 10


def test_the_cell_is_judged_correct_and_the_four_read_zero(traced):
    line, err = traced
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name in FOUR:
        assert line["compared"][name] == {"value": 0, "limit": 0}, name


def test_every_placement_lands_by_evicting(traced):
    _line, err = traced
    m = re.search(r"read back (\d+) resident allocs of (\d+) tier jobs in "
                  r"[\d.]+s: (\d+) evicted, (\d+) placed since", err)
    assert m, err[-3000:]
    evicted = int(m.group(3))
    m = re.search(r"read back (\d+) allocs of (\d+) jobs", err)
    assert m, err[-3000:]
    placed = int(m.group(1))
    # the fleet has no room: an eviction a placement at the least
    assert placed >= 1 and evicted >= placed


@pytest.mark.parametrize("metric", NEW)
def test_the_cells_own_metrics_are_declared_and_in_the_traced_line(
        traced, metric):
    line, _err = traced
    name = metric + ".batch"
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "placements_per_s"
    assert entry["better"] == "lower"
    assert entry["layer"] == ("select kernel" if "kernel" in metric
                              else "scheduler host")
    got = line["metrics"][name]["value"]
    assert got is not None and got >= 0.0


def test_both_selects_ran_an_arm_each(traced):
    line, _err = traced
    # room first on the chunked arm, then the victims' columns into the
    # scan arm: two dispatches an eval's round, and a round a plan
    # (kway besides, when a follow-up eval of an evicted batch job asks
    # for more than 512 replacements at once)
    assert {"chunked", "scan"} <= set(line["arms"]) \
        <= {"chunked", "scan", "kway"}
    assert line["arms"]["scan"] >= 1
    assert line["metrics"]["preempt_rounds_per_eval.batch"]["value"] >= 0.9
