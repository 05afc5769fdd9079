"""The manifest's lists take additions: a copy of BENCHMARK.json that
has grown by a configuration, a cell that reports `placements_per_s`
and a per-layer metric of that cell's own, with their files laid in a
temp tree, passes every manifest-level test of this directory as it
stands — the next `model_config` PR edits no file here."""
import inspect
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark_manifest as tm            # noqa: E402
import test_benchmark_service_fill as ts        # noqa: E402
from benchrun_helper import MANIFEST, ROOT      # noqa: E402
from benchmark.run import plan_cell             # noqa: E402

CONFIG, CELL = "next-10k", "next-10k_next-fill"
METRIC = "next_ms_per_eval.batch"
ACCEPTED = ["prod-10k_batch-fill", "svc-10k_service-fill"]


@pytest.fixture()
def grown(tmp_path):
    """(manifest, root): the copy with its three entries appended, and
    a tree that holds BENCHMARK.json and the data files under `paths`."""
    root = str(tmp_path)
    for part in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", part),
                        os.path.join(root, "benchmark", part))
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    m = json.loads(json.dumps(MANIFEST))
    with open(os.path.join(root, "benchmark", "configs",
                           "prod-10k.json")) as f:
        body = dict(json.load(f), name=CONFIG)
    # the next deployment preempts (PR 32): its backlog comes in tiers
    # and it states a scheduler configuration, as the tests' toy does
    with open(os.path.join(HERE, "preempt-toy.json")) as f:
        toy = json.load(f)
    body.update({k: toy[k] for k in ("resident_tiers",
                                     "scheduler_configuration")})
    # and its machines carry devices, its jobs ask for them, as the
    # tests' GPU toy's do
    with open(os.path.join(HERE, "gpu-toy.json")) as f:
        body["machine_classes"] = json.load(f)["machine_classes"]
    with open(os.path.join(root, "benchmark", "configs",
                           CONFIG + ".json"), "w") as f:
        json.dump(body, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "batch-fill.json")) as f:
        mix = json.load(f)
    with open(os.path.join(HERE, "traffic", "toy-gpu.json")) as f:
        gpu = json.load(f)
    mix.update({k: gpu[k] for k in ("device_deck", "device_check_nodes")})
    with open(os.path.join(root, "benchmark", "traffic", "next-fill.json"),
              "w") as f:
        json.dump(mix, f)
    m["configs"].append({
        "name": CONFIG, "source": "a public source of its own",
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "the deployment the next model_config PR brings"})
    m["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "next-fill", "chips": 1,
        "why": "what the next cell exercises and bypasses"})
    for metric in m["end_to_end"]:
        if metric["name"] == "placements_per_s":
            metric["workloads"].append(CELL)
    entry = {"name": METRIC, "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "scheduler host",
             "moves": "placements_per_s", "workloads": [CELL]}
    m["per_layer"].append(entry)
    spec = {k: entry[k] for k in ("name", "unit", "source", "layer", "moves")}
    with open(os.path.join(root, "benchmark", "metrics",
                           METRIC + ".json"), "w") as f:
        json.dump(dict(spec, kind="per_layer", reader="stage_per_eval",
                       args={"stage": "preempt"}), f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    return m, root


def _tests(module, reading=""):
    """The module's test functions whose source mentions `reading`."""
    return [(name, fn) for name, fn in sorted(vars(module).items())
            if name.startswith("test_") and inspect.isfunction(fn)
            and reading in inspect.getsource(fn)]


def _run(fn, name, over):
    """One test function over its cases: none for a plain test, the
    manifest's entries where test_benchmark_manifest.over() names them,
    else the values its own parametrize mark carries."""
    params = list(inspect.signature(fn).parameters)
    if not params:
        fn()
        return 1
    assert len(params) == 1, f"{name} takes a fixture: not manifest-level"
    if name in over:
        cases = over[name]
    else:
        mark, = [k for k in getattr(fn, "pytestmark", [])
                 if k.name == "parametrize"]
        cases = mark.args[1]
    for case in cases:
        fn(case)
    return len(cases)


def test_a_grown_manifest_passes_every_manifest_level_test(grown, monkeypatch):
    m, root = grown
    monkeypatch.setattr(tm, "M", m)
    monkeypatch.setattr(tm, "CELLS", [w["name"] for w in m["workloads"]])
    monkeypatch.setattr(tm, "METRICS", m["end_to_end"] + m["per_layer"])
    monkeypatch.setattr(tm, "ROOT", root)
    monkeypatch.setattr(ts, "MANIFEST", m)
    monkeypatch.setattr(ts, "ROOT", root)
    over = tm.over(m)
    # every test of the manifest's own file, and of the service cell's
    # file those that read the manifest
    mine = _tests(tm)
    theirs = _tests(ts, reading="MANIFEST")
    assert len(mine) >= 12 and len(theirs) == 3
    assert set(over) <= {name for name, _fn in mine}
    ran = sum(_run(fn, name, over) for name, fn in mine + theirs)
    # the grown lists were walked, not the module's own
    assert ran > len(m["per_layer"]) * 2 + len(m["configs"]) \
        + len(m["workloads"]) * 2


def test_the_grown_config_is_tiered_and_the_accepted_ones_are_not(grown):
    _m, root = grown
    def cfg(name):
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)
    keys = {"resident_tiers", "scheduler_configuration"}
    assert keys <= set(cfg(CONFIG))
    assert not keys & set(cfg("prod-10k")) and not keys & set(cfg("svc-10k"))
    for mix in ("batch-fill", "service-fill", "service-stream"):
        with open(os.path.join(root, "benchmark", "traffic",
                               mix + ".json")) as f:
            assert "priority" not in json.load(f)["job"]
    # devices: the grown configuration and mix have them, no accepted one
    assert any("devices" in c for c in cfg(CONFIG)["machine_classes"])
    for name in ("prod-10k", "svc-10k", "preempt-10k"):
        assert not any("devices" in c for c in cfg(name)["machine_classes"])
    for mix in ("batch-fill", "service-fill", "service-stream",
                "service-evict", "next-fill"):
        with open(os.path.join(root, "benchmark", "traffic",
                               mix + ".json")) as f:
            body = json.load(f)
        grown = mix == "next-fill"
        assert "devices" not in body["job"]
        assert ("device_deck" in body) is grown
        assert ("device_check_nodes" in body) is grown


def test_a_new_cell_gets_the_unkeyed_metrics_and_its_own(grown):
    m, _root = grown
    plan = plan_cell(m, CELL)
    got = [x["name"] for x in plan["per_layer"]]
    unkeyed = [x["name"] for x in MANIFEST["per_layer"]
               if "workloads" not in x]
    assert got == unkeyed + [METRIC]
    assert {x["name"] for x in plan["end_to_end"]} == {"placements_per_s",
                                                       "setup_s"}
    assert plan["config_file"] == f"benchmark/configs/{CONFIG}.json"


@pytest.mark.parametrize("cell", ACCEPTED)
def test_accepted_cells_plan_as_they_do_today(grown, cell):
    m, _root = grown
    now, then = plan_cell(m, cell), plan_cell(MANIFEST, cell)
    assert now["per_layer"] == then["per_layer"]
    assert [x["name"] for x in now["end_to_end"]] == \
        [x["name"] for x in then["end_to_end"]]
    assert (now["cell"], now["config_file"]) == (then["cell"],
                                                 then["config_file"])
