"""BENCHMARK.json against the contract's limits and the harness's files."""
import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def over(m):
    """What each parametrised test of this file runs over, as a
    function of the manifest: test_benchmark_manifest_room.py runs the
    same tests over a manifest that has grown."""
    return {
        "test_config_entry_and_file": m["configs"],
        "test_cell_entry_and_its_files": m["workloads"],
        "test_end_to_end_entry": m["end_to_end"],
        "test_per_layer_entry": m["per_layer"],
        "test_metric_file_names_a_reader_and_agrees":
            m["end_to_end"] + m["per_layer"],
        "test_every_cell_reports_setup_another_metric_and_a_layer":
            [w["name"] for w in m["workloads"]]}


M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]
OVER = over(M)


def spec_of(metric):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric["name"] + ".json")) as f:
        return json.load(f)


def test_top_level_keys_are_exactly_the_contract_s():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)


def test_command_stays_inside_paths():
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in M["paths"])
    assert "tests/benchmark" in M["paths"]


@pytest.mark.parametrize("config", OVER["test_config_entry_and_file"],
                         ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert body["guarantees"] and body["reference"] == "plain_scheduler"
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(w["config"] == config["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", OVER["test_cell_entry_and_its_files"],
                         ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic",
                                       cell["traffic"] + ".json"))


def test_cells_pair_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(CELLS) == len(set(CELLS))
    # one file and one source a configuration, and four chips for at
    # most half of the cells (one always may)
    for key in ("name", "file", "source"):
        assert len({c[key] for c in M["configs"]}) == len(M["configs"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) \
        <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", OVER["test_end_to_end_entry"],
                         ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", OVER["test_per_layer_entry"],
                         ids=lambda m: m["name"])
def test_per_layer_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert metric["moves"] in {m["name"] for m in M["end_to_end"]}
    assert metric["moves"] != "setup_s"
    assert 1 <= len(metric["layer"]) <= 200
    suffix = metric["name"].rsplit(".", 1)[1]
    assert (suffix == "batch") == (metric["moves"] == "placements_per_s")


@pytest.mark.parametrize(
    "metric", OVER["test_metric_file_names_a_reader_and_agrees"],
    ids=lambda m: m["name"])
def test_metric_file_names_a_reader_and_agrees(metric):
    spec = spec_of(metric)
    assert spec["name"] == metric["name"]
    assert spec["unit"] == metric["unit"]
    assert spec["source"] == metric["source"]
    assert spec.get("moves") == metric.get("moves")
    assert spec.get("layer") == metric.get("layer")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)


def test_no_metric_file_without_an_entry():
    names = {m["name"] for m in METRICS}
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "metrics"))}
    assert on_disk == names and len(names) == len(METRICS)


@pytest.mark.parametrize(
    "cell", OVER["test_every_cell_reports_setup_another_metric_and_a_layer"])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    from benchmark.run import plan_cell
    plan = plan_cell(M, cell)
    e2e = {m["name"] for m in plan["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert plan["per_layer"]
    assert all(m["moves"] in e2e for m in plan["per_layer"])


def test_rooflines_are_percentages_and_no_mfu_without_a_model():
    for m in M["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
        assert "mfu" not in m["name"]


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in M["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers) <= 12
