"""Shared by the run.py tests: drive benchmark/run.py in a process of its
own, as a toy rehearsal on the CPU, optionally with the program patched
underneath (the patch runs in that process before main())."""
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmark", "run.py")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# the open-loop mix is held back (PERF.md, Open questions 1): no cell of
# BENCHMARK.json runs it, so the tests rehearse it from a manifest of
# their own, reporting the metrics that have files
STREAM = "toy_service-stream"


def env():
    e = dict(os.environ)
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("BENCH_RUN", None)
    return e


def _add_cell(m, name, config, mix, why):
    m["workloads"].append({"name": name, "config": config, "traffic": mix,
                           "chips": 1, "why": why})
    for metric in m["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].append(name)


def _written(m, directory):
    path = os.path.join(directory, "manifest.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def stream_manifest(directory):
    m = json.loads(json.dumps(MANIFEST))
    _add_cell(m, STREAM, "prod-10k", "service-stream",
              "toy rehearsal of the open loop")
    return _written(m, directory)


# PR 32's toy preempting deployment: files that are all new and all
# the tests' own (the mix tests/benchmark/traffic/toy-evict.json and two
# tiered configurations beside this file), through the harness as it
# stands: it finds a mix under the manifest's `paths`
EVICT = "preempt-toy_toy-evict"
EVICT_ROOM = "preempt-toy-room_toy-evict"


def evict_manifest(directory):
    """BENCHMARK.json grown by the toy's two configurations and a cell
    for each on the `toy-evict` mix."""
    m = json.loads(json.dumps(MANIFEST))
    for cell in (EVICT, EVICT_ROOM):
        config = cell.split("_")[0]
        m["configs"].append({
            "name": config, "source": "the tests' toy: no deployment",
            "file": f"tests/benchmark/{config}.json", "reduced": [],
            "why": "three resident tiers, service preemption on"})
        _add_cell(m, cell, config, "toy-evict",
                  "toy rehearsal of a preempting deployment")
    return _written(m, directory)


# the toy GPU fleet: a configuration and a mix that are the tests' own
# (tests/benchmark/gpu-toy.json, tests/benchmark/traffic/toy-gpu.json):
# device groups on two machine classes, jobs that ask for devices
GPU = "gpu-toy_toy-gpu"


def gpu_manifest(directory):
    """BENCHMARK.json grown by the toy GPU fleet and a cell on it."""
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({
        "name": "gpu-toy", "source": "the tests' toy: no deployment",
        "file": "tests/benchmark/gpu-toy.json", "reduced": [],
        "why": "device groups on two machine classes"})
    _add_cell(m, GPU, "gpu-toy", "toy-gpu",
              "toy rehearsal of a fleet with devices")
    return _written(m, directory)


def rehearse(cell, *extra, patch="", seconds="3", nodes="640"):
    with tempfile.TemporaryDirectory() as tmp:
        argv = ["--workload", cell, "--seed", "2147483999", "--seconds",
                seconds, "--rehearse-cpu", "--nodes", nodes, *extra]
        if cell == STREAM:
            argv += ["--manifest", stream_manifest(tmp)]
        if cell in (EVICT, EVICT_ROOM):
            argv += ["--manifest", evict_manifest(tmp)]
        if cell == GPU:
            argv += ["--manifest", gpu_manifest(tmp)]
        code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
                f"import benchmark.run as run\n{patch}\n"
                f"sys.exit(run.main({argv!r}))\n")
        p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env(),
                           capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
