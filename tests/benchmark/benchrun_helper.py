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


def stream_manifest(directory):
    m = json.loads(json.dumps(MANIFEST))
    m["workloads"].append({"name": STREAM, "config": "prod-10k",
                           "traffic": "service-stream", "chips": 1,
                           "why": "toy rehearsal of the open loop"})
    for metric in m["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].append(STREAM)
    path = os.path.join(directory, "manifest.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def rehearse(cell, *extra, patch="", seconds="3", nodes="640"):
    with tempfile.TemporaryDirectory() as tmp:
        argv = ["--workload", cell, "--seed", "2147483999", "--seconds",
                seconds, "--rehearse-cpu", "--nodes", nodes, *extra]
        if cell == STREAM:
            argv += ["--manifest", stream_manifest(tmp)]
        code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
                f"import benchmark.run as run\n{patch}\n"
                f"sys.exit(run.main({argv!r}))\n")
        p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env(),
                           capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
