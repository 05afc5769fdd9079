"""bench_multichip: the mesh-residency ladder scenario (ISSUE 12).

The MULTICHIP artifacts prove the SPMD program runs; this scenario
measures what residency buys it: the SAME warm eval stream driven
through the full scheduler path twice — once with the node axis
sharded over a forced 8-device CPU mesh (NOMAD_TPU_MESH=1, the
mesh-resident table live) and once single-device (NOMAD_TPU_MESH=0) —
recording placements/s for both arms plus the mesh arm's H2D economics:
`mesh_reupload_bytes` (full-column sharded uploads inside the TIMED
window — ZERO in a healthy steady state; the cold upload lands in
`mesh_reupload_bytes_total`) against the dense per-dispatch column
footprint the un-resident path would ship every eval.

Run shape: the mesh needs 8 virtual CPU devices configured BEFORE jax
initializes a backend, and bench.py has already initialized one — so
`run_multichip_bench` drives this module's `main()` in a child pinned
to JAX_PLATFORMS=cpu and parses its one JSON line. The child never
needs an accelerator; bench.py calls it only when its own platform is
the CPU, so no key here is ever filed under a device's name.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict


def _seed_harness(n_nodes: int):
    from ..mock import fixtures as mock
    from ..scheduler.harness import Harness
    h = Harness()
    for i in range(n_nodes):
        node = mock.node()
        # deterministic ids: table order (sorted by id) must match
        # between the meshed and single-device arms
        node.id = f"9a51a7b0-{i:04d}-4000-8000-0000000{i:05d}"
        node.name = f"mc-{i}"
        node.datacenter = f"dc{(i % 4) + 1}"
        node.meta["rack"] = f"r{i % 8}"
        node.compute_class()
        h.store.upsert_node(h.next_index(), node)
    return h


def _make_job(i: int, count: int):
    from ..mock import fixtures as mock
    job = mock.job()
    job.id = f"mc-svc-{i}"
    job.datacenters = [f"dc{d}" for d in (1, 2, 3, 4)]
    tg = job.task_groups[0]
    tg.count = count
    for t in tg.tasks:
        t.resources.networks = []
    tg.networks = []
    return job


def _eval_for(job):
    from ..models import (Evaluation, EVAL_STATUS_PENDING,
                          TRIGGER_JOB_REGISTER)
    from ..utils.ids import generate_uuid
    return Evaluation(
        id=generate_uuid(), namespace=job.namespace,
        priority=job.priority, triggered_by=TRIGGER_JOB_REGISTER,
        job_id=job.id, status=EVAL_STATUS_PENDING, type=job.type)


def _run_arm(mesh_on: bool, n_nodes: int, n_evals: int,
             count: int) -> Dict:
    """One arm of the comparison: warm evals (compiles + the cold
    resident upload) outside the timer, then a timed eval stream whose
    plan applies drive the delta path between dispatches."""
    from ..ops.select import mesh_stats_snapshot
    os.environ["NOMAD_TPU_MESH"] = "1" if mesh_on else "0"
    h = _seed_harness(n_nodes)
    for w in range(3):
        job = _make_job(10**6 + w, count)
        h.store.upsert_job(h.next_index(), job)
        h.process("service", _eval_for(job))
    stats0 = mesh_stats_snapshot() if mesh_on else {}
    placed = 0
    n_warm_plans = len(h.plans)
    t0 = time.perf_counter()
    for i in range(n_evals):
        job = _make_job(i, count)
        h.store.upsert_job(h.next_index(), job)
        h.process("service", _eval_for(job))
    wall = time.perf_counter() - t0
    stats1 = mesh_stats_snapshot() if mesh_on else {}
    for plan in h.plans[n_warm_plans:]:
        placed += sum(len(a) for a in plan.node_allocation.values())
    out = {"rate": placed / max(wall, 1e-9), "placed": placed,
           "wall_s": wall}
    if mesh_on:
        for key in ("reshard_uploads", "reshard_bytes",
                    "delta_scatters", "resident_hits", "stale_misses"):
            out[key] = int(stats1.get(key, 0)) - int(stats0.get(key, 0))
        out["devices"] = int(stats1.get("devices", 0))
        out["reshard_bytes_total"] = int(stats1.get("reshard_bytes", 0))
        out["resident_bytes_per_device"] = float(
            stats1.get("resident_bytes_per_device", 0.0))
    return out


def run_scenario(n_nodes: int, n_evals: int, count: int) -> Dict:
    """Both arms, in-process (kernels re-read NOMAD_TPU_MESH per eval
    since engines rebuild them). Must run under a multi-device
    platform — main() forces the 8-device virtual CPU mesh."""
    prev = os.environ.get("NOMAD_TPU_MESH")
    try:
        on = _run_arm(True, n_nodes, n_evals, count)
        off = _run_arm(False, n_nodes, n_evals, count)
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_MESH", None)
        else:
            os.environ["NOMAD_TPU_MESH"] = prev
    # the dense per-dispatch footprint the un-resident mesh path paid:
    # capacity + used (n_pad x D x 4 B each) + free_ports (n_pad x 4 B)
    # per dispatch — the comparison basis for mesh_reupload_bytes
    from ..ops.select import _pad_n
    from ..ops.tables import RES_DIMS
    n_pad = _pad_n(n_nodes)
    dense = n_pad * (2 * RES_DIMS * 4 + 4)
    return {
        "mesh_devices": on.get("devices", 0),
        "mesh_placements_per_sec": round(on["rate"], 1),
        "mesh_placements_per_sec_off": round(off["rate"], 1),
        "mesh_speedup": round(on["rate"] / max(off["rate"], 1e-9), 2),
        "mesh_placed": on["placed"],
        # steady-state H2D economics: full-column re-uploads inside the
        # timed window (target 0 — the zero-reupload acceptance bar),
        # the cold/warmup upload total, and the per-dispatch dense
        # bytes the NOMAD_TPU_MESH=0-era mesh path shipped per eval
        "mesh_reupload_bytes": on.get("reshard_bytes", 0),
        "mesh_reupload_bytes_total": on.get("reshard_bytes_total", 0),
        "mesh_reshard_uploads": on.get("reshard_uploads", 0),
        "mesh_delta_scatters": on.get("delta_scatters", 0),
        "mesh_resident_hits": on.get("resident_hits", 0),
        "mesh_dense_bytes_per_dispatch_off": dense,
        "mesh_resident_bytes_per_device": round(
            on.get("resident_bytes_per_device", 0.0), 1),
    }


def main() -> None:
    """Subprocess entry: force the 8-device virtual CPU platform
    BEFORE any backend initializes, run both arms, print ONE JSON
    line."""
    from ..utils.platform import (assert_cpu_devices,
                                  configure_compile_cache,
                                  force_cpu_platform)
    force_cpu_platform(8)
    configure_compile_cache()
    assert_cpu_devices(8)
    quick = os.environ.get("NOMAD_TPU_BENCH_QUICK", "") not in ("", "0")
    out = run_scenario(n_nodes=192 if quick else 1000,
                       n_evals=6 if quick else 20,
                       count=8 if quick else 10)
    print(json.dumps(out))


def run_multichip_bench(quick: bool = False,
                        timeout_s: float = 600.0) -> Dict:
    """Drive main() in a CPU-pinned child (this process's jax backend
    is already initialized single-device) and return its artifact keys.
    Raises when the child fails; bench.py records that as a failed
    phase."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["NOMAD_TPU_BENCH_QUICK"] = "1" if quick else "0"
    res = subprocess.run(
        [sys.executable, "-m", "nomad_tpu.bench.multichip"],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    if res.returncode != 0:
        raise RuntimeError(f"multichip child rc={res.returncode}: "
                           f"{res.stderr[-500:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    main()
