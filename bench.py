"""Benchmark: batched placement throughput, kernel-level and end-to-end.

Headline metric = BASELINE.json config #2 on the raw device kernel: a
batch job with count=10k placed over 1k in-memory nodes — the pure
BinPackIterator path. The reference's headline number for this shape is
the C1M claim of "thousands of container deployments per second" (~5k/s
cluster-wide on 5k nodes,
/root/reference/website/pages/intro/use-cases.mdx:56-58); vs_baseline is
measured placements/sec over that 5000/s reference rate.

Extra keys on the same line (nomad_tpu/bench/ladder.py): the SAME
scenario driven end-to-end through the full control plane
(e2e_placements_per_sec, e2e_vs_baseline), ladder #3 service-job p99
Process() latency over 10k nodes (service_p99_ms; BASELINE target
<= 100 ms), and ladder #4 preemption throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
stamped with the platform, device_kind and device count JAX reports.
This process initializes the ambient backend itself (JAX_PLATFORMS=cpu
set by the caller means CPU; there is no fallback to it). A phase that
raises leaves its "*error" key on the line for diagnosis and the run
exits non-zero.
"""

import json
import sys
import time
import traceback

import numpy as np

BASELINE_RATE = 5000.0  # C1M: "thousands of deployments per second"


def run_kernel_bench():
    """Sustained kernel placement throughput: FOUR 10k-instance batch
    jobs over a 1k-node table placed in ONE device dispatch
    (select_many — multi-eval batching, SURVEY §2.6 row 1: the broker
    queues evals and the device should be fed whole batches of them).
    A sequential per-eval measurement is bounded by two host<->device
    round trips per eval regardless of kernel speed; sustained
    placements/sec is the metric the C1M baseline states."""
    from nomad_tpu.ops.select import SelectKernel, SelectRequest

    n_nodes = 1000
    batch = 10240  # whole job in ONE device dispatch (kernel carries state)
    pipeline = 8   # batches in flight, like queued evals on the broker

    rng = np.random.RandomState(42)
    capacity = np.tile(
        np.array([[4000.0, 8192.0, 102400.0, 1000.0]], np.float32),
        (n_nodes, 1))
    used = (capacity * rng.uniform(0.0, 0.2, size=(n_nodes, 4))).astype(np.float32)
    ask = np.array([100.0, 100.0, 10.0, 0.0], np.float32)  # mock batch task

    kernel = SelectKernel()

    def make_req(count):
        return SelectRequest(
            ask=ask, count=count,
            feasible=np.ones(n_nodes, dtype=bool),
            capacity=capacity, used=used.copy(),
            desired_count=float(count),
            tg_collisions=np.zeros(n_nodes, np.int32),
            job_count=np.zeros(n_nodes, np.int32),
        )

    # warm-up / compile
    kernel.select_many([make_req(batch) for _ in range(pipeline)])

    # median of 5 timed rounds: a single sample misstates steady-state
    # throughput
    rates = []
    for _ in range(5):
        t0 = time.perf_counter()
        results = kernel.select_many([make_req(batch)
                                      for _ in range(pipeline)])
        placed = sum(r.placed for r in results)
        elapsed = time.perf_counter() - t0
        rates.append(placed / elapsed)
    rates.sort()
    return rates[2]


def main() -> int:
    out = {
        "metric": "placements_per_sec_batch10k_1k_nodes",
        "value": 0.0,
        "unit": "placements/s",
        "vs_baseline": 0.0,
    }
    import os
    # governed soak runs must be attributable: record whether the
    # runtime sanitizer's kernel-boundary guards were armed
    from nomad_tpu.analysis.sanitizer import enabled as _sanitize_on
    out["sanitizer"] = "on" if _sanitize_on() else "off"
    # runtime race sanitizer engagement (ISSUE 14): governed runs must
    # record whether the lock shims were instrumenting the process
    from nomad_tpu.analysis.race import enabled as _race_on
    out["race"] = "on" if _race_on() else "off"
    # micro-batch gateway engagement must be attributable per round
    # (ISSUE 7): record whether the env kill switch disabled it
    out["microbatch"] = ("off" if os.environ.get(
        "NOMAD_TPU_MICROBATCH", "1") in ("0", "off") else "on")
    # write-side ingest gateway engagement (ISSUE 19), same discipline
    from nomad_tpu.server.ingest import ingest_batch_enabled
    out["ingest"] = "on" if ingest_batch_enabled() else "off"
    # retained telemetry collector engagement (ISSUE 11)
    from nomad_tpu.telemetry import enabled as _telemetry_on
    out["telemetry"] = "on" if _telemetry_on() else "off"
    quick = os.environ.get("NOMAD_TPU_BENCH_QUICK", "") not in ("", "0")
    try:
        from nomad_tpu.utils.platform import init_backend
        device = init_backend()
        out.update(device)
        # per-stage breakdown (ISSUE 2 satellite): every pipeline stage
        # (host table build / H2D / kernel / D2H / plan apply / broker
        # ack) accumulates wall clock for the whole run and the shares
        # land in the artifact — the kernel-vs-e2e gap is attributable
        # per round instead of inferred
        from nomad_tpu.utils import stages
        stages.enable()
        per_sec = run_kernel_bench()
        out.update({
            "value": round(per_sec, 1),
            "vs_baseline": round(per_sec / BASELINE_RATE, 2),
        })
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        out["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(out))
        return 1

    # the raw-kernel phase is all `kernel` stage by construction;
    # reset so the emitted breakdown attributes the END-TO-END phases
    # (ladder + C2M), where the host-vs-device split is the question
    stages.enable(reset=True)

    # End-to-end ladder (VERDICT r1 item 4): full scheduler path, not
    # just the kernel — BASELINE configs #2/#3/#4. A ladder failure
    # still emits the headline line.
    try:
        from nomad_tpu.bench.ladder import run_ladder
        out.update(run_ladder(quick=quick))
        out["e2e_vs_baseline"] = round(
            out["e2e_placements_per_sec"] / BASELINE_RATE, 2)
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        out["ladder_error"] = f"{type(e).__name__}: {e}"

    # mesh-residency ladder (ISSUE 12): the same warm eval stream over
    # a forced 8-device virtual CPU mesh vs single-device, with the
    # sharded resident table's H2D economics (zero full re-uploads
    # steady state) recorded. It is a CPU dry run in a child pinned to
    # JAX_PLATFORMS=cpu, so it runs only when this artifact is a CPU
    # one: under an accelerator's name its keys are dropped.
    if device["platform"] == "cpu":
        try:
            from nomad_tpu.bench.multichip import run_multichip_bench
            out.update(run_multichip_bench(quick=quick))
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            out["multichip_error"] = f"{type(e).__name__}: {e}"

    # ladder #5 — C2M at its real scale (BASELINE config #5): 50k nodes
    # pre-loaded with 2M running allocs (40k through the real scheduler
    # path, the rest via the replay loader), then batch + service evals
    # against the resident table over the full 2M-row alloc table.
    try:
        from nomad_tpu.bench.ladder import bench_c2m_scale
        c2m_allocs = int(os.environ.get("NOMAD_TPU_C2M_ALLOCS", 2_000_000))
        if c2m_allocs > 0:
            out.update(bench_c2m_scale(n_nodes=50000,
                                       seed_allocs=c2m_allocs,
                                       n_service=20))
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        out["c2m_error"] = f"{type(e).__name__}: {e}"

    # per-stage attribution over the e2e phases, plus the resident-
    # table maintenance counters (full builds vs delta refreshes vs
    # device scatters) — the steady-state story in one place
    try:
        out["stage_breakdown"] = stages.snapshot()
        # eval flight recorder (ISSUE 9): the per-stage PERCENTILE
        # breakdown (sums can't show bimodality), whether tracing was
        # armed for this round, and the tail-exemplar evidence — a TPU
        # run comes back with the anatomy of its worst evals, and the
        # completeness bit proves the span tree covered enqueue->ack
        # with gateway + commit attrs populated
        from nomad_tpu.trace import tracer as flight
        out["trace"] = "on" if flight.enabled() else "off"
        out["stage_percentiles"] = flight.stage_percentiles()
        exemplars = flight.exemplars()
        out["trace_exemplars"] = len(exemplars)
        need = {"queue_wait", "sched_host", "plan_verify",
                "plan_commit", "broker_ack"}

        def _complete(t):
            names = {sp["name"] for sp in t["spans"]}
            gw = any(sp["name"] == "gateway_wait"
                     and "batch" in sp.get("attrs", {})
                     for sp in t["spans"])
            cm = any(sp["name"] == "plan_commit"
                     and "group" in sp.get("attrs", {})
                     for sp in t["spans"])
            return need <= names and gw and cm

        out["trace_exemplar_complete"] = any(
            _complete(t) for t in exemplars)
        # which exemplars survive worst-K retention is load-dependent
        # (a drift auto-pin mid-bench can park early traces), so the
        # CI-stable completeness claim scans the whole recorder: a
        # complete capture exists SOMEWHERE in exemplars ∪ ring
        out["trace_capture_complete"] = (
            out["trace_exemplar_complete"]
            or any(_complete(t) for t in flight.recent(512)))
        if exemplars:
            out["trace_exemplar_max_ms"] = round(
                max(t["total_ms"] for t in exemplars), 1)
        from nomad_tpu.ops.select import cost_model
        from nomad_tpu.ops.tables import BUILD_STATS
        out["table_build_stats"] = dict(BUILD_STATS)
        out["dispatch_cost_model"] = cost_model.snapshot()
        # device economics (ISSUE 11): pad waste and per-arm dispatch
        # seconds / fresh-compile counts over the whole run — the
        # first-class instruments the real-TPU validation campaign
        # reads (a pad_waste_ratio near 1.0 at small scale is the
        # power-of-two bucketing's floor cost; the number that matters
        # is the C2M-scale one)
        from nomad_tpu.ops.select import device_stats_snapshot
        dev = device_stats_snapshot()
        out["pad_waste_ratio"] = dev["pad_waste_ratio"]
        out["device_dispatch_s"] = dev["dispatch_s"]
        out["device_dispatches"] = dev["dispatches"]
        out["device_compiles"] = dev["compiles"]
        from nomad_tpu.analysis.sanitizer import traces
        out["lint_recompiles"] = traces.per_kernel()
        # group-commit applier + cross-eval engine reuse (ISSUE 4):
        # group sizing and the host-phase reuse hit rate, so the next
        # TPU run can confirm the commit half of the e2e gap closed
        from nomad_tpu.server.plan_applier import GROUP_STATS
        out["plan_group_stats"] = dict(GROUP_STATS)
        out["plan_group_mean_size"] = round(
            GROUP_STATS["plans"] / max(GROUP_STATS["groups"], 1), 2)
        out["plan_group_conflict_retries"] = \
            GROUP_STATS["conflict_retries"]
        # write-side ingest coalescing over the whole run (ISSUE 19):
        # the cross-server aggregate behind the bench_ingest cell
        from nomad_tpu.server.ingest import INGEST_STATS
        out["ingest_stats"] = dict(INGEST_STATS)
        out["ingest_mean_batch"] = round(
            INGEST_STATS["writes"] / max(INGEST_STATS["batches"], 1), 2)
        from nomad_tpu.scheduler.stack import engine_cache_stats
        ec = engine_cache_stats()
        out["engine_reuse"] = ec
        out["engine_reuse_hit_rate"] = round(
            ec["mask_hits"] / max(ec["mask_hits"] + ec["mask_misses"],
                                  1), 4)
        # columnar reconcile engine (ISSUE 6): the tasks_updated memo
        # over the whole run — the deployment-wave scenario reports its
        # own deploy_wave_* keys for the on-vs-off comparison
        from nomad_tpu.scheduler.stack import (tasks_updated_hit_rate,
                                               tasks_updated_stats)
        out["tasks_updated"] = tasks_updated_stats()
        out["tasks_updated_hit_rate"] = round(tasks_updated_hit_rate(),
                                              4)
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        out["stage_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))
    # a phase that raised is a failed run, not a result with a caveat
    return 1 if any(k.endswith("error") for k in out) else 0


if __name__ == "__main__":
    sys.exit(main())
