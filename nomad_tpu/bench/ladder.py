"""End-to-end scheduler benchmarks over the BASELINE.json ladder.

Unlike the raw-kernel benchmark (bench.py run_kernel_bench), every
number here drives the REAL control plane path: state store snapshot →
GenericScheduler.process → reconciler → placement kernel → plan →
plan application back into the store — the same work the reference's
`nomad.worker.invoke_scheduler_service` metric times
(/root/reference/nomad/worker.go:199).

Ladder configs (BASELINE.md):
  #2  batch job count=10k over 1k nodes        -> placements/sec e2e
  #3  service job w/ spread+affinity, 10k nodes -> p99 Process() latency
  #4  mixed-priority preemption, 1k nodes       -> preemption evals/sec
      (run twice in-process: batched columnar victim selection vs the
      NOMAD_TPU_COLUMNAR_PREEMPT=0 reference path — ISSUE 10)
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np


def _seed_nodes(h, n: int, dcs: int = 4):
    from ..mock import fixtures as mock
    nodes = []
    for i in range(n):
        node = mock.node()
        node.name = f"node-{i}"
        node.datacenter = f"dc{(i % dcs) + 1}"
        node.meta["rack"] = f"r{i % 16}"
        node.compute_class()
        nodes.append(node)
        h.store.upsert_node(h.next_index(), node)
    return nodes


def _eval_for(job):
    from ..models import (Evaluation, EVAL_STATUS_PENDING,
                          TRIGGER_JOB_REGISTER)
    from ..utils.ids import generate_uuid
    return Evaluation(
        id=generate_uuid(), namespace=job.namespace, priority=job.priority,
        triggered_by=TRIGGER_JOB_REGISTER, job_id=job.id,
        status=EVAL_STATUS_PENDING, type=job.type)


def bench_batch_e2e(n_nodes: int = 1000, count: int = 10000,
                    warm: bool = True) -> Dict:
    """Ladder #2: one batch job, count instances, through the full
    scheduler. Returns {rate, process_s, placed}."""
    from ..mock import fixtures as mock
    from ..scheduler.harness import Harness

    def once() -> Dict:
        h = Harness()
        _seed_nodes(h, n_nodes, dcs=1)
        job = mock.batch_job()
        job.datacenters = ["dc1"]
        job.task_groups[0].count = count
        h.store.upsert_job(h.next_index(), job)
        t0 = time.perf_counter()
        h.process("batch", _eval_for(job))
        elapsed = time.perf_counter() - t0
        placed = sum(len(a) for a in h.plans[0].node_allocation.values()) \
            if h.plans else 0
        return {"rate": placed / elapsed, "process_s": elapsed,
                "placed": placed}

    if warm:
        once()  # compile + caches
    return once()


def bench_service_p99(n_nodes: int = 10000, n_evals: int = 50,
                      count: int = 10) -> Dict:
    """Ladder #3: service jobs with spread{} + affinity{} over a 10k-node
    table; p99 of full Process() latency across n_evals evals (the
    BASELINE target is p99 <= 100 ms)."""
    from ..mock import fixtures as mock
    from ..models import Affinity, Spread, SpreadTarget
    from ..scheduler.harness import Harness

    h = Harness()
    _seed_nodes(h, n_nodes)

    def make_job(i: int):
        job = mock.job()
        job.id = f"svc-{i}"
        job.datacenters = [f"dc{d}" for d in (1, 2, 3, 4)]
        tg = job.task_groups[0]
        tg.count = count
        # drop the dynamic-port ask so the bench isolates scheduling,
        # not port bookkeeping; ladder #3 is about spread/affinity
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        tg.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                             spread_target=[SpreadTarget("dc1", 40),
                                            SpreadTarget("dc2", 30)]),
                      Spread(attribute="${meta.rack}", weight=30)]
        tg.affinities = [Affinity(ltarget="${meta.rack}", rtarget="r3",
                                  operand="=", weight=50)]
        return job

    # warm compile for this table shape
    wjob = make_job(10**6)
    h.store.upsert_job(h.next_index(), wjob)
    h.process("service", _eval_for(wjob))

    # the production worker's GC regime (utils/gcsafe.py; on in the
    # CLI agent): collector pauses land between evals, not inside the
    # timed Process() calls
    from ..utils import gcsafe
    times: List[float] = []
    placed = 0
    t_all = time.perf_counter()
    with gcsafe.safepoints():
        for i in range(n_evals):
            job = make_job(i)
            h.store.upsert_job(h.next_index(), job)
            t0 = time.perf_counter()
            h.process("service", _eval_for(job))
            times.append(time.perf_counter() - t0)
            gcsafe.safepoint()
    wall = time.perf_counter() - t_all
    for plan in h.plans[1:]:  # skip warm-up plan
        placed += sum(len(a) for a in plan.node_allocation.values())
    arr = np.array(times)
    return {
        "p50_ms": float(np.percentile(arr, 50) * 1e3),
        "p99_ms": float(np.percentile(arr, 99) * 1e3),
        "rate": placed / wall,
        "placed": placed,
    }


def bench_broker_service(n_nodes: int = 10000, n_jobs: int = 64,
                         count: int = 10, batch: int = 8,
                         schedulers: int = 2) -> Dict:
    """Service throughput through the PRODUCTION control plane: a real
    Server — eval broker -> workers -> micro-batch gateway/select_many
    -> plan queue -> pipelined applier -> store. Jobs are registered
    while workers are paused so the broker's queue depth exists (the
    C1M shape: a deployment wave, not a drip), then the wall clock runs
    until every job is fully placed.

    Three runs, all against a dispatch cost model SEEDED by the
    startup calibration probe (ISSUE 7 — the 1-in-16 organic probe
    never fires inside a scenario this short, which is exactly how
    BENCH_r05 shipped service_broker_batches=0):
      1. micro-batching ON (the headline service_broker_* keys +
         service_microbatch_* occupancy/window/latency keys)
      2. the SAME run with NOMAD_TPU_MICROBATCH=0 (the legacy
         rendezvous path; service_microbatch_*_off keys)
      3. eval_batch_size=1, micro-batching off (the sequential
         baseline behind service_batching_speedup)
    so both the micro-batch win and the legacy batching win are
    measured, not asserted."""
    import os

    from ..mock import fixtures as mock
    from ..models import Affinity
    from ..server import Server, ServerConfig

    def run(batch_size: int, micro: bool) -> Dict:
        prev = os.environ.get("NOMAD_TPU_MICROBATCH")
        os.environ["NOMAD_TPU_MICROBATCH"] = "1" if micro else "0"
        try:
            s = Server(ServerConfig(num_schedulers=schedulers,
                                    eval_batch_size=batch_size,
                                    heartbeat_ttl_s=3600.0))
        finally:
            if prev is None:
                os.environ.pop("NOMAD_TPU_MICROBATCH", None)
            else:
                os.environ["NOMAD_TPU_MICROBATCH"] = prev
        s.start()
        try:
            for w in s.workers:
                w.set_pause(True)
            idx = s._raft_index
            for i in range(n_nodes):
                node = mock.node()
                node.name = f"node-{i}"
                node.datacenter = f"dc{(i % 4) + 1}"
                node.meta["rack"] = f"r{i % 16}"
                node.compute_class()
                idx += 1
                s.store.upsert_node(idx, node)
            s._raft_index = idx

            def make_job(i):
                job = mock.job()
                job.id = f"bsvc-{i}"
                job.datacenters = [f"dc{d}" for d in (1, 2, 3, 4)]
                tg = job.task_groups[0]
                tg.count = count
                for t in tg.tasks:
                    t.resources.networks = []
                tg.networks = []
                tg.affinities = [Affinity(ltarget="${meta.rack}",
                                          rtarget="r3", operand="=",
                                          weight=50)]
                return job

            # warm compile at this table shape for every batch width the
            # measured run can hit: the vmapped K-way kernel compiles per
            # power-of-2 lane bucket, and paying a 20-40s XLA compile
            # inside the timed window would measure the compiler
            widths = {batch_size}
            w_ = batch_size
            while w_ > 1:
                w_ //= 2
                widths.add(max(w_, 1))
            warm_done = 0
            for wave in sorted(widths, reverse=True):
                warm = [make_job(10**6 + warm_done + k)
                        for k in range(wave)]
                warm_done += wave
                for j in warm:
                    s.register_job(j)
                for w in s.workers:
                    w.set_pause(False)
                deadline = time.perf_counter() + 180
                while time.perf_counter() < deadline:
                    if all(len(s.store.allocs_by_job(
                            "default", j.id)) == count for j in warm):
                        break
                    time.sleep(0.01)
                for w in s.workers:
                    w.set_pause(True)

            jobs = [make_job(i) for i in range(n_jobs)]
            for j in jobs:
                s.register_job(j)
            t0 = time.perf_counter()
            for w in s.workers:
                w.set_pause(False)
            deadline = time.perf_counter() + 300
            while time.perf_counter() < deadline:
                if all(len(s.store.allocs_by_job("default", j.id)) == count
                       for j in jobs):
                    break
                time.sleep(0.005)
            wall = time.perf_counter() - t0
            placed = sum(len(s.store.allocs_by_job("default", j.id))
                         for j in jobs)
            ga = s.plan_applier.stats
            gw = s.gateway
            out = {"rate": placed / wall, "placed": placed,
                   "wall_s": wall,
                   # legacy rendezvous batches + gateway multi-lane
                   # dispatches: either one is "evals shared a device
                   # dispatch"
                   "batches": sum(w.stats["batches"] for w in s.workers)
                   + (gw.stats["batches"] if gw is not None else 0),
                   "occupancy": (gw.occupancy_mean()
                                 if gw is not None else 1.0),
                   "window_us": (gw.window_us() if gw is not None
                                 else 0.0),
                   "plan_groups": ga["groups"],
                   "plan_group_plans": ga["plans"],
                   "plan_group_conflicts": ga["conflict_retries"]}
            # worker-observed eval latency (queue wait INCLUDED — the
            # ISSUE 7 attribution fix), read from the governor's
            # reservoir
            if s.governor is not None:
                out["p50_ms"] = s.governor.latency_percentile_ms(50)
                out["p99_ms"] = s.governor.latency_percentile_ms(99)
            return out
        finally:
            s.shutdown()

    # deterministic width warm: rendezvous widths depend on queue
    # timing, so job-based warm can miss a lane bucket and leak its
    # XLA compile into the timed window — compile every power-of-2
    # bucket at the measured (n, count) shape up front
    import numpy as np
    from ..ops.select import SelectKernel, SelectRequest
    wcap = np.tile(np.array([[4000.0, 8192.0, 102400.0, 1000.0]],
                            np.float32), (n_nodes, 1))

    def _warm_req():
        return SelectRequest(
            ask=np.array([500.0, 256.0, 150.0, 0.0], np.float32),
            count=count, feasible=np.ones(n_nodes, bool),
            capacity=wcap, used=np.zeros_like(wcap),
            desired_count=float(count),
            tg_collisions=np.zeros(n_nodes, np.int32),
            job_count=np.zeros(n_nodes, np.int32))

    wk = SelectKernel()
    width = 2
    while width <= max(2, batch):
        wk.select_many([_warm_req() for _ in range(width)])
        width *= 2

    # startup calibration probe (ISSUE 7): seed the cost model with
    # measured solo + batched per-lane costs at THIS table shape so
    # batched lanes are cost-favored (or correctly demoted) from the
    # first dispatch — the 1-in-16 organic probe never fires inside a
    # scenario this short (BENCH_r05: service_broker_batches=0)
    from ..ops.select import calibrate_cost_model
    calibrate_cost_model(n_nodes, count=count, lanes=min(batch, 8),
                         kernel=wk)

    batched = run(batch, micro=True)
    legacy = run(batch, micro=False)
    solo = run(1, micro=False)
    # CPU-CI regression fence (ISSUE 7 satellite): with the cost model
    # seeded, the burst scenario MUST engage batching — evals sharing
    # device dispatches is the entire point of the gateway
    assert batched["batches"] > 0, (
        f"broker scenario never batched: {batched}")
    # flight-recorder engagement for the service workload (ISSUE 9):
    # this burst is where tail exemplars are born on CPU CI — record
    # how many the recorder holds after the three runs so the
    # artifact shows the soak story will have its evidence
    from ..trace import tracer as _flight
    return {
        "service_trace_exemplars": _flight.exemplar_count(),
        "service_broker_placements_per_sec": round(batched["rate"], 1),
        "service_broker_wall_s": round(batched["wall_s"], 3),
        "service_broker_batches": batched["batches"],
        "service_broker_seq_placements_per_sec": round(solo["rate"], 1),
        "service_batching_speedup": round(
            batched["rate"] / max(solo["rate"], 1e-9), 2),
        # micro-batch gateway engagement + win (ISSUE 7): occupancy,
        # live window, and the on/off rate + latency comparison the
        # TPU re-run verifies
        "service_microbatch_occupancy_mean": round(
            batched["occupancy"], 2),
        "service_microbatch_window_us": round(batched["window_us"], 1),
        "service_microbatch_placements_per_sec": round(
            batched["rate"], 1),
        "service_microbatch_placements_per_sec_off": round(
            legacy["rate"], 1),
        "service_microbatch_speedup": round(
            batched["rate"] / max(legacy["rate"], 1e-9), 2),
        "service_microbatch_p50_ms": round(
            batched.get("p50_ms", 0.0), 1),
        "service_microbatch_p99_ms": round(
            batched.get("p99_ms", 0.0), 1),
        "service_microbatch_p50_ms_off": round(
            legacy.get("p50_ms", 0.0), 1),
        "service_microbatch_p99_ms_off": round(
            legacy.get("p99_ms", 0.0), 1),
        # group-commit visibility for THIS burst scenario (the queue
        # depth a deployment wave builds is exactly the grouping
        # opportunity): mean plans per commit over the on/off/seq runs
        "service_broker_plan_group_mean_size": round(
            (batched["plan_group_plans"] + legacy["plan_group_plans"]
             + solo["plan_group_plans"])
            / max(batched["plan_groups"] + legacy["plan_groups"]
                  + solo["plan_groups"], 1), 2),
        "service_broker_plan_group_conflicts":
            batched["plan_group_conflicts"]
            + legacy["plan_group_conflicts"]
            + solo["plan_group_conflicts"],
    }


def bench_preemption(n_nodes: int = 1000, n_evals: int = 10,
                     count: int = 50) -> Dict:
    """Ladder #4: nodes saturated by low-priority batch allocs; a
    high-priority service job must preempt to place. Runs the scenario
    twice in-process — batched columnar victim selection vs the
    NOMAD_TPU_COLUMNAR_PREEMPT=0 per-node reference path (ISSUE 10) —
    and reports the victim-selection speedup from the accumulated
    preempt-phase seconds (the e2e rate also rides along for both, but
    at CI scale the eval's kernel/plan/commit overhead would mask the
    selector win the acceptance floor is about)."""
    import os

    # both arms force their switch explicitly (the bench_reconcile
    # idiom) — an ambient kill switch in the environment must not
    # silently turn the "on" arm into a second reference run
    prev = os.environ.get("NOMAD_TPU_COLUMNAR_PREEMPT")
    try:
        os.environ["NOMAD_TPU_COLUMNAR_PREEMPT"] = "1"
        # a throwaway run at the REAL shape absorbs process-global
        # warmup (imports, allocator growth, fresh XLA traces for this
        # node/count bucket) that would otherwise land entirely on
        # whichever arm runs first and skew rate and speedup alike
        _preemption_run(n_nodes, 1, count)
        on = _preemption_run(n_nodes, n_evals, count)
        os.environ["NOMAD_TPU_COLUMNAR_PREEMPT"] = "0"
        off = _preemption_run(n_nodes, n_evals, count)
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_COLUMNAR_PREEMPT", None)
        else:
            os.environ["NOMAD_TPU_COLUMNAR_PREEMPT"] = prev
    out = dict(on)
    out["rate_off"] = off["rate"]
    out["speedup"] = (off["select_s"] / on["select_s"]
                      if on["select_s"] > 0 else 0.0)
    return out


def _preemption_run(n_nodes: int, n_evals: int, count: int) -> Dict:
    from ..mock import fixtures as mock
    from ..scheduler import preemption as pmod
    from ..scheduler.harness import Harness

    h = Harness()
    h.store.set_scheduler_config(
        h.next_index(),
        _preemption_config())
    _seed_nodes(h, n_nodes, dcs=1)
    # fill: one low-prio batch job consuming most of each node
    filler = mock.batch_job()
    filler.datacenters = ["dc1"]
    filler.priority = 20
    filler.task_groups[0].count = n_nodes
    filler.task_groups[0].tasks[0].resources.cpu = 3300
    filler.task_groups[0].tasks[0].resources.memory_mb = 6000
    h.store.upsert_job(h.next_index(), filler)
    h.process("batch", _eval_for(filler))

    def make_hi(i: int):
        hi = mock.job()
        hi.id = f"hi-{i}"
        hi.priority = 80
        hi.datacenters = ["dc1"]
        tg = hi.task_groups[0]
        tg.count = count
        for t in tg.tasks:
            t.resources.networks = []
            t.resources.cpu = 2000
            t.resources.memory_mb = 4000
        tg.networks = []
        return hi

    # warm the kernel at this exact (table, count-bucket) shape so the
    # timed evals measure scheduling, not XLA compilation
    warm = make_hi(10**6)
    h.store.upsert_job(h.next_index(), warm)
    h.process("service", _eval_for(warm))
    n_warm_plans = len(h.plans)
    stats0 = pmod.preempt_stats()       # baseline AFTER the warm eval

    # same GC regime as the agent's workers (utils/gcsafe.py)
    from ..utils import gcsafe
    times: List[float] = []
    placed = 0
    t_all = time.perf_counter()
    with gcsafe.safepoints():
        for i in range(n_evals):
            hi = make_hi(i)
            h.store.upsert_job(h.next_index(), hi)
            t0 = time.perf_counter()
            h.process("service", _eval_for(hi))
            times.append(time.perf_counter() - t0)
            gcsafe.safepoint()
    wall = time.perf_counter() - t_all
    stats1 = pmod.preempt_stats()
    preempted = 0
    for plan in h.plans[n_warm_plans:]:
        placed += sum(len(a) for a in plan.node_allocation.values())
        preempted += sum(len(a) for a in plan.node_preemptions.values())
    hits = stats1["cache_hits"] - stats0["cache_hits"]
    misses = stats1["cache_misses"] - stats0["cache_misses"]
    arr = np.array(times)
    return {
        "rate": placed / wall,
        "placed": placed,
        "preempted": preempted,
        "p50_ms": float(np.percentile(arr, 50) * 1e3),
        "p99_ms": float(np.percentile(arr, 99) * 1e3),
        "select_s": stats1["select_s"] - stats0["select_s"],
        "nodes_scanned": int(stats1["nodes_scanned"]
                             - stats0["nodes_scanned"]),
        "cache_hit_rate": hits / max(hits + misses, 1),
    }


def _preemption_config():
    from ..models import PreemptionConfig, SchedulerConfiguration
    return SchedulerConfiguration(
        preemption_config=PreemptionConfig(
            system_scheduler_enabled=True,
            batch_scheduler_enabled=True,
            service_scheduler_enabled=True))


def bench_feasibility(n_nodes: int = 5000, n_rounds: int = 20) -> Dict:
    """Ladder cell: constraint-heavy service jobs (=, version, regexp,
    set_contains_any, is_set, and an attr-vs-attr pair) over a large
    node fleet, compiled feasibility engine vs the
    NOMAD_TPU_COLUMNAR_FEAS=0 per-node scalar checks in-process
    (ISSUE 17). Each timed round updates ONE node (journaling a single
    attr-index row) and registers a fresh job with the same constraint
    shape, so the on-arm's steady state is the mask-patch path: the
    speedup is the accumulated feasibility-stage seconds ratio, and
    the warm window must show ZERO full attribute-column rebuilds
    (feas_column_rebuilds) with a mask-cache hit rate near 1."""
    import os

    # both arms force their switch explicitly (the bench_preemption
    # idiom) — an ambient kill switch must not silently turn the "on"
    # arm into a second reference run
    prev = os.environ.get("NOMAD_TPU_COLUMNAR_FEAS")
    try:
        os.environ["NOMAD_TPU_COLUMNAR_FEAS"] = "1"
        on = _feasibility_run(n_nodes, n_rounds)
        os.environ["NOMAD_TPU_COLUMNAR_FEAS"] = "0"
        off = _feasibility_run(n_nodes, n_rounds)
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_COLUMNAR_FEAS", None)
        else:
            os.environ["NOMAD_TPU_COLUMNAR_FEAS"] = prev
    return {
        "feas_mask_build_ms": round(on["feas_ms"], 3),
        "feas_mask_build_ms_off": round(off["feas_ms"], 3),
        "feas_speedup": round(off["feas_s"] / on["feas_s"]
                              if on["feas_s"] > 0 else 0.0, 2),
        "feas_intern_values": on["intern_values"],
        "feas_mask_cache_hit_rate": round(on["hit_rate"], 4),
        "feas_column_rebuilds": on["column_rebuilds"],
        "feas_rows_patched": on["rows_patched"],
    }


def _feasibility_run(n_nodes: int, n_rounds: int) -> Dict:
    import copy

    from ..mock import fixtures as mock
    from ..models import Constraint
    from ..scheduler import feasible_compiler as fc
    from ..scheduler.harness import Harness
    from ..utils import gcsafe, stages

    h = Harness()
    nodes = []
    for i in range(n_nodes):
        node = mock.node()
        node.name = f"node-{i}"
        node.datacenter = f"dc{(i % 4) + 1}"
        node.meta["rack"] = f"r{i % 16}"
        node.meta["tier"] = ("gold", "silver", "bronze")[i % 3]
        node.attributes["cpu.arch"] = "amd64" if i % 8 else "arm64"
        node.attributes["kernel.version"] = f"5.{10 + (i % 4)}.0"
        node.attributes["driver.docker.version"] = f"24.0.{i % 5}"
        node.compute_class()
        nodes.append(node)
        h.store.upsert_node(h.next_index(), node)

    def make_job(i: int):
        job = mock.job()
        job.id = f"feas-{i}"
        job.datacenters = ["dc1", "dc2", "dc3", "dc4"]
        tg = job.task_groups[0]
        tg.count = 2
        for t in tg.tasks:
            t.resources.networks = []
            t.resources.cpu = 20
            t.resources.memory_mb = 32
        tg.networks = []
        tg.constraints.extend([
            Constraint(ltarget="${attr.cpu.arch}",
                       rtarget="amd64", operand="="),
            Constraint(ltarget="${attr.kernel.version}",
                       rtarget=">= 5.10.0", operand="version"),
            Constraint(ltarget="${meta.rack}",
                       rtarget="r([0-9]|1[0-3])$", operand="regexp"),
            Constraint(ltarget="${meta.tier}",
                       rtarget="gold,silver",
                       operand="set_contains_any"),
            Constraint(ltarget="${attr.driver.docker.version}",
                       rtarget="", operand="is_set"),
            Constraint(ltarget="${node.class}",
                       rtarget="${node.class}", operand="="),
        ])
        return job

    # warm throwaway evals at the REAL shape absorb process-global
    # warmup AND the one-time engine costs (column interning, program
    # compile, first full mask build, XLA traces for this table/count
    # bucket); the node update between them walks the mask-PATCH path
    # once too (incl. the device scatter's compile) — the timed warm
    # window then measures the steady state
    for i in (10**6, 10**6 + 1):
        w = make_job(i)
        h.store.upsert_job(h.next_index(), w)
        h.process("service", _eval_for(w))
        node = copy.deepcopy(h.store.node_by_id(nodes[0].id))
        node.meta["canary"] = f"w{i}"
        h.store.upsert_node(h.next_index(), node)

    fc.reset_stats()
    g0 = h.store.attr_index.gauge_stats()
    # delta-read the global accumulators (bench_preemption idiom): in a
    # bench.py run stages are already collecting for the whole e2e
    # phase, and a reset here would wipe the plan_verify/commit counts
    # the artifact's stage_breakdown reports
    was_collecting = getattr(stages, "_collecting", False)
    if not was_collecting:
        stages.enable(reset=False)
    pre = stages.snapshot().get("feasibility",
                                {"seconds": 0.0, "calls": 0})
    with gcsafe.safepoints():
        for r in range(n_rounds):
            # one node update per round: a benign meta write journals
            # exactly one index row without moving any verdict
            node = copy.deepcopy(
                h.store.node_by_id(nodes[r % n_nodes].id))
            node.meta["canary"] = f"c{r}"
            h.store.upsert_node(h.next_index(), node)
            job = make_job(r)
            h.store.upsert_job(h.next_index(), job)
            h.process("service", _eval_for(job))
            gcsafe.safepoint()
    snap = stages.snapshot()
    if not was_collecting:
        stages.disable()
    post = snap.get("feasibility", {"seconds": 0.0, "calls": 0})
    feas = {"seconds": post["seconds"] - pre["seconds"],
            "calls": post["calls"] - pre["calls"]}
    st = fc.stats()
    g1 = h.store.attr_index.gauge_stats()
    return {
        "feas_s": feas["seconds"],
        "feas_ms": feas["seconds"] * 1e3 / max(feas["calls"], 1),
        "feas_calls": feas["calls"],
        "intern_values": g1["intern_values"],
        "hit_rate": fc.hit_rate(),
        "column_rebuilds": (g1.get("idx_column_builds", 0)
                            - g0.get("idx_column_builds", 0)),
        "rows_patched": st["rows_patched"],
    }


def bench_feas_residue(n_nodes: int = 5000, n_rounds: int = 20) -> Dict:
    """Ladder cell (ISSUE 20): spread/distinct/CSI-heavy service jobs,
    residue-compiled feasibility on vs NOMAD_TPU_FEAS_RESIDUE=0
    in-process (both arms keep the compiled engine on — this cell
    measures the RESIDUE layer, not ISSUE 17's mask compile). Each
    timed round updates ONE node (full table rebuild, which drops the
    per-table attr_codes cache) and registers a fresh CSI job with two
    spreads and a distinct_property constraint, so the off-arm pays
    the O(N) Python dictionary re-encode per spread attribute per eval
    while the on-arm derives codes from the write-through interned
    columns; spread_score_speedup is the accumulated input-build
    seconds ratio. The CSI topology subset mutates the combined mask
    every eval: the on-arm must keep the device token alive via sparse
    residue scatters (survival rate ~1, warm mask uploads ~0)."""
    import os

    prev_r = os.environ.get("NOMAD_TPU_FEAS_RESIDUE")
    prev_c = os.environ.get("NOMAD_TPU_COLUMNAR_FEAS")
    try:
        os.environ["NOMAD_TPU_COLUMNAR_FEAS"] = "1"
        os.environ["NOMAD_TPU_FEAS_RESIDUE"] = "1"
        on = _feas_residue_run(n_nodes, n_rounds)
        os.environ["NOMAD_TPU_FEAS_RESIDUE"] = "0"
        off = _feas_residue_run(n_nodes, n_rounds)
    finally:
        for var, prev in (("NOMAD_TPU_FEAS_RESIDUE", prev_r),
                          ("NOMAD_TPU_COLUMNAR_FEAS", prev_c)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev
    decided = on["token_survivals"] + on["token_invalidations"]
    return {
        "feas_resident_token_survival_rate": round(
            on["token_survivals"] / max(decided, 1), 4),
        "feas_residue_rows": on["residue_rows"],
        "feas_residue_scatters": on["residue_scatters"],
        # warm-window full mask re-uploads on the on-arm: the token
        # survives CSI residue, so this must stay ~0
        "feas_warm_mask_uploads": on["warm_uploads"],
        "spread_build_ms": round(on["build_ms"], 3),
        "spread_build_ms_off": round(off["build_ms"], 3),
        "spread_score_speedup": round(
            off["build_s"] / on["build_s"]
            if on["build_s"] > 0 else 0.0, 2),
        "spread_score_evals": on["spread_score_evals"],
    }


def _feas_residue_run(n_nodes: int, n_rounds: int) -> Dict:
    import copy

    from ..mock import fixtures as mock
    from ..models import Constraint, Spread, SpreadTarget
    from ..models.csi import ACCESS_MULTI_NODE_MULTI_WRITER, CSIVolume
    from ..models.job import VolumeRequest
    from ..ops import spread as spread_ops
    from ..scheduler import feasible_compiler as fc
    from ..scheduler.harness import Harness
    from ..utils import gcsafe

    h = Harness()
    nodes = []
    for i in range(n_nodes):
        node = mock.node()
        node.name = f"node-{i}"
        node.datacenter = f"dc{(i % 4) + 1}"
        node.meta["rack"] = f"r{i % 16}"
        node.meta["tier"] = f"t{i % 8}"
        node.attributes["csi.plugin.p1"] = "1"
        node.compute_class()
        nodes.append(node)
        h.store.upsert_node(h.next_index(), node)

    # multi-writer volume whose topology admits 3 of 4 nodes: every
    # eval mutates the combined mask (the residue diff the on-arm
    # ships as a sparse scatter) without ever exhausting claims
    vol = CSIVolume(id="data-vol", plugin_id="p1",
                    access_mode=ACCESS_MULTI_NODE_MULTI_WRITER,
                    topology_node_ids=[n.id for i, n in enumerate(nodes)
                                       if i % 4 != 3])
    h.store.upsert_csi_volumes(h.next_index(), [vol])

    def make_job(i: int):
        job = mock.job()
        job.id = f"residue-{i}"
        job.datacenters = ["dc1", "dc2", "dc3", "dc4"]
        job.spreads = [Spread(
            attribute="${node.datacenter}", weight=70,
            spread_target=[SpreadTarget(value="dc1", percent=40),
                           SpreadTarget(value="dc2", percent=30)])]
        tg = job.task_groups[0]
        tg.count = 2
        for t in tg.tasks:
            t.resources.networks = []
            t.resources.cpu = 20
            t.resources.memory_mb = 32
        tg.networks = []
        # host-balancing spread over the full node axis plus a
        # low-cardinality tier: each attribute the off-arm re-encodes
        # O(N) in Python per rebuilt table, the on-arm reads off the
        # interned columns — the spread set mirrors a real placement
        # policy (dc targets, rack balance, host anti-affinity)
        tg.spreads = [Spread(attribute="${meta.rack}", weight=30),
                      Spread(attribute="${node.unique.name}", weight=10),
                      Spread(attribute="${meta.tier}", weight=20)]
        tg.constraints.append(Constraint(
            ltarget="${meta.rack}", rtarget="8",
            operand="distinct_property"))
        tg.volumes = {"vol": VolumeRequest(
            name="vol", type="csi", source="data-vol")}
        return job

    # warm throwaway evals: engine compile, first mask park, device
    # scatter traces, and the feas token the timed rounds dispatch on
    for i in (10**6, 10**6 + 1):
        w = make_job(i)
        h.store.upsert_job(h.next_index(), w)
        h.process("service", _eval_for(w))
        node = copy.deepcopy(h.store.node_by_id(nodes[0].id))
        node.meta["canary"] = f"w{i}"
        h.store.upsert_node(h.next_index(), node)

    fc.reset_stats()
    spread_ops.reset_stats()
    feas_store = h.store.table_cache.device.feas
    up0 = feas_store.stats["uploads"]
    t0 = time.perf_counter()
    with gcsafe.safepoints():
        for r in range(n_rounds):
            # one benign node meta write per round: a full table
            # rebuild that drops the per-table attr_codes cache — the
            # off-arm re-encodes every spread attribute O(N) in Python
            node = copy.deepcopy(
                h.store.node_by_id(nodes[r % n_nodes].id))
            node.meta["canary"] = f"c{r}"
            h.store.upsert_node(h.next_index(), node)
            job = make_job(r)
            h.store.upsert_job(h.next_index(), job)
            h.process("service", _eval_for(job))
            gcsafe.safepoint()
    wall_s = time.perf_counter() - t0
    st = fc.stats()
    sp = spread_ops.stats()
    on_arm = fc.residue_enabled()
    build_s = sp["vector_s"] if on_arm else sp["scalar_s"]
    builds = sp["vector_builds"] if on_arm else sp["scalar_builds"]
    return {
        "token_survivals": st["token_survivals"],
        "token_invalidations": st["token_invalidations"],
        "residue_rows": st["residue_rows"],
        "residue_scatters": feas_store.stats["residue_scatters"],
        "warm_uploads": feas_store.stats["uploads"] - up0,
        "spread_score_evals": sp["spread_score_evals"],
        "build_s": build_s,
        "build_ms": build_s * 1e3 / max(builds, 1),
        "wall_s": wall_s,
    }


def seed_c2m_allocs(h, nodes, seed_allocs: int,
                    sched_allocs: int = 40000) -> Dict:
    """Load the C2M substrate: `sched_allocs` go through the REAL
    scheduler/plan path (proving that machinery at depth), the rest
    through the replay loader (store.bulk_load_allocs — the snapshot-
    restore analog; seeding 2M rows one eval at a time would measure
    nothing new for half an hour). Every seeded alloc carries real
    resources so the resident table's used columns are non-trivial.
    Returns {"seed_s", "sched_s"}."""
    from ..mock import fixtures as mock
    from ..models import Allocation
    from ..models.resources import (AllocatedCpuResources,
                                    AllocatedMemoryResources,
                                    AllocatedResources,
                                    AllocatedSharedResources,
                                    AllocatedTaskResources)

    dcs = [f"dc{d}" for d in (1, 2, 3, 4)]
    t0 = time.perf_counter()
    remaining = min(sched_allocs, seed_allocs)
    chunk = 20000
    while remaining > 0:
        filler_chunk = mock.batch_job()
        filler_chunk.id = f"filler-{remaining}"
        filler_chunk.priority = 20
        filler_chunk.datacenters = dcs
        tg = filler_chunk.task_groups[0]
        tg.count = min(chunk, remaining)
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 64
        tg.tasks[0].resources.networks = []
        tg.networks = []
        h.store.upsert_job(h.next_index(), filler_chunk)
        h.process("batch", _eval_for(filler_chunk))
        remaining -= tg.count
    sched_s = time.perf_counter() - t0

    bulk_n = seed_allocs - min(sched_allocs, seed_allocs)
    if bulk_n > 0:
        seed_job = mock.batch_job()
        seed_job.id = "c2m-seed"
        seed_job.priority = 20
        seed_job.datacenters = dcs
        tg = seed_job.task_groups[0]
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 64
        tg.tasks[0].resources.networks = []
        tg.networks = []
        tg.count = bulk_n
        tgn = tg.name
        task_name = tg.tasks[0].name
        h.store.upsert_job(h.next_index(), seed_job)
        # one shared flyweight resource row: the table builder only
        # reads it, and 2M private copies would cost GBs for nothing
        res = AllocatedResources(
            tasks={task_name: AllocatedTaskResources(
                cpu=AllocatedCpuResources(cpu_shares=50),
                memory=AllocatedMemoryResources(memory_mb=64))},
            shared=AllocatedSharedResources(disk_mb=10))
        n_nodes = len(nodes)
        allocs = []
        eval_id = "c2m-seed-eval"
        for i in range(bulk_n):
            allocs.append(Allocation(
                id=f"c2m-{i:08d}", namespace="default",
                job_id=seed_job.id, task_group=tgn,
                name=f"c2m-seed.{tgn}[{i}]",
                node_id=nodes[i % n_nodes].id, eval_id=eval_id,
                client_status="running", desired_status="run",
                allocated_resources=res))
            if len(allocs) >= 250_000:
                h.store.bulk_load_allocs(h.next_index(), allocs)
                allocs = []
        if allocs:
            h.store.bulk_load_allocs(h.next_index(), allocs)
    return {"seed_s": time.perf_counter() - t0, "sched_s": sched_s}


def bench_c2m_scale(n_nodes: int = 50000, seed_allocs: int = 2_000_000,
                    batch_count: int = 10000, n_service: int = 10,
                    n_stream: int = 5) -> Dict:
    """See _bench_c2m_scale_impl; this wrapper guarantees the process-
    wide GC regime (disable + freeze) is unwound and the server torn
    down even when a step raises — a bench failure must not leave the
    collector off or worker threads running against the 2M-row store."""
    from ..server import Server, ServerConfig
    from ..utils import gcsafe
    srv = Server(ServerConfig(num_schedulers=2, eval_batch_size=1,
                              heartbeat_ttl_s=3600.0,
                              gc_safepoints=True))
    srv.start()
    gcsafe.enter()
    try:
        return _bench_c2m_scale_impl(srv, n_nodes, seed_allocs,
                                     batch_count, n_service, n_stream)
    finally:
        gcsafe.exit_()
        gcsafe.unfreeze_steady_state()
        srv.shutdown()


def _bench_c2m_scale_impl(srv, n_nodes: int, seed_allocs: int,
                          batch_count: int, n_service: int,
                          n_stream: int) -> Dict:
    """Ladder #5 (C2M replay scale): a 50k-node cluster pre-loaded with
    2M running allocs (BASELINE config #5), then (a) a 10k-instance
    batch job e2e, (a') the stock iterator baseline on the same store,
    (b) service-eval p99, and (c) a STREAM of `n_stream` 10k-instance
    batch jobs through the production control plane (eval broker ->
    two workers -> plan queue -> pipelined applier), where one
    worker's device wait overlaps the other's host work — compute
    overlapping apply end-to-end, the plan_apply.go:44-70 shape."""
    from ..mock import fixtures as mock
    from ..scheduler.harness import Harness

    # the store lives inside the wrapper-owned Server; the single-eval
    # measures below drive it through a store-sharing harness while
    # workers are paused, then the stream runs through the workers
    for w in srv.workers:
        w.set_pause(True)

    # the whole C2M ladder runs under the agent's GC-safepoint regime
    # (entered by the wrapper): automatic collection off, young-gen
    # collects + a gen-2 budget at safepoints, and — once the 2M-alloc
    # substrate is loaded — the steady state frozen out of future
    # collections (utils/gcsafe.py). Without this, CPython's automatic
    # collector walks the multi-million-object heap mid-measurement.
    from ..utils import gcsafe

    h = Harness(store=srv.store)
    h._next_index = srv.store.latest_index() + 1000
    nodes = _seed_nodes(h, n_nodes)
    dcs = [f"dc{d}" for d in (1, 2, 3, 4)]

    seed_stats = seed_c2m_allocs(h, nodes, seed_allocs)
    seed_s = seed_stats["seed_s"]
    total_allocs = sum(1 for _ in h.store.allocs())

    # the one-time post-seed resident-table build (a full 2M-row scan)
    # is reported as its own metric; the batch/service numbers below
    # measure steady state against the delta-maintained table
    t0 = time.perf_counter()
    h.store.snapshot().node_table()
    table_build_s = time.perf_counter() - t0
    gcsafe.freeze_steady_state()

    # (a) batch throughput at scale — three timed evals, best rate
    # (one sample rides dispatch-latency variance)
    batch_s = float("inf")
    placed = 0
    for bi in range(3):
        job = mock.batch_job()
        job.id = f"c2m-batch-{bi}"
        job.datacenters = dcs
        tg = job.task_groups[0]
        tg.count = batch_count
        tg.tasks[0].resources.networks = []
        tg.networks = []
        h.store.upsert_job(h.next_index(), job)
        t0 = time.perf_counter()
        h.process("batch", _eval_for(job))
        el = time.perf_counter() - t0
        p = sum(len(a) for a in h.plans[-1].node_allocation.values())
        if el < batch_s:
            batch_s, placed = el, p

    # (a') the stock pull-iterator scheduler on the SAME store, same
    # plan-apply path — the same-host baseline the kernel path is
    # proven against (bench/iterbaseline.py; measured at a smaller
    # count, which favors the baseline: its walk degrades as prefix
    # nodes fill)
    from .iterbaseline import bench_iter_baseline

    def _iter_proto(i):
        j = mock.batch_job()
        j.id = f"c2m-iterbase-{i}"
        j.datacenters = dcs
        tgp = j.task_groups[0]
        tgp.count = 1000
        tgp.tasks[0].resources.networks = []
        tgp.networks = []
        return j

    iter_stats = bench_iter_baseline(h, _iter_proto, count=1000,
                                     n_evals=2)

    # (b) service p99 at scale (spread + affinity live)
    from ..models import Affinity, Spread, SpreadTarget

    def make_svc(i):
        svc = mock.job()
        svc.id = f"c2m-svc-{i}"
        svc.datacenters = dcs
        tg = svc.task_groups[0]
        tg.count = 10
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        tg.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                             spread_target=[SpreadTarget("dc1", 40),
                                            SpreadTarget("dc2", 30)])]
        tg.affinities = [Affinity(ltarget="${meta.rack}", rtarget="r3",
                                  operand="=", weight=50)]
        return svc

    # three warm evals: the first compiles at this table shape, the
    # rest settle the per-table-version caches and the allocator so
    # the timed window measures steady state, not residual warm-up
    # (instrumented runs show eval latency decaying over the first
    # few evals at the 2M scale)
    for w in range(3):
        warm = make_svc(10**6 + w)
        h.store.upsert_job(h.next_index(), warm)
        h.process("service", _eval_for(warm))

    # the SAME GC-safepoint protocol the production worker runs
    # (utils/gcsafe.py via ServerConfig.gc_safepoints, on in the CLI
    # agent): collector pauses happen between evals, so the timed
    # window measures the latency an eval experiences in an agent
    from ..utils import gcsafe
    times: List[float] = []
    with gcsafe.safepoints():
        for i in range(n_service):
            svc = make_svc(i)
            h.store.upsert_job(h.next_index(), svc)
            t0 = time.perf_counter()
            h.process("service", _eval_for(svc))
            times.append(time.perf_counter() - t0)
            gcsafe.safepoint()
    arr = np.array(times)

    # (c) streamed batch throughput through the production workers:
    # two schedulers dequeue from the broker concurrently, so one's
    # device dispatch wait (round trip + kernel) overlaps the
    # other's host-side reconcile/expand/plan work, and the plan queue
    # + applier pipeline the commits (plan_apply.go:44-70 overlap).
    srv._raft_index = h.store.latest_index()
    stream_jobs = []
    for i in range(n_stream):
        sj = mock.batch_job()
        sj.id = f"c2m-stream-{i}"
        sj.datacenters = dcs
        tgj = sj.task_groups[0]
        tgj.count = batch_count
        tgj.tasks[0].resources.networks = []
        tgj.networks = []
        stream_jobs.append(sj)
    tg_names = {sj.id: sj.task_groups[0].name for sj in stream_jobs}

    def _stream_placed() -> int:
        total = 0
        for sj in stream_jobs:
            summ = srv.store.job_summary("default", sj.id)
            if summ is None:
                continue
            total += sum(summ.summary.get(tg_names[sj.id], {}).values())
        return total

    for sj in stream_jobs:
        srv.register_job(sj)
    want = n_stream * batch_count
    t0 = time.perf_counter()
    for w in srv.workers:
        w.set_pause(False)
    deadline = time.perf_counter() + 600
    while time.perf_counter() < deadline:
        if _stream_placed() >= want:
            break
        time.sleep(0.05)
    stream_wall = time.perf_counter() - t0
    stream_placed = _stream_placed()

    # (c') the same stream with multi-eval batching: workers drain two
    # READY evals into BatchGateway lanes whose dispatches coalesce
    # into one vmapped kernel call — half the device round trips per
    # eval pair. One warm wave compiles the B=2 shape outside the
    # timed window.
    for w in srv.workers:
        w.set_pause(True)
        w.batch_size = 2

    def _stream_jobs(tag, count_jobs):
        out = []
        for i in range(count_jobs):
            sj = mock.batch_job()
            sj.id = f"c2m-{tag}-{i}"
            sj.datacenters = dcs
            tgj = sj.task_groups[0]
            tgj.count = batch_count
            tgj.tasks[0].resources.networks = []
            tgj.networks = []
            out.append(sj)
        return out

    def _placed_of(jobs_):
        total = 0
        for sj in jobs_:
            summ = srv.store.job_summary("default", sj.id)
            if summ is not None:
                total += sum(
                    summ.summary.get(sj.task_groups[0].name, {})
                    .values())
        return total

    def _run_stream(jobs_):
        for sj in jobs_:
            srv.register_job(sj)
        want_ = len(jobs_) * batch_count
        t0_ = time.perf_counter()
        for w in srv.workers:
            w.set_pause(False)
        deadline_ = time.perf_counter() + 600
        while time.perf_counter() < deadline_:
            if _placed_of(jobs_) >= want_:
                break
            time.sleep(0.05)
        wall_ = time.perf_counter() - t0_
        for w in srv.workers:
            w.set_pause(True)
        return wall_

    _run_stream(_stream_jobs("stream-warm", 2))      # B=2 compile
    batches_before = sum(w.stats["batches"] for w in srv.workers)
    bjobs = _stream_jobs("bstream", n_stream)
    bwall = _run_stream(bjobs)
    bplaced = _placed_of(bjobs)
    stream_batches = sum(w.stats["batches"]
                         for w in srv.workers) - batches_before

    return {
        "c2m_nodes": n_nodes,
        "c2m_allocs": total_allocs,
        "c2m_seed_rate": round(seed_allocs / max(seed_s, 1e-9), 1),
        "c2m_seed_sched_s": round(seed_stats["sched_s"], 1),
        "c2m_table_build_s": round(table_build_s, 2),
        "c2m_batch_placements_per_sec": round(placed / batch_s, 1),
        "c2m_batch_placed": placed,
        "c2m_iter_baseline_placements_per_sec": round(
            iter_stats["iter_rate"], 1),
        "c2m_vs_iter_baseline": round(
            (placed / batch_s) / max(iter_stats["iter_rate"], 1e-9), 1),
        "c2m_service_p99_ms": round(float(np.percentile(arr, 99) * 1e3), 1),
        "c2m_service_p50_ms": round(float(np.percentile(arr, 50) * 1e3), 1),
        "c2m_stream_placements_per_sec": round(
            stream_placed / max(stream_wall, 1e-9), 1),
        "c2m_stream_placed": stream_placed,
        "c2m_stream_wall_s": round(stream_wall, 2),
        "c2m_stream_batched_placements_per_sec": round(
            bplaced / max(bwall, 1e-9), 1),
        "c2m_stream_batches": stream_batches,
        "c2m_stream_batching_speedup": round(
            (bplaced / max(bwall, 1e-9))
            / max(stream_placed / max(stream_wall, 1e-9), 1e-9), 2),
    }


def bench_deployment_wave(n_nodes: int = 1000, count: int = 10000,
                          versions: int = 3,
                          evals_per_version: int = 8) -> Dict:
    """Deployment-wave reconcile cost (ISSUE 6): a count-N service job
    with a rolling update stanza takes `versions` spec bumps; every
    eval of a wave re-reconciles ALL N allocs but places at most
    max_parallel — the reference path pays O(N) per-alloc Python plus
    one deep `tasks_updated` diff PER ALLOC per eval, the columnar
    engine pays numpy masks plus ONE memoized diff per version pair.
    Runs the same workload with the engine on and off
    (NOMAD_TPU_COLUMNAR_RECONCILE) and reports evals/s for both, the
    memo hit rate, and the `reconcile` stage seconds for the engine-on
    run."""
    import os

    from ..mock import fixtures as mock
    from ..models.job import UpdateStrategy
    from ..scheduler.harness import Harness
    from ..scheduler.stack import TASKS_UPDATED_STATS
    from ..utils import stages

    def run() -> Dict:
        h = Harness()
        _seed_nodes(h, n_nodes, dcs=1)
        job = mock.job()
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = count
        # rolling stanza: wave evals reconcile everything, place little
        tg.update = UpdateStrategy(max_parallel=2, canary=0)
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        h.store.upsert_job(h.next_index(), job)
        h.process("service", _eval_for(job))        # seed placement
        # warm wave OUTSIDE the timer: the first spec bump compiles the
        # max_parallel-sized kernel shape, and whichever run goes first
        # must not donate that compile to the other
        job = job.copy()
        job.task_groups[0].tasks[0].env = {"WAVE": "warm"}
        h.store.upsert_job(h.next_index(), job)
        h.process("service", _eval_for(job))

        tu0 = dict(TASKS_UPDATED_STATS)
        rec0 = (stages.snapshot().get("reconcile", {})
                .get("seconds", 0.0) if stages.enabled else 0.0)
        evals = 0
        t0 = time.perf_counter()
        for v in range(versions):
            job = job.copy()
            job.task_groups[0].tasks[0].env = {"WAVE": str(v)}
            h.store.upsert_job(h.next_index(), job)
            for _ in range(evals_per_version):
                h.process("service", _eval_for(job))
                evals += 1
        elapsed = time.perf_counter() - t0
        tu1 = dict(TASKS_UPDATED_STATS)
        rec1 = (stages.snapshot().get("reconcile", {})
                .get("seconds", 0.0) if stages.enabled else 0.0)
        hits = tu1["hits"] - tu0["hits"]
        misses = tu1["misses"] - tu0["misses"]
        return {"rate": evals / elapsed, "evals": evals,
                "hit_rate": hits / max(hits + misses, 1),
                "reconcile_s": rec1 - rec0}

    prev = os.environ.get("NOMAD_TPU_COLUMNAR_RECONCILE")
    try:
        os.environ["NOMAD_TPU_COLUMNAR_RECONCILE"] = "1"
        on = run()
        os.environ["NOMAD_TPU_COLUMNAR_RECONCILE"] = "0"
        off = run()
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_COLUMNAR_RECONCILE", None)
        else:
            os.environ["NOMAD_TPU_COLUMNAR_RECONCILE"] = prev
    return {
        "deploy_wave_evals_per_sec": round(on["rate"], 2),
        "deploy_wave_evals_per_sec_off": round(off["rate"], 2),
        "deploy_wave_speedup": round(on["rate"] / max(off["rate"], 1e-9),
                                     2),
        "deploy_wave_tasks_updated_hit_rate": round(on["hit_rate"], 4),
        "deploy_wave_reconcile_stage_s": round(on["reconcile_s"], 4),
    }


def bench_cold_start(n_nodes: int = 1000, seed_allocs: int = 30000,
                     n_jobs: int = 8, wal_tail: int = 48) -> Dict:
    """Cold-start recovery (ISSUE 8): seed a C2M-CI-scale store, write
    BOTH snapshot formats of the same state plus a shared WAL tail,
    then time a fresh boot from each — snapshot restore, cold
    resident-table build, batched WAL replay. The columnar pipeline
    (state/columnar.py + the primed NodeTable + eager alloc index)
    must beat the legacy object snapshot ≥ 3× on the summed recovery
    stages (asserted in tests/test_bench_smoke.py), and after the
    columnar boot the recovery invariants hold: the first columnar
    read per job pays ZERO dense index rebuilds and the first
    node_table() read pays ZERO full NodeTable builds."""
    import os
    import shutil
    import tempfile

    from ..mock import fixtures as mock
    from ..models import Allocation
    from ..models.resources import (AllocatedCpuResources,
                                    AllocatedMemoryResources,
                                    AllocatedResources,
                                    AllocatedSharedResources,
                                    AllocatedTaskResources)
    from ..server import Server, ServerConfig
    from ..server.persistence import Persistence

    base = tempfile.mkdtemp(prefix="nomad-tpu-cold-")
    col_dir = os.path.join(base, "columnar")
    leg_dir = os.path.join(base, "legacy")
    try:
        srv = Server(ServerConfig(num_schedulers=0, data_dir=col_dir,
                                  snapshot_background=False,
                                  heartbeat_ttl_s=3600.0))
        idx = srv._raft_index
        nodes = []
        for i in range(n_nodes):
            node = mock.node()
            node.name = f"cold-{i}"
            node.datacenter = f"dc{(i % 4) + 1}"
            node.compute_class()
            idx += 1
            srv.store.upsert_node(idx, node)
            nodes.append(node)
        jobs = []
        per_job = max(seed_allocs // n_jobs, 1)
        for jn in range(n_jobs):
            job = mock.batch_job()
            job.id = f"cold-job-{jn}"
            tg = job.task_groups[0]
            tg.count = per_job
            tg.tasks[0].resources.networks = []
            tg.networks = []
            idx += 1
            srv.store.upsert_job(idx, job)
            jobs.append(job)
            # one shared flyweight resources row per job (the C2M seed
            # shape — the columnar pool collapses it to one entry)
            res = AllocatedResources(
                tasks={tg.tasks[0].name: AllocatedTaskResources(
                    cpu=AllocatedCpuResources(cpu_shares=50),
                    memory=AllocatedMemoryResources(memory_mb=64))},
                shared=AllocatedSharedResources(disk_mb=10))
            allocs = [Allocation(
                id=f"cold-{jn}-{i:07d}", namespace="default",
                job_id=job.id, task_group=tg.name,
                name=f"{job.id}.{tg.name}[{i}]",
                node_id=nodes[(jn * per_job + i) % n_nodes].id,
                eval_id=f"cold-seed-eval-{jn}",
                client_status="running", desired_status="run",
                allocated_resources=res) for i in range(per_job)]
            idx += 1
            srv.store.bulk_load_allocs(idx, allocs)
        srv._raft_index = srv.store.latest_index()
        # legacy (object) snapshot of the SAME state, columnar
        # snapshot via the server's own persistence, one shared WAL
        # tail appended after both
        leg = Persistence(leg_dir, columnar=False, background=False)
        leg.snapshot(srv.store)
        srv.persistence.snapshot(srv.store)
        for k in range(wal_tail):
            srv.raft_apply("eval_update",
                           dict(evals=[_eval_for(jobs[k % n_jobs])]))
        srv.shutdown()
        shutil.copyfile(os.path.join(col_dir, "raft.log"),
                        os.path.join(leg_dir, "raft.log"))

        def boot(data_dir: str):
            s2 = Server(ServerConfig(num_schedulers=0,
                                     data_dir=data_dir,
                                     heartbeat_ttl_s=3600.0))
            st = dict(s2.cold_start_stats)
            st["total_s"] = (st["restore_s"] + st["table_build_s"]
                             + st["wal_replay_s"])
            return s2, st

        s2, cst = boot(col_dir)
        assert cst["snapshot_format"] == 2.0, cst
        # recovery invariants (acceptance): the first columnar read
        # per job finds the eagerly rebuilt index (zero dense
        # rebuilds), the first table read finds the primed resident
        # table (zero full builds)
        snap = s2.store.snapshot()
        for job in jobs:
            snap.job_alloc_columns("default", job.id)
        assert s2.store.alloc_index.stats["rebuilds"] == 0, \
            s2.store.alloc_index.stats
        snap.node_table()
        assert s2.store.table_cache.stats["full_builds"] == 0, \
            s2.store.table_cache.stats
        n_allocs = sum(1 for _ in s2.store.allocs())
        s2.shutdown()

        s3, lst = boot(leg_dir)
        assert lst["snapshot_format"] == 1.0, lst
        assert sum(1 for _ in s3.store.allocs()) == n_allocs
        s3.shutdown()
        return {
            "cold_nodes": n_nodes,
            "cold_allocs": n_allocs,
            "cold_restore_s": round(cst["restore_s"], 4),
            "cold_table_build_s": round(cst["table_build_s"], 4),
            "cold_wal_replay_s": round(cst["wal_replay_s"], 4),
            "cold_start_s": round(cst["total_s"], 4),
            "cold_start_legacy_s": round(lst["total_s"], 4),
            "cold_start_speedup": round(
                lst["total_s"] / max(cst["total_s"], 1e-9), 2),
        }
    finally:
        shutil.rmtree(base, ignore_errors=True)


def bench_cluster_stats(n_clients: int = 4, n_allocs: int = 8) -> Dict:
    """Fleet observability rollup (ISSUE 13): a real server + client
    agents with the stats sampler on, a running job, and the folded
    cluster economics — the artifact records nodes reporting and the
    fleet used-vs-allocated ratios so a TPU soak's bin-packing truth
    is a first-class number next to the device truth (pad_waste)."""
    import time as _time

    from ..client import Client, ClientConfig
    from ..mock import fixtures as mock
    from ..server import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=2, heartbeat_ttl_s=30.0,
                              telemetry_sample_interval_s=3600.0))
    srv.start()
    clients = [Client(srv, ClientConfig(node_name=f"stats-{i}",
                                        heartbeat_interval_s=0.2,
                                        stats_sample_interval_s=0.1))
               for i in range(n_clients)]
    out: Dict = {}
    try:
        for c in clients:
            c.start()
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = n_allocs
        tg.networks = []
        for t in tg.tasks:
            t.resources.networks = []
            t.driver = "mock_driver"
            t.config = {"run_for": "10s"}
        srv.register_job(job)
        deadline = _time.time() + 30.0
        while _time.time() < deadline:
            allocs = srv.store.allocs_by_job(job.namespace, job.id)
            if len(allocs) >= n_allocs and any(
                    a.client_status == "running" for a in allocs):
                break
            _time.sleep(0.05)
        # wait for every client's heartbeat to land a stats payload
        deadline = _time.time() + 10.0
        cs = srv.cluster_stats()
        while _time.time() < deadline and \
                cs["nodes_reporting"] < n_clients:
            _time.sleep(0.1)
            cs = srv.cluster_stats()
        if srv.telemetry is not None:
            # the cluster.* family lands in the retained ring too
            srv.telemetry.sample_once()
        out["cluster_nodes"] = int(cs["nodes_total"])
        out["cluster_nodes_reporting"] = int(cs["nodes_reporting"])
        out["cluster_stale_heartbeats"] = int(cs["stale_heartbeats"])
        out["fleet_cpu_used_ratio"] = cs["fleet_cpu_used_ratio"]
        out["fleet_mem_used_ratio"] = cs["fleet_mem_used_ratio"]
        out["fleet_cpu_allocated_ratio"] = \
            cs["fleet_cpu_allocated_ratio"]
        out["fleet_mem_allocated_ratio"] = \
            cs["fleet_mem_allocated_ratio"]
    finally:
        for c in clients:
            c.shutdown()
        srv.shutdown()
    return out


def bench_multiserver(n_nodes: int = 100, n_jobs: int = 32,
                      count: int = 6, waves: int = 3,
                      rtt_ms: float = 80.0) -> Dict:
    """Distributed scheduler plane (ISSUE 16): a real 3-server raft
    ring where followers dequeue evals from the leader's broker over
    RPC, schedule against their fenced local snapshots, and stream
    plans back through Plan.Submit into the leader's group-commit
    applier. The control arm is the SAME ring with
    NOMAD_TPU_FOLLOWER_SCHED=0 — only the leader schedules, i.e.
    single-server scheduling as every pre-r20 cluster ran it.

    The ring is geo-stretched: the fault injector's wire_latency arm
    stretches every AppendEntries round trip by `rtt_ms` in BOTH arms,
    standing in for real inter-server network distance on a loopback
    CI box. That is the regime the plane exists for — the control
    arm's single worker already hides commit latency behind its own
    depth-limited pipeline (r7), so on a co-located loopback ring the
    two arms mostly measure Python overhead. Once the commit RTT
    exceeds per-eval CPU, the control arm goes latency-bound while the
    plane keeps a cluster-wide window of plans in flight and the r9
    applier amortizes them into shared group commits (watch
    multiserver groups < plans). Placement rate is the best of
    `waves` identical deployment waves per arm — wave 0 pays JIT and
    cache warmup, and on a 1-core CI box any wave can lose the host
    to a neighbour, so per-wave best-of is the stable statistic.

    Per-server num_schedulers=1 in both arms: the plane's claim is
    that it turns the standby servers' otherwise-idle worker pools
    into schedulers, so the arms differ only in whether those pools
    may dequeue remotely (follower_max_remote=4)."""
    import os

    from ..chaos.faults import FaultInjector
    from ..mock import fixtures as mock
    from ..rpc import RpcServer
    from ..server import Server, ServerConfig

    def make_job(i: int) -> object:
        job = mock.job()
        job.id = f"msvc-{i}"
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = count
        tg.networks = []
        for t in tg.tasks:
            t.resources.networks = []
            t.resources.cpu = 50
            t.resources.memory_mb = 32
        return job

    def pause(servers, p: bool) -> None:
        for s in servers:
            for w in s.workers:
                w.set_pause(p)
            if s.follower_sched is not None:
                s.follower_sched.set_pause(p)

    def wait(pred, timeout_s: float) -> bool:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if pred():
                return True
            time.sleep(0.01)
        return False

    def run_arm(follower_on: bool) -> Dict:
        prev = os.environ.get("NOMAD_TPU_FOLLOWER_SCHED")
        os.environ["NOMAD_TPU_FOLLOWER_SCHED"] = \
            "1" if follower_on else "0"
        inj = FaultInjector(seed=0xB16).install()
        if rtt_ms > 0:
            inj.wire_latency(rtt_ms / 1000.0)
        servers, rpcs = [], []
        try:
            for _ in range(3):
                s = Server(ServerConfig(
                    num_schedulers=1, heartbeat_ttl_s=3600.0,
                    telemetry_sample_interval_s=0,
                    governor_interval_s=3600.0,
                    follower_max_remote=4))
                r = RpcServer(s, port=0)
                servers.append(s)
                rpcs.append(r)
            addrs = [r.addr for r in rpcs]
            for s, r in zip(servers, rpcs):
                s.attach_raft(r, addrs)
                r.start()
                s.start()
            assert wait(lambda: sum(
                s.raft.is_leader() for s in servers) == 1, 30.0), \
                "multiserver ring never elected a leader"
            lead = next(s for s in servers if s.raft.is_leader())
            pause(servers, True)
            time.sleep(1.0)     # park in-flight dequeues
            # pipelined node seeding: one raft entry per node, wait
            # only the last waiter (a sync register per node would pay
            # the stretched RTT n_nodes times)
            last_waiter = None
            for i in range(n_nodes):
                node = mock.node()
                node.name = f"mnode-{i}"
                node.datacenter = "dc1"
                node.compute_class()
                _idx, w = lead.raft_apply_async(
                    "node_register", dict(node=node))
                if w is not None:
                    last_waiter = w
            if last_waiter is not None:
                last_waiter()
            # warm wave outside the timed window: JIT compiles, device
            # table upload, select-kernel caches
            warm = [make_job(10 ** 6 + k) for k in range(2)]
            for j in warm:
                lead.register_job(j)
            pause(servers, False)
            assert wait(lambda: all(
                len(lead.store.allocs_by_job("default", j.id)) == count
                for j in warm), 120.0), "multiserver warm wave stuck"
            best_rate = 0.0
            placed_ok = True
            for wave in range(waves):
                pause(servers, True)
                time.sleep(1.0)
                jobs = [make_job(wave * 1000 + i)
                        for i in range(n_jobs)]
                for j in jobs:
                    lead.register_job(j)
                t0 = time.perf_counter()
                pause(servers, False)
                placed_ok = wait(lambda: all(
                    len(lead.store.allocs_by_job("default", j.id))
                    == count for j in jobs), 180.0) and placed_ok
                wall = time.perf_counter() - t0
                placed = sum(
                    len(lead.store.allocs_by_job("default", j.id))
                    for j in jobs)
                best_rate = max(best_rate, placed / wall)
            leases = dict(lead.eval_leases.snapshot_stats())
            fence = max((s.follower_sched.fence_wait_p99_ms()
                         for s in servers
                         if s.follower_sched is not None),
                        default=0.0)
            applier = dict(lead.plan_applier.stats)
            return {"rate": best_rate, "ok": placed_ok,
                    "leases": leases, "fence_p99_ms": fence,
                    "groups": applier.get("groups", 0),
                    "plans": applier.get("plans", 0)}
        finally:
            inj.uninstall()
            for s, r in zip(servers, rpcs):
                r.shutdown()
                s.shutdown()
            if prev is None:
                os.environ.pop("NOMAD_TPU_FOLLOWER_SCHED", None)
            else:
                os.environ["NOMAD_TPU_FOLLOWER_SCHED"] = prev

    on = run_arm(True)
    off = run_arm(False)
    # structural engagement fence (same spirit as the broker-batches
    # assert above): the plane must actually have scheduled remotely,
    # else the headline ratio is two copies of the control arm
    assert on["leases"].get("remote_plans", 0) > 0, (
        f"follower plane never submitted a remote plan: {on}")
    assert on["ok"] and off["ok"], (
        f"multiserver wave never fully placed: on={on} off={off}")
    return {
        "multiserver_placements_per_sec": round(on["rate"], 1),
        "multiserver_placements_per_sec_off": round(off["rate"], 1),
        "multiserver_speedup": round(
            on["rate"] / max(off["rate"], 1e-9), 2),
        "multiserver_fence_wait_p99_ms": round(
            on["fence_p99_ms"], 2),
        "multiserver_remote_demotions": int(
            on["leases"].get("remote_demotions", 0)),
        "multiserver_remote_dequeues": int(
            on["leases"].get("remote_dequeues", 0)),
        "multiserver_plan_groups": int(on["groups"]),
        "multiserver_plans": int(on["plans"]),
        "multiserver_rtt_ms": rtt_ms,
    }


def bench_ingest(n_nodes: int = 100, n_writers: int = 12,
                 regs_per_writer: int = 16,
                 updates_per_writer: int = 16,
                 warm_jobs: int = 4, warm_count: int = 4) -> Dict:
    """Columnar admission path (ISSUE 19): a register storm + client
    status flood from `n_writers` concurrent submitters, mixed with
    the service reads those registers trigger (the workers keep
    scheduling the storm's jobs while it runs). The batched arm runs
    the IngestGateway; the control arm is the SAME storm with
    `NOMAD_TPU_INGEST_BATCH=0` in-process — one raft entry, one store
    transaction, one event flush per write, as every pre-r22 server
    ingested. Registers go through the bulk array-body path in chunks
    (the designed storm client); status updates push one group per
    call so coalescing across submitters is the gateway's doing, not
    the workload's. Both arms run with a DURABLE WAL (wal_fsync, the
    r12 group-fsync discipline): the per-write cost a real server
    pays is the durability boundary, and amortizing it is precisely
    what write group-commit exists for — the control arm fsyncs once
    per raft entry, the batched arm once per coalesced batch. Keys:
    writes/s on vs off + speedup, the full write p99 each submitter
    saw, mean coalesced group size, shed count, and placements/s of
    the concurrent service reads (the not-regressing guard)."""
    import os
    import shutil
    import tempfile
    import threading

    from ..mock import fixtures as mock
    from ..models import Allocation
    from ..server import Server, ServerConfig
    from ..server.ingest import INGEST_ENV
    from ..utils.codec import from_wire, to_wire

    def make_job(tag: str, i: int, count: int) -> object:
        job = mock.job()
        job.id = f"ing-{tag}-{i}"
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = count
        tg.networks = []
        for t in tg.tasks:
            t.resources.networks = []
            t.resources.cpu = 20
            t.resources.memory_mb = 16
        return job

    def wait(pred, timeout_s: float) -> bool:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if pred():
                return True
            time.sleep(0.01)
        return False

    def run_arm(batch_on: bool) -> Dict:
        prev = os.environ.get(INGEST_ENV)
        os.environ[INGEST_ENV] = "1" if batch_on else "0"
        data_dir = tempfile.mkdtemp(prefix="nomad-tpu-bench-ingest-")
        srv = Server(ServerConfig(
            num_schedulers=2, heartbeat_ttl_s=3600.0,
            telemetry_sample_interval_s=0,
            governor_interval_s=3600.0,
            data_dir=data_dir, wal_fsync=True,
            snapshot_every=1 << 20))
        try:
            srv.start()
            for i in range(n_nodes):
                node = mock.node()
                node.name = f"ingnode-{i}"
                node.datacenter = "dc1"
                node.compute_class()
                srv.raft_apply("node_register", dict(node=node))
            # warm wave: real placed allocs for the status flood to
            # target, plus JIT/cache warmup outside the timed window
            warm = [make_job("warm", i, warm_count)
                    for i in range(warm_jobs)]
            for j in warm:
                srv.register_job(j)
            assert wait(lambda: all(
                len(srv.store.allocs_by_job("default", j.id))
                == warm_count for j in warm), 60.0), \
                "ingest warm wave stuck"
            warm_allocs = [a for j in warm
                           for a in srv.store.allocs_by_job(
                               "default", j.id)]
            # update payloads prepared OUTSIDE the timed window: the
            # client-side copy a real agent would push
            updates = []
            for k in range(n_writers * updates_per_writer):
                a = warm_allocs[k % len(warm_allocs)]
                cp = from_wire(Allocation, to_wire(a))
                cp.client_status = "running"
                updates.append([cp])
            storm = [[make_job("storm", w * regs_per_writer + i, 1)
                      for i in range(regs_per_writer)]
                     for w in range(n_writers)]

            def writer(w: int) -> None:
                regs, chunk = storm[w], 8
                ups = updates[w * updates_per_writer:
                              (w + 1) * updates_per_writer]
                ri = ui = 0
                while ri < len(regs) or ui < len(ups):
                    if ri < len(regs):
                        res = srv.register_jobs_bulk(
                            regs[ri:ri + chunk])
                        for r in res:
                            if isinstance(r, Exception):
                                raise r
                        ri += chunk
                    if ui < len(ups):
                        srv.update_alloc_status_from_client(ups[ui])
                        ui += 1

            threads = [threading.Thread(target=writer, args=(w,),
                                        daemon=True)
                       for w in range(n_writers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            write_wall = time.perf_counter() - t0
            all_storm = [j for regs in storm for j in regs]
            placed_ok = wait(lambda: all(
                len(srv.store.allocs_by_job("default", j.id)) == 1
                for j in all_storm), 120.0)
            place_wall = time.perf_counter() - t0
            placed = sum(len(srv.store.allocs_by_job("default", j.id))
                         for j in all_storm)
            writes = n_writers * (regs_per_writer + updates_per_writer)
            ing = srv.ingest
            return {
                "writes_per_sec": writes / write_wall,
                "placements_per_sec": placed / place_wall,
                "ok": placed_ok,
                "p99_ms": ing.write_p99_ms() if ing else 0.0,
                "group_mean": ing.mean_batch_size() if ing else 0.0,
                "shed": int(ing.stats["shed"]) if ing else 0,
                "coalesced": int(ing.stats["coalesced_writes"])
                if ing else 0,
            }
        finally:
            srv.shutdown()
            shutil.rmtree(data_dir, ignore_errors=True)
            if prev is None:
                os.environ.pop(INGEST_ENV, None)
            else:
                os.environ[INGEST_ENV] = prev

    on = run_arm(True)
    off = run_arm(False)
    # structural engagement fence: the gateway must actually have
    # coalesced concurrent writes, else the headline ratio compares
    # two copies of the sequential path
    assert on["group_mean"] > 1.0, (
        f"ingest gateway never coalesced a batch: {on}")
    assert on["ok"] and off["ok"], (
        f"ingest storm never fully placed: on={on} off={off}")
    return {
        "ingest_writes_per_sec": round(on["writes_per_sec"], 1),
        "ingest_writes_per_sec_off": round(off["writes_per_sec"], 1),
        "ingest_speedup": round(
            on["writes_per_sec"] / max(off["writes_per_sec"], 1e-9), 2),
        "ingest_write_p99_ms": round(on["p99_ms"], 2),
        "ingest_group_mean_size": round(on["group_mean"], 2),
        "ingest_coalesced_writes": int(on["coalesced"]),
        "ingest_shed": int(on["shed"]),
        "ingest_read_placements_per_sec": round(
            on["placements_per_sec"], 1),
        "ingest_read_placements_per_sec_off": round(
            off["placements_per_sec"], 1),
    }


def bench_scenario_matrix(quick: bool = True,
                          write: bool = False) -> Dict:
    """Scenario matrix under chaos (ISSUE 15): seeded workloads +
    injected faults + invariant checks against a real in-process
    server per cell (nomad_tpu/chaos/). Quick mode runs the three
    fastest cells — including the two acceptance-critical ones (a
    worker killed mid-commit, a corrupted WAL tail) — the full bench
    runs every single-process cell and writes the CHAOS_rNN.json
    artifact next to the bench's own."""
    from ..chaos.matrix import run_matrix, write_artifact
    names = (["batch_backfill", "drain_storm", "blocked_herd"]
             if quick else None)
    result = run_matrix(names=names, quick=quick)
    if write:
        write_artifact(result)
    s = result["summary"]
    by_name = {c["name"]: c for c in result["cells"]}
    out: Dict = {
        "chaos_cells": s["cells"],
        "chaos_cells_passed": s["passed"],
        "chaos_invariants_checked": s["invariants_checked"],
        "chaos_invariants_failed": s["invariants_failed"],
        "chaos_race_findings": s["race_findings"],
        "chaos_race": result["race"],
    }
    # the two acceptance cells get first-class pass/fail keys: no
    # lost/duplicated alloc across a worker kill mid-commit and
    # across a WAL-tail-corruption recovery
    if "batch_backfill" in by_name:
        out["chaos_worker_kill_pass"] = by_name["batch_backfill"]["pass"]
    if "drain_storm" in by_name:
        out["chaos_wal_corruption_pass"] = by_name["drain_storm"]["pass"]
    return out


def run_ladder(quick: bool = False) -> Dict:
    """Run the full ladder; returns a flat dict of results."""
    out: Dict = {}
    r2 = bench_batch_e2e()
    out["e2e_placements_per_sec"] = round(r2["rate"], 1)
    out["e2e_batch10k_process_s"] = round(r2["process_s"], 3)
    out["e2e_batch10k_placed"] = r2["placed"]
    r3 = bench_service_p99(n_nodes=2000 if quick else 10000,
                           n_evals=10 if quick else 50)
    out["service_p99_ms"] = round(r3["p99_ms"], 1)
    out["service_p50_ms"] = round(r3["p50_ms"], 1)
    # same measurement + key as prior rounds (harness-sequential rate)
    out["service_placements_per_sec"] = round(r3["rate"], 1)
    # production-path service throughput: broker -> batched workers ->
    # select_many -> pipelined applier (VERDICT r3 item 1), reported
    # under its own keys
    out.update(bench_broker_service(
        n_nodes=2000 if quick else 10000,
        n_jobs=16 if quick else 64))
    r4 = bench_preemption(n_nodes=200 if quick else 1000,
                          n_evals=3 if quick else 10)
    out["preemption_placements_per_sec"] = round(r4["rate"], 1)
    out["preemption_placements_per_sec_off"] = round(r4["rate_off"], 1)
    out["preemption_preempted"] = r4["preempted"]
    out["preemption_p99_ms"] = round(r4["p99_ms"], 1)
    # batched columnar victim selection vs the per-node reference
    # path, same seeded scenario in-process (ISSUE 10): speedup is the
    # accumulated preempt-stage (victim-selection) seconds ratio
    out["preemption_speedup"] = round(r4["speedup"], 2)
    out["preemption_p50_ms"] = round(r4["p50_ms"], 2)
    out["preemption_nodes_scanned"] = r4["nodes_scanned"]
    out["preemption_victim_cache_hit_rate"] = round(
        r4["cache_hit_rate"], 4)
    # compiled feasibility engine vs the per-node scalar checks over
    # the same seeded constraint-heavy scenario in-process (ISSUE 17):
    # speedup is the accumulated feasibility-stage seconds ratio; the
    # warm window must run entirely on the mask patch path (zero
    # column rebuilds, hit rate ~1)
    out.update(bench_feasibility(
        n_nodes=512 if quick else 5000,
        n_rounds=8 if quick else 20))
    # residue layer atop the compiled engine (ISSUE 20): CSI/spread/
    # distinct-heavy rounds where the device mask token must outlive
    # per-eval mask mutations via sparse residue scatters, and
    # spread/distinct scoring inputs build vectorized off the interned
    # columns vs the O(N) Python re-encode
    out.update(bench_feas_residue(
        n_nodes=512 if quick else 5000,
        n_rounds=8 if quick else 20))
    # columnar reconcile engine on vs off over a rolling deployment
    # wave (ISSUE 6 satellite: 10k-alloc job, 3 rolling versions)
    # quick mode keeps 8 evals/version: the on-vs-off ratio is asserted
    # >= 2x in CI (measured ~3.6x) and more timed evals smooth
    # wall-clock noise on loaded runners
    out.update(bench_deployment_wave(
        n_nodes=300 if quick else 1000,
        count=2000 if quick else 10000,
        versions=2 if quick else 3,
        evals_per_version=8))
    # cold-start recovery: columnar vs legacy snapshot restore on the
    # same seeded store (ISSUE 8; speedup floor asserted in
    # tests/test_bench_smoke.py)
    out.update(bench_cold_start(
        n_nodes=300 if quick else 1000,
        seed_allocs=8000 if quick else 30000,
        n_jobs=6 if quick else 8))
    # fleet observability rollup (ISSUE 13): real client agents with
    # the stats sampler on; records the used-vs-allocated economics
    out.update(bench_cluster_stats(
        n_clients=2 if quick else 4,
        n_allocs=4 if quick else 8))
    # distributed scheduler plane over a geo-stretched 3-server ring
    # (ISSUE 16): follower scheduling on vs the leader-only control
    out.update(bench_multiserver(
        n_jobs=24 if quick else 32,
        waves=2 if quick else 3))
    # columnar admission path (ISSUE 19): batched write ingest on vs
    # the one-entry-per-write control, same in-process storm
    out.update(bench_ingest(
        regs_per_writer=16 if quick else 32,
        updates_per_writer=16 if quick else 32))
    # scenario matrix under chaos (ISSUE 15): quick runs the three
    # fastest cells (incl. worker-kill + WAL-corruption); the full
    # bench runs every single-process cell and emits CHAOS_rNN.json
    out.update(bench_scenario_matrix(quick=quick, write=not quick))
    return out
