"""Profile the C2M batch-eval path wall-to-wall (round-5 perf work).

Usage: python -m nomad_tpu.bench.profile_c2m [n_nodes] [seed_allocs]
Runs on the ambient JAX backend (JAX_PLATFORMS=cpu for a CPU profile)
and prints which one that was.
"""
import cProfile
import io
import pstats
import sys
import time

import numpy as np


def main():
    n_nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 50000
    seed_allocs = int(sys.argv[2]) if len(sys.argv) > 2 else 0

    from nomad_tpu.utils.platform import init_backend
    print(f"backend: {init_backend()}", flush=True)
    from nomad_tpu.bench.ladder import (_eval_for, _seed_nodes,
                                        seed_c2m_allocs)
    from nomad_tpu.mock import fixtures as mock
    from nomad_tpu.scheduler.harness import Harness

    h = Harness()
    t0 = time.perf_counter()
    nodes = _seed_nodes(h, n_nodes)
    print(f"seed_nodes: {time.perf_counter()-t0:.2f}s", flush=True)

    if seed_allocs:
        t0 = time.perf_counter()
        seed_c2m_allocs(h, nodes, seed_allocs, sched_allocs=0)
        print(f"seed_allocs({seed_allocs}): {time.perf_counter()-t0:.2f}s",
              flush=True)

    t0 = time.perf_counter()
    h.store.snapshot().node_table()
    print(f"table_build: {time.perf_counter()-t0:.2f}s", flush=True)

    dcs = [f"dc{d}" for d in (1, 2, 3, 4)]

    def make_batch(i, count=10000):
        job = mock.batch_job()
        job.id = f"pb-{i}"
        job.datacenters = dcs
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.networks = []
        tg.networks = []
        return job

    # warm (compile + caches)
    for i in range(2):
        job = make_batch(10**6 + i)
        h.store.upsert_job(h.next_index(), job)
        t0 = time.perf_counter()
        h.process("batch", _eval_for(job))
        print(f"warm eval {i}: {time.perf_counter()-t0:.2f}s", flush=True)

    # timed, no profiler (clean number)
    for i in range(3):
        job = make_batch(i)
        h.store.upsert_job(h.next_index(), job)
        t0 = time.perf_counter()
        h.process("batch", _eval_for(job))
        el = time.perf_counter() - t0
        placed = sum(len(a) for a in h.plans[-1].node_allocation.values())
        print(f"timed eval {i}: {el:.3f}s placed={placed} "
              f"rate={placed/el:.0f}/s", flush=True)

    # profiled
    job = make_batch(999)
    h.store.upsert_job(h.next_index(), job)
    pr = cProfile.Profile()
    pr.enable()
    h.process("batch", _eval_for(job))
    pr.disable()
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue())


if __name__ == "__main__":
    main()
