"""The fleet and backlog seeders the benchmark (benchmark/lib/agent.py),
chip_smoke.py and the tests build their clusters with. They belong in
nomad_tpu/mock/ and stay here because the benchmark's import may be
re-pointed by a benchmark issue alone (ROADMAP D0).
"""

from __future__ import annotations

import time
from typing import Dict


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
