"""What nomad_tpu/bench/ladder.py still holds: the fleet and backlog
seeders the benchmark, chip_smoke.py and other tests build clusters
with, at a scale tier-1 can afford."""


def test_c2m_seed_path_at_toy_scale():
    """seed_c2m_allocs' two ways in (the scheduler path and the replay
    loader): the store really holds the rows, and a batch eval over
    the seeded fleet still places."""
    from nomad_tpu import mock
    from nomad_tpu.bench.ladder import (_eval_for, _seed_nodes,
                                        seed_c2m_allocs)
    from nomad_tpu.scheduler.harness import Harness

    h = Harness()
    nodes = _seed_nodes(h, 200)
    out = seed_c2m_allocs(h, nodes, 5000, sched_allocs=2000)
    assert set(out) == {"seed_s", "sched_s"}
    assert sum(1 for _ in h.store.allocs()) == 5000
    assert len(h.store.allocs_by_job("default", "c2m-seed")) == 3000

    job = mock.batch_job()
    job.id = "c2m-batch"
    job.datacenters = [f"dc{d}" for d in (1, 2, 3, 4)]
    tg = job.task_groups[0]
    tg.count = 50
    tg.tasks[0].resources.networks = []
    tg.networks = []
    h.store.upsert_job(h.next_index(), job)
    h.process("batch", _eval_for(job))
    assert sum(len(a) for a in h.plans[-1].node_allocation.values()) == 50
