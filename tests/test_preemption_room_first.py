"""Room first (PR 34; upstream selectNextOption): a select without
preemption runs first, and only the instances it found no node for go
to a second select that carries the round. On a fleet with room for k
instances an ask of k + m evicts for exactly m; an eval on a fleet with
room builds no round at all."""
import pytest

from nomad_tpu import mock
from nomad_tpu.models import (EVAL_STATUS_PENDING, TRIGGER_JOB_REGISTER,
                              Evaluation, PreemptionConfig,
                              SchedulerConfiguration)
from nomad_tpu.scheduler import preemption as pmod
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.utils.ids import generate_uuid

NODES = 640


def _filler(job, node_id):
    a = mock.alloc()
    a.job, a.job_id, a.namespace = job, job.id, job.namespace
    a.node_id = node_id
    a.task_group = job.task_groups[0].name
    tr = a.allocated_resources.tasks["web"]
    tr.cpu.cpu_shares, tr.memory.memory_mb, tr.networks = 3500, 7000, []
    return a


def fleet(room: int) -> Harness:
    """640 mock nodes (3,900 MHz / 7,936 MB each), all but `room` of
    them filled by one priority-20 resident of cpu 3,500: an ask of cpu
    2,000 fits once on an empty node and nowhere else without an
    eviction. Service preemption on."""
    h = Harness()
    h.store.set_scheduler_config(
        h.next_index(),
        SchedulerConfiguration(preemption_config=PreemptionConfig(
            service_scheduler_enabled=True)))
    nodes = [mock.node() for _ in range(NODES)]
    for n in nodes:
        h.store.upsert_node(h.next_index(), n)
    low = mock.job()
    low.id, low.priority = "backfill", 20
    h.store.upsert_job(h.next_index(), low)
    h.store.upsert_allocs(h.next_index(),
                          [_filler(low, n.id) for n in nodes[room:]])
    return h


def place(h: Harness, count: int):
    job = mock.job()
    job.id, job.priority = f"svc-{count}", 70
    tg = job.task_groups[0]
    tg.count, tg.networks = count, []
    for t in tg.tasks:
        t.resources.networks = []
        t.resources.cpu, t.resources.memory_mb = 2000, 4000
    h.store.upsert_job(h.next_index(), job)
    h.process("service", Evaluation(
        id=generate_uuid(), namespace="default", priority=70,
        triggered_by=TRIGGER_JOB_REGISTER, job_id=job.id,
        status=EVAL_STATUS_PENDING, type="service"))
    plan = h.plans[-1]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    evicted = [a for allocs in plan.node_preemptions.values()
               for a in allocs]
    return placed, evicted


@pytest.mark.parametrize("k,m", [(5, 3), (1, 1), (12, 20)])
def test_an_ask_of_k_plus_m_on_room_for_k_evicts_for_exactly_m(k, m):
    h = fleet(room=k)
    placed, evicted = place(h, k + m)
    assert len(placed) == k + m
    evicting = [a for a in placed if a.preempted_allocations]
    assert len(evicting) == m and len(evicted) == m
    # the k that found room took the empty nodes and evicted nobody
    assert len({a.node_id for a in placed}) == k + m
    assert sorted(v for a in evicting for v in a.preempted_allocations) \
        == sorted(a.id for a in evicted)


@pytest.mark.parametrize("columnar", ["1", "0"])
def test_an_eval_on_a_fleet_with_room_builds_no_round(columnar,
                                                       monkeypatch):
    """Preemption is switched on and the fleet has room: the eval pays
    no round (PREEMPT_STATS unmoved, no victims' columns derived), on
    either arm of NOMAD_TPU_COLUMNAR_PREEMPT."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_PREEMPT", columnar)
    h = fleet(room=8)
    before = pmod.preempt_stats()
    placed, evicted = place(h, 8)
    assert len(placed) == 8 and not evicted
    assert pmod.preempt_stats() == before
    assert h.store.snapshot().node_table().victims is None


def test_a_fleet_with_no_room_evicts_for_every_instance():
    h = fleet(room=0)
    placed, evicted = place(h, 6)
    assert len(placed) == 6 == len(evicted)
    assert all(len(a.preempted_allocations) == 1 for a in placed)


def test_an_evicting_ask_ranks_its_workers_lane_at_any_count():
    """Two workers that read the same victims' columns see the same
    ties; a select that carries them is cut to the worker's hash lane
    whatever its count (an ask with room keeps the rule it had: 256
    instances or more), so the two never chase one node."""
    import numpy as np
    from nomad_tpu.ops.select import SelectKernel, SelectRequest

    n = 640

    def req(count, victims):
        return SelectRequest(
            ask=np.array([600, 512, 150, 0], np.float32), count=count,
            feasible=np.ones(n, bool),
            capacity=np.full((n, 4), 3900, np.float32),
            used=np.full((n, 4), 3850, np.float32),    # nobody has room
            desired_count=1.0, tg_collisions=np.zeros(n, np.int32),
            job_count=np.zeros(n, np.int32), victims=victims)

    lanes = []
    for lane in (0, 1):
        k = SelectKernel()
        k.decorrelate = (lane, 2)
        r = req(1, victims=object())
        assert k._decorrelate_mask(r) is not None     # the mask it had
        lanes.append(r.feasible.copy())
        assert k._decorrelate_mask(req(1, None)) is None
        # more instances than half the lane holds: the whole fleet
        assert k._decorrelate_mask(req(200, object())) is None
    assert not (lanes[0] & lanes[1]).any() and (lanes[0] | lanes[1]).all()
    assert 250 < lanes[0].sum() < 390
