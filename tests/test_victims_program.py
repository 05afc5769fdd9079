"""The victims' program (ops/victims.py `_select_victims_fn`) against
the per-node `Preemptor`, on seeded fleets (PR 34).

Held to: the SAME victim ids on every node (and in the same order), the
same freed resources, and scores within SCORE_TOL. Why a tolerance at
all, and why this one: the program computes in float32 on the device,
the Preemptor in Python floats (float64). Resources are whole MHz / MB
below 2^24, so sums, fits and therefore victim sets are exact in both;
a score is ((20 - 10^a - 10^b) / 18 + 1 / (1 + e^x)) / 2 in [0, 1],
where float32 rounds each step by 6e-8 and a chip's pow is off by up to
60 ulp (.claude/skills/verify, PR 21): 2e-5 covers that with room and
is two hundred times tighter than bfloat16's 4e-3, which the last test
shows would fail it.
"""
import os
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.models.job import MigrateStrategy
from nomad_tpu.models.plan import Plan
from nomad_tpu.ops.tables import ProposedIndex
from nomad_tpu.scheduler import preemption as pmod
from nomad_tpu.scheduler.preemption import PreemptionRound
from nomad_tpu.state.store import StateStore

SCORE_TOL = 2e-5
TIERS = [(20, 100, 128, 10), (40, 200, 256, 20), (65, 210, 512, 50)]


@pytest.fixture(autouse=True)
def _switch():
    prev = os.environ.pop("NOMAD_TPU_COLUMNAR_PREEMPT", None)
    yield
    os.environ.pop("NOMAD_TPU_COLUMNAR_PREEMPT", None)
    if prev is not None:
        os.environ["NOMAD_TPU_COLUMNAR_PREEMPT"] = prev


def _alloc(job, node_id, cpu, mem, disk):
    a = mock.alloc()
    a.job, a.job_id, a.namespace = job, job.id, job.namespace
    a.node_id = node_id
    a.task_group = job.task_groups[0].name
    tr = a.allocated_resources.tasks["web"]
    tr.cpu.cpu_shares, tr.memory.memory_mb, tr.networks = cpu, mem, []
    a.allocated_resources.shared.disk_mb = disk
    return a


def fleet(seed: int, priority: int, nodes: int = 24, wide: int = 0):
    """A seeded fleet filled in tiers like the benchmark's (priorities
    20 / 40 / 65, two jobs a tier, some with a max_parallel), each node
    nearly to the brim, with a few residents of the placing job
    itself; `wide` > 0 gives node 0 that many small residents more (a
    row wider than the columns)."""
    with mock.seeded_mock_ids(seed):
        rng = random.Random(seed)
        store = StateStore()
        idx = 1
        ns = [mock.node() for _ in range(nodes)]
        for n in ns:
            store.upsert_node(idx, n)
            idx += 1
        jobs = []
        for prio, cpu, mem, disk in TIERS:
            for _ in range(2):
                j = mock.job()
                j.priority = prio
                if rng.random() < 0.3:
                    j.task_groups[0].migrate = MigrateStrategy(
                        max_parallel=rng.randint(1, 2))
                store.upsert_job(idx, j)
                idx += 1
                jobs.append((j, cpu, mem, disk))
        placing = mock.job()
        placing.priority = priority
        store.upsert_job(idx, placing)
        idx += 1
        allocs = []
        for k, n in enumerate(ns):
            # to within 50 / 200 / 500 / 900 MHz of the node's 3,900
            left = 3900 - rng.choice([50, 200, 500, 900])
            if rng.random() < 0.2:
                allocs.append(_alloc(placing, n.id, 300, 256, 0))
                left -= 300
            while True:
                j, cpu, mem, disk = rng.choice(jobs)
                if cpu > left:
                    break
                allocs.append(_alloc(j, n.id, cpu, mem, disk))
                left -= cpu
            if k == 0:
                low = jobs[0]
                allocs.extend(_alloc(low[0], n.id, 10, 16, 1)
                              for _ in range(wide))
        store.upsert_allocs(idx, allocs)
        snap = store.snapshot()
        return snap, snap.node_table(), placing


def round_of(snap, table, job, ask, program: bool, staged=()):
    os.environ["NOMAD_TPU_COLUMNAR_PREEMPT"] = "1" if program else "0"
    table.preempt_cache.clear()
    plan = Plan(job=job, eval_id="e1")
    for v in staged:
        plan.append_preempted_alloc(v, "")
    r = PreemptionRound(snap, table, np.ones(table.n, bool),
                        np.asarray(ask, np.float32), job, plan)
    assert r._columnar == program
    used = ProposedIndex(table, job,
                         snap.allocs_by_job(job.namespace, job.id),
                         plan).used()
    pre, freed = r.columns(used)
    return r, pre, freed


def agree(a, b):
    (ra, pre_a, freed_a), (rb, pre_b, freed_b) = a, b
    assert {i: [v.id for v in vs] for i, vs in ra._victims.items()} == \
        {i: [v.id for v in vs] for i, vs in rb._victims.items()}
    assert np.array_equal(freed_a, freed_b)
    assert np.allclose(pre_a, pre_b, rtol=0.0, atol=SCORE_TOL)
    assert np.allclose(ra._scores, rb._scores, rtol=0.0, atol=SCORE_TOL)
    return len(rb._victims)


@pytest.mark.parametrize("priority", [55, 70])
@pytest.mark.parametrize("ask", [(600, 512, 150, 0), (2000, 4000, 0, 0),
                                 (1500, 2048, 300, 0)],
                         ids=["600", "2000", "1500"])
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_the_program_picks_the_preemptors_victims(seed, ask, priority):
    snap, table, job = fleet(seed, priority)
    n = agree(round_of(snap, table, job, ask, True),
              round_of(snap, table, job, ask, False))
    # priority 55 sees the two batch tiers, 70 the same (65 is within
    # 10 of it): a fleet this full has victims to give for every ask
    assert n > 0


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_preemptions_the_plan_already_holds_count_against_their_group(
        seed):
    """The max_parallel penalty reads how many the plan has already
    preempted from a slot's group; those slots are themselves out."""
    snap, table, job = fleet(seed, 70)
    pool = [a for node in table.nodes
            for a in snap.allocs_by_node(node.id) if a.job.priority < 60]
    staged = random.Random(seed).sample(pool, 3)
    agree(round_of(snap, table, job, (600, 512, 150, 0), True, staged),
          round_of(snap, table, job, (600, 512, 150, 0), False, staged))


@pytest.mark.parametrize("seed", [21, 22])
def test_a_row_wider_than_the_columns_takes_the_per_node_path(
        seed, monkeypatch):
    """One node holds more residents than the columns are allowed slots:
    its row stays empty on the device, the Preemptor evaluates it on
    the host, and its entry is laid over the program's result."""
    monkeypatch.setattr(pmod, "ROWS_MAX", 64)
    snap, table, job = fleet(seed, 70, wide=60)
    fb0 = pmod.PREEMPT_STATS["fallback_nodes"]
    a = round_of(snap, table, job, (1500, 2048, 300, 0), True)
    vc = table.victims
    wide = max(range(table.n), key=lambda i: len(table.live_allocs[i]))
    assert vc.slots == 64 and vc.over == frozenset({wide})
    assert pmod.PREEMPT_STATS["fallback_nodes"] == fb0 + 1
    agree(a, round_of(snap, table, job, (1500, 2048, 300, 0), False))
    assert wide in a[0]._victims and a[1][wide] > 0


def test_the_tolerance_would_refuse_bfloat16():
    """What SCORE_TOL is worth: the same scores rounded to bfloat16 (8
    bits of mantissa) leave it on most nodes."""
    import jax.numpy as jnp
    snap, table, job = fleet(1, 70)
    _r, pre, _freed = round_of(snap, table, job, (600, 512, 150, 0),
                               False)
    rounded = np.asarray(jnp.asarray(pre).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    off = np.abs(rounded - pre)[pre > 0]
    assert off.size and (off > SCORE_TOL).mean() > 0.5


@pytest.mark.parametrize("seed", [31, 32])
def test_a_node_that_needs_more_victims_than_the_program_picks(
        seed, monkeypatch):
    """The program writes down at most PICKS_MAX picks a node; a node
    still short of the ask then is reported unfinished, evaluated by
    the Preemptor on the host and laid over a second dispatch."""
    from nomad_tpu.ops import victims as vops
    monkeypatch.setattr(vops, "PICKS_MAX", 2)
    snap, table, job = fleet(seed, 70)
    fb0 = pmod.PREEMPT_STATS["fallback_nodes"]
    a = round_of(snap, table, job, (1500, 2048, 300, 0), True)
    handed_over = pmod.PREEMPT_STATS["fallback_nodes"] - fb0
    b = round_of(snap, table, job, (1500, 2048, 300, 0), False)
    agree(a, b)
    deep = [i for i, vs in b[0]._victims.items() if len(vs) > 2]
    assert deep and handed_over >= len(deep)
    assert set(deep) <= a[0]._host
