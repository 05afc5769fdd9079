"""The victims' columns kept with the node table (ops/victims.py
`VictimColumns`, NodeTable.victim_columns; PR 34): after a seeded run
of commits, evictions and stops the columns a table version is served
equal a fresh build, row for row — whether every version was asked for
them or several refreshes went by in between — and a cluster that never
asks derives nothing."""
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.models.alloc import (ALLOC_DESIRED_EVICT,
                                    ALLOC_DESIRED_STOP)
from nomad_tpu.models.job import MigrateStrategy
from nomad_tpu.ops import victims as vops
from nomad_tpu.ops.victims import VictimColumns
from nomad_tpu.state.store import StateStore


def _alloc(job, node_id, cpu, mem):
    a = mock.alloc()
    a.job, a.job_id, a.namespace = job, job.id, job.namespace
    a.node_id = node_id
    a.task_group = job.task_groups[0].name
    tr = a.allocated_resources.tasks["web"]
    tr.cpu.cpu_shares, tr.memory.memory_mb, tr.networks = cpu, mem, []
    return a


class Cluster:
    def __init__(self, seed: int, nodes: int = 400):
        self.rng = random.Random(seed)
        self.store = StateStore()
        self.idx = 0
        self.nodes = [mock.node() for _ in range(nodes)]
        for n in self.nodes:
            self.store.upsert_node(self._next(), n)
        self.jobs = []
        for prio in (20, 20, 40, 65):
            j = mock.job()
            j.priority = prio
            if prio == 40:
                j.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
            self.store.upsert_job(self._next(), j)
            self.jobs.append(j)
        self.live = []
        self.commit(3 * nodes)

    def _next(self) -> int:
        self.idx += 1
        return self.idx

    def commit(self, count: int) -> None:
        """`count` fresh placements on random nodes."""
        new = [_alloc(self.rng.choice(self.jobs),
                      self.rng.choice(self.nodes).id,
                      self.rng.choice([100, 200, 210]),
                      self.rng.choice([128, 256, 512]))
               for _ in range(count)]
        self.store.upsert_allocs(self._next(), new)
        self.live.extend(new)

    def end(self, count: int, status: str) -> None:
        """`count` residents evicted or stopped."""
        self.rng.shuffle(self.live)
        gone, self.live = self.live[:count], self.live[count:]
        out = []
        for a in gone:
            b = a.copy()
            b.desired_status = status
            out.append(b)
        self.store.upsert_allocs(self._next(), out)

    def step(self) -> None:
        what = self.rng.choice(["commit", "evict", "stop", "both"])
        if what in ("commit", "both"):
            self.commit(self.rng.randint(1, 12))
        if what in ("evict", "both"):
            self.end(self.rng.randint(1, 6), ALLOC_DESIRED_EVICT)
        if what == "stop":
            self.end(self.rng.randint(1, 6), ALLOC_DESIRED_STOP)

    def served(self):
        snap = self.store.snapshot()
        return snap, snap.node_table()


def same(a: VictimColumns, b: VictimColumns) -> None:
    assert (a.n, a.n_pad, a.slots) == (b.n, b.n_pad, b.slots)
    assert [[x.id for x in row] for row in a.rows] == \
        [[x.id for x in row] for row in b.rows]
    assert a.over == b.over and a.mp_groups == b.mp_groups
    ca, cb = np.asarray(a.cols), np.asarray(b.cols)
    for i in range(a.n_pad):
        assert np.array_equal(ca[:, i], cb[:, i]), i


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_columns_advanced_commit_by_commit_equal_a_fresh_build(seed):
    c = Cluster(seed)
    snap, table = c.served()
    first = table.victim_columns(snap)
    assert first.refreshed == table.n           # the one full build
    for _ in range(25):
        c.step()
        snap, table = c.served()
        assert table.victims is None            # nothing derived yet
        vc = table.victim_columns(snap)
        assert 0 < vc.refreshed < table.n       # advanced, not rebuilt
        assert vc.slots == first.slots
        same(vc, VictimColumns.build(table, snap, first.slots))
    assert table.victim_columns(snap) is vc     # once a version


@pytest.mark.parametrize("seed", [4, 5])
def test_refreshes_nobody_asked_about_are_caught_up_in_one_advance(seed):
    """Three refreshes go by between two demands: the later version
    advances from the earlier one's columns by the union of the rows
    touched since."""
    c = Cluster(seed)
    snap, table = c.served()
    table.victim_columns(snap)
    for _ in range(6):
        touched = set()
        for _ in range(3):
            c.step()
            snap, table = c.served()
            touched |= set(table._victims_base[1])
        assert table._victims_base[1] == frozenset(touched)
        vc = table.victim_columns(snap)
        assert vc.refreshed == len(touched)
        assert table._victims_base is None
        same(vc, VictimColumns.build(table, snap, vc.slots))


def test_a_cluster_that_never_preempts_derives_nothing():
    c = Cluster(6)
    for _ in range(5):
        c.step()
        _snap, table = c.served()
        assert table.victims is None and table._victims_base is None


def test_a_refresh_that_touches_most_of_the_fleet_builds_anew():
    c = Cluster(7, nodes=8)
    snap, table = c.served()
    table.victim_columns(snap)
    c.commit(60)                                # every row, most likely
    snap, table = c.served()
    assert table._victims_base is None
    vc = table.victim_columns(snap)
    assert vc.refreshed == table.n
    same(vc, VictimColumns.build(table, snap, vc.slots))


def test_a_row_that_outgrows_the_columns_leaves_them_and_comes_back():
    c = Cluster(8, nodes=8)
    snap, table = c.served()
    vc = table.victim_columns(snap)
    row = 0
    node_id = table.ids[row]
    extra = [_alloc(c.jobs[0], node_id, 10, 16)
             for _ in range(vc.slots + 1 - len(vc.rows[row]))]
    c.store.upsert_allocs(c._next(), extra)
    snap, table = c.served()
    wide = table.victim_columns(snap)
    assert wide.over == frozenset({row}) and wide.slots == vc.slots
    assert np.isinf(np.asarray(wide.cols)[vops.F_PRIO, row]).all()
    assert len(wide.rows[row]) == vc.slots + 1
    gone = [a.copy() for a in extra[:3]]
    for a in gone:
        a.desired_status = ALLOC_DESIRED_STOP
    c.store.upsert_allocs(c._next(), gone)
    snap, table = c.served()
    back = table.victim_columns(snap)
    assert not back.over
    same(back, VictimColumns.build(table, snap, vc.slots))
