"""Two stalls the 10k-node service cell met, and what keeps them out of
a window (ISSUE 27): the shed valve opened on the collector's pauses
(its p99 gauge now reads an eval's latency without the collections
that gcsafe ran meanwhile), and a whole-store snapshot every 1,024
entries (now every 8,192 entries or 1 GiB of WAL)."""
import os
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.persistence import Persistence
from nomad_tpu.utils import gcsafe


@pytest.mark.parametrize("whole", [False, True],
                         ids=["full_pass", "whole_walk"])
def test_safepoint_collections_are_on_the_ledger(monkeypatch, whole):
    """A full pass that walks what is new, and one that unfreezes and
    walks everything (the regime's first; later ones by growth): either
    way the whole interval, freeze and count included, is a pause the
    governor's gauge can take out."""
    from nomad_tpu.utils import stages
    monkeypatch.setattr(gcsafe, "MIN_COLLECT_INTERVAL_S", 0.0)
    monkeypatch.setattr(gcsafe, "MIN_PAUSE_S", 0.0)
    walks = []
    prev, prev_on = stages._trace_hook, stages._trace_on
    stages.set_trace_hook(
        lambda stage, seconds, attrs=None:
        stage == "gc_whole_walk" and walks.append(seconds))
    try:
        with gcsafe.safepoints():
            if not whole:
                monkeypatch.setattr(gcsafe, "_last_full_collect", 0.0)
                gcsafe.safepoint()      # the regime's first, out of the way
                del walks[:]
            monkeypatch.setattr(gcsafe, "_last_collect", 0.0)
            monkeypatch.setattr(gcsafe, "_last_full_collect", 0.0)
            gcsafe.PAUSES.clear()
            t0 = time.monotonic()
            gcsafe.safepoint()          # a full collection: the budget is due
            t1 = time.monotonic()
    finally:
        stages.set_trace_hook(prev, on=prev_on)
    (start, end), = gcsafe.PAUSES
    assert t0 <= start <= end <= t1
    assert gcsafe.pause_overlap_s(t0, t1) == end - start
    assert len(walks) == (1 if whole else 0)
    assert all(0.0 < s <= end - start for s in walks)
    gcsafe.PAUSES.clear()


def test_pause_overlap_is_clipped_to_the_interval():
    gcsafe.PAUSES.clear()
    try:
        gcsafe.PAUSES.extend([(10.0, 11.6), (20.0, 20.5), (30.0, 31.0)])
        assert gcsafe.pause_overlap_s(0.0, 5.0) == 0.0
        assert gcsafe.pause_overlap_s(10.5, 10.75) == 0.25      # inside one
        assert gcsafe.pause_overlap_s(11.0, 20.25) == pytest.approx(0.85)
        assert gcsafe.pause_overlap_s(9.0, 40.0) == pytest.approx(3.1)
    finally:
        gcsafe.PAUSES.clear()


def test_the_pressure_gauge_does_not_read_the_collector(monkeypatch):
    """An eval that spans a collection feeds the governor its latency
    less the pause: here all of it, so the gauge reads nought."""
    srv = Server(ServerConfig(num_schedulers=1, heartbeat_ttl_s=3600.0))
    seen = []
    real = srv.governor.observe_eval_latency
    monkeypatch.setattr(
        srv.governor, "observe_eval_latency",
        lambda seconds, queue_wait_s=0.0: (
            seen.append(seconds), real(seconds, queue_wait_s))[1])
    # every interval the worker asks about was one long collection
    monkeypatch.setattr(gcsafe, "pause_overlap_s", lambda t0, t1: t1 - t0)
    srv.start()
    try:
        for i in range(4):
            node = mock.node()
            node.name = f"valve-n{i}"
            node.compute_class()
            srv.register_node(node)
        job = mock.job()
        job.id = "valve-job"
        tg = job.task_groups[0]
        tg.count = 2
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        srv.register_job(job)
        deadline = time.time() + 30
        while time.time() < deadline and not seen:
            time.sleep(0.01)
    finally:
        srv.shutdown()
    assert seen and all(s < 1e-6 for s in seen)     # all of it was pause


@pytest.mark.parametrize("stall", ["slow_host", "collector"])
def test_the_valve_opens_on_a_slow_host_and_not_on_the_collector(
        monkeypatch, stall):
    """Overload still sheds: evals that take 300 ms of the host each
    put the p99 gauge over its watermark (100 ms here) and engage
    backpressure. The same 300 ms spent inside collections do not:
    holding new evals back shortens no collection, and the silence it
    makes (P99_STALE_S) is longer than the pause it answers."""
    from nomad_tpu.governor.governor import Governor
    from nomad_tpu.scheduler.generic import GenericScheduler
    monkeypatch.setattr(Governor, "P99_WINDOW", 4)
    srv = Server(ServerConfig(
        num_schedulers=1, heartbeat_ttl_s=3600.0, governor_interval_s=3600.0,
        governor_p99_high_ms=100.0, governor_p99_min_samples=4))
    srv.start()
    gcsafe.PAUSES.clear()

    def run_jobs(tag, n):
        before = srv.governor.latency_samples()
        for i in range(n):
            job = mock.job()
            job.id = f"valve-{stall}-{tag}-{i}"
            tg = job.task_groups[0]
            tg.count = 1
            for t in tg.tasks:
                t.resources.networks = []
            tg.networks = []
            srv.register_job(job)
        deadline = time.time() + 60
        while time.time() < deadline and \
                srv.governor.latency_samples() < before + n:
            time.sleep(0.01)
        assert srv.governor.latency_samples() >= before + n

    try:
        for i in range(4):
            node = mock.node()
            node.name = f"valve-{stall}-n{i}"
            node.compute_class()
            srv.register_node(node)
        run_jobs("warm", 2)             # compiles, first table build
        real = GenericScheduler.process

        def stalled(self, ev):
            t0 = time.monotonic()
            time.sleep(0.3)
            if stall == "collector":
                gcsafe.PAUSES.append((t0, time.monotonic()))
            return real(self, ev)

        monkeypatch.setattr(GenericScheduler, "process", stalled)
        run_jobs("stalled", 6)
        srv.governor.sample_once()
        assert srv.governor.p99_ms() >= (100.0 if stall == "slow_host"
                                         else 0.0)
        assert srv.governor.backpressure() is (stall == "slow_host")
    finally:
        gcsafe.PAUSES.clear()
        srv.shutdown()


def test_snapshot_is_due_by_entries_or_by_wal_bytes(tmp_path, monkeypatch):
    assert ServerConfig().snapshot_every == 8192
    assert Persistence(str(tmp_path / "d")).snapshot_every == 8192
    assert Persistence.SNAPSHOT_WAL_BYTES == 1 << 30

    def boot(name, **cfg):
        return Server(ServerConfig(num_schedulers=0,
                                   data_dir=str(tmp_path / name), **cfg))

    # neither bound reached: no snapshot
    srv = boot("quiet")
    srv.start()
    for _ in range(12):
        srv.raft_apply("node_register", dict(node=mock.node()))
    srv.persistence.wait_idle()
    assert srv.persistence.stats["snapshots"] == 0
    srv.shutdown()

    # the byte bound alone: 12 node entries are a few KB each
    monkeypatch.setattr(Persistence, "SNAPSHOT_WAL_BYTES", 8 << 10)
    srv = boot("bytes")
    srv.start()
    for _ in range(12):
        srv.raft_apply("node_register", dict(node=mock.node()))
    srv.persistence.wait_idle()
    taken = srv.persistence.stats["snapshots"]
    srv.shutdown()
    assert 1 <= taken < 12
    assert os.path.exists(str(tmp_path / "bytes" / "state.snap"))
    again = boot("bytes")
    assert len(again.store.nodes()) == 12


def test_a_snapshot_the_cache_moved_past_finds_its_own_table():
    """Two workers race for the table cache: the one whose snapshot the
    cache has moved past gets the table that was current at its index
    (held for a few versions), not a private full build — 0.75 s at
    10k nodes under the cache's lock, 19-27 times a window of the
    service cell."""
    from nomad_tpu.ops.tables import NodeTable
    from nomad_tpu.state.store import StateStore
    s = StateStore()
    nodes = []
    for i in range(3):
        node = mock.node()
        node.name = f"recent-{i}"
        nodes.append(node)
        s.upsert_node(i + 1, node)

    def place(index, node):
        a = mock.alloc()
        a.node_id = node.id
        s.upsert_allocs(index, [a])
        return a

    mine = s.snapshot()                     # this worker's snapshot ...
    t_mine = mine.node_table()              # ... and its refresh
    a1 = place(50, nodes[0])
    theirs = s.snapshot()
    t_theirs = theirs.node_table()          # the other worker moves the cache
    assert t_theirs is not t_mine
    cache = s.table_cache
    before = dict(cache.stats)
    again = mine.node_table()               # Process(): the same snapshot
    assert again is t_mine
    assert cache.stats["recent_hits"] == before["recent_hits"] + 1
    i0 = again.id_to_idx[nodes[0].id]
    assert not any(x.id == a1.id for x in again.live_allocs[i0])
    # a snapshot nobody refreshed for still pays its own build, and
    # gets the state at ITS index
    place(60, nodes[1])
    between = s.snapshot()
    a3 = place(70, nodes[2])
    s.snapshot().node_table()
    t_between = between.node_table()
    assert cache.stats["recent_hits"] == before["recent_hits"] + 1
    i2 = t_between.id_to_idx[nodes[2].id]
    assert not any(x.id == a3.id for x in t_between.live_allocs[i2])
    # only the last few are held
    for k in range(cache.RECENT_TABLES + 1):
        place(80 + k, nodes[k % 3])
        s.snapshot().node_table()
    assert len(cache._recent) == cache.RECENT_TABLES
    assert isinstance(mine.node_table(), NodeTable)     # a private build
    assert cache.stats["recent_hits"] == before["recent_hits"] + 1
