"""Persistence.stats carries the snapshot trigger's two quantities
(asked for by ISSUE 31 and 33, brought by ISSUE 36): the WAL's absolute
stream position, and the bytes and entries it has taken since the last
snapshot was triggered. The benchmark's Agent.counters() reads the
private attributes in their place until a benchmark PR re-points it;
these tests hold the keys to those attributes."""

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.utils import stages

WAL_KEYS = ("wal_bytes", "wal_bytes_since_snapshot",
            "wal_entries_since_snapshot")


def _private(p):
    size = p.log.size()
    return {"wal_bytes": size,
            "wal_bytes_since_snapshot": size - p._bytes_at_snapshot,
            "wal_entries_since_snapshot": p._since_snapshot}


def _register(srv, i):
    node = mock.node()
    node.name = f"stats-n{i}"
    node.compute_class()
    srv.register_node(node)


def test_wal_position_is_in_stats_after_every_entry_and_across_a_trigger(
        tmp_path):
    cfg = dict(num_schedulers=0, data_dir=str(tmp_path),
               snapshot_every=4, snapshot_background=False)
    srv = Server(ServerConfig(**cfg))
    try:
        p = srv.persistence
        assert {k: p.stats[k] for k in WAL_KEYS} == dict.fromkeys(
            WAL_KEYS, 0)
        seen = []
        for i in range(6):
            _register(srv, i)
            got = {k: p.stats[k] for k in WAL_KEYS}
            assert got == _private(p)
            seen.append(got)
        # the stream position only grows; the fourth entry triggered a
        # snapshot, which starts the other two from nothing
        assert [g["wal_bytes"] for g in seen] == sorted(
            g["wal_bytes"] for g in seen)
        assert [g["wal_entries_since_snapshot"] for g in seen] \
            == [1, 2, 3, 0, 1, 2]
        assert seen[3]["wal_bytes_since_snapshot"] == 0
        assert 0 < seen[5]["wal_bytes_since_snapshot"] < seen[5]["wal_bytes"]
        assert p.stats["snapshots"] == 1
        tail = seen[5]["wal_bytes"] - seen[3]["wal_bytes"]
    finally:
        srv.shutdown()
    # a restart reads them off the log it finds: the tail the last
    # snapshot left (shutdown wrote one more: nothing)
    again = Server(ServerConfig(**cfg))
    try:
        p = again.persistence
        assert {k: p.stats[k] for k in WAL_KEYS} == _private(p)
        assert p.stats["wal_bytes"] <= tail
    finally:
        again.shutdown()


@pytest.mark.parametrize("fsync, group, synced", [
    (False, True, [False]),             # the default: flush, no fsync
    (True, True, [False, True]),        # one group fsync at the barrier
    (True, False, [True]),              # every entry pays its own
])
def test_wal_write_says_whether_an_fsync_ran(tmp_path, fsync, group,
                                             synced):
    """wal_write is the plan entry's write + flush, and the commit
    barrier that covers it where that pays the fsync (attr synced)."""
    srv = Server(ServerConfig(num_schedulers=0, data_dir=str(tmp_path),
                              wal_fsync=fsync, wal_group_fsync=group))
    heard = []
    prev = stages._trace_hook, stages._trace_on
    stages.set_trace_hook(lambda st, s, a=None: heard.append((st, a)))
    try:
        _register(srv, 0)               # no plan: names none of them
        assert not {st for st, _a in heard} & {
            "raft_lock_wait", "wal_encode", "wal_write", "fsm_apply",
            "event_publish"}
        del heard[:]
        srv.raft_apply("plan_results", dict(
            allocs_stopped=[], allocs_placed=[], allocs_preempted=[],
            deployment=None, deployment_updates=[], evals=[]))
    finally:
        stages.set_trace_hook(*prev)
        srv.shutdown()
    names = [st for st, _a in heard if not st.endswith("_cpu")]
    assert names == ["raft_lock_wait", "wal_encode", "wal_write",
                     "fsm_apply"] + ["wal_write"] * (len(synced) - 1) \
        + ["event_publish"]
    assert [a["synced"] for st, a in heard if st == "wal_write"] == synced
    apply = dict(heard)["fsm_apply"]
    assert apply == {"kind": "plan_results", "cpu_ms": apply["cpu_ms"]}
    assert dict(heard)["event_publish"] == {"events": 0}
    # of the five the store's transaction alone reads the CPU clock
    assert [st for st, _a in heard if st.endswith("_cpu")] \
        == ["fsm_apply_cpu"]
