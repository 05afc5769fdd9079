"""Checkpoint/resume tests: WAL replay, snapshot restore, crash
tolerance (reference patterns: nomad/fsm_test.go snapshot round trips)."""

import json
import logging
import multiprocessing
import os
import signal
import threading
import time

import msgpack
import pytest

from nomad_tpu import mock
from nomad_tpu.client import Client, ClientConfig
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server import persistence as persistence_mod
from nomad_tpu.server.persistence import Persistence, RaftLog
from nomad_tpu.state import StateStore
from nomad_tpu.state.store import StateSnapshot


def _wait_for(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def test_store_dump_restore_roundtrip():
    s = StateStore()
    n = mock.node()
    s.upsert_node(11, n)
    j = mock.job()
    s.upsert_job(12, j)
    a = mock.alloc()
    a.node_id = n.id
    a.job_id = j.id
    s.upsert_allocs(13, [a])
    e = mock.evaluation()
    s.upsert_evals(14, [e])
    d = mock.deployment()
    s.upsert_deployment(15, d)

    data = s.dump()
    s2 = StateStore()
    s2.restore(data)
    assert s2.node_by_id(n.id).name == n.name
    assert s2.job_by_id("default", j.id).version == 0
    assert s2.alloc_by_id(a.id).job is not None
    assert len(s2.allocs_by_node(n.id)) == 1
    assert len(s2.allocs_by_job("default", j.id)) == 1
    assert s2.eval_by_id(e.id) is not None
    assert s2.deployment_by_id(d.id) is not None
    assert s2.latest_index() == s.latest_index()
    assert s2.job_summary("default", j.id) is not None


def test_wal_replay_and_torn_write(tmp_path):
    log = RaftLog(str(tmp_path / "raft.log"))
    log.open()
    log.append(1, "node_register", {"node": mock.node()})
    log.append(2, "eval_update", {"evals": [mock.evaluation()]})
    log.close()
    # simulate a torn final frame
    with open(str(tmp_path / "raft.log"), "ab") as f:
        f.write(b"\xff\x00\x00\x00partial")
    entries = log.replay()
    assert len(entries) == 2
    assert entries[0][1] == "node_register"
    assert entries[0][2]["node"].name == "foobar"
    assert entries[1][2]["evals"][0].status == "pending"


def test_server_restart_recovers_state(tmp_path):
    data_dir = str(tmp_path / "data")
    server = Server(ServerConfig(num_schedulers=2, data_dir=data_dir,
                                 heartbeat_ttl_s=60.0))
    server.start()
    client = Client(server, ClientConfig(node_name="persist-client"))
    client.start()
    job = mock.batch_job()
    job.type = "service"
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].config = {"run_for": "60s"}
    job.canonicalize()
    server.register_job(job)
    assert _wait_for(lambda: len(
        server.store.allocs_by_job("default", job.id)) == 2)
    node_id = client.node.id
    client.shutdown()
    server.shutdown()

    # "restart" the server from the same data dir
    server2 = Server(ServerConfig(num_schedulers=2, data_dir=data_dir,
                                  heartbeat_ttl_s=60.0))
    assert server2.store.job_by_id("default", job.id) is not None
    assert len(server2.store.allocs_by_job("default", job.id)) == 2
    assert server2.store.node_by_id(node_id) is not None
    assert server2._raft_index >= server.store.latest_index()
    server2.start()
    server2.shutdown()


def test_snapshot_truncates_wal(tmp_path):
    data_dir = str(tmp_path / "snap")
    server = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                 snapshot_every=5))
    server.start()
    for i in range(12):
        server.raft_apply("node_register", dict(node=mock.node()))
    server.shutdown()
    # WAL should have been truncated at least twice; snapshot exists
    assert os.path.exists(os.path.join(data_dir, "state.snap"))
    wal_entries = RaftLog(os.path.join(data_dir, "raft.log")).replay()
    assert len(wal_entries) < 12

    server2 = Server(ServerConfig(num_schedulers=0, data_dir=data_dir))
    assert len(server2.store.nodes()) == 12


def test_blocked_eval_survives_restart(tmp_path):
    data_dir = str(tmp_path / "blocked")
    server = Server(ServerConfig(num_schedulers=2, data_dir=data_dir,
                                 heartbeat_ttl_s=60.0))
    server.start()
    client = Client(server, ClientConfig(node_name="c1"))
    client.start()
    job = mock.batch_job()
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].resources.cpu = 9000   # cannot place
    server.register_job(job)
    assert _wait_for(lambda: server.blocked_evals.blocked_count() == 1)
    client.shutdown()
    server.shutdown()

    server2 = Server(ServerConfig(num_schedulers=2, data_dir=data_dir,
                                  heartbeat_ttl_s=60.0))
    server2.start()   # restore_evals re-blocks it
    assert server2.blocked_evals.blocked_count() == 1
    # a big node joining unblocks and places
    big = Client(server2, ClientConfig(node_name="big", cpu_shares=16000))
    big.start()
    try:
        assert _wait_for(lambda: len(
            server2.store.allocs_by_job("default", job.id)) == 1, timeout=15)
    finally:
        big.shutdown()
        server2.shutdown()


# -- the background snapshot is written by a forked child (PR 33) -------
# Counts and bytes, no wall-clock gate. A child that hangs is killed
# after CHILD_TIMEOUT_S by the `forks` fixture, so it fails its own test
# (the writer thread then reads a killed child) and not the run.

CHILD_TIMEOUT_S = 60.0
FORK = multiprocessing.get_context("fork")      # events a child shares


def _is_my_child(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/status") as f:
            return f"PPid:\t{os.getpid()}\n" in f.read()
    except OSError:
        return False


@pytest.fixture
def forks(monkeypatch):
    """The pids os.fork handed this process during the test."""
    pids = []
    real_fork = os.fork

    def fork():
        pid = real_fork()
        if pid:
            pids.append(pid)
        return pid

    def reap():
        for pid in pids:
            if _is_my_child(pid):
                os.kill(pid, signal.SIGKILL)

    monkeypatch.setattr(os, "fork", fork)
    timer = threading.Timer(CHILD_TIMEOUT_S, reap)
    timer.daemon = True
    timer.start()
    yield pids
    timer.cancel()
    reap()


def _canon(d) -> str:
    return json.dumps(d, sort_keys=True, default=str)


def _filled_store(nodes: int = 6) -> StateStore:
    s = StateStore()
    job = mock.job()
    s.upsert_job(1, job)
    for i in range(nodes):
        n = mock.node()
        s.upsert_node(10 + 2 * i, n)
        a = mock.alloc()
        a.node_id, a.job_id = n.id, job.id
        s.upsert_allocs(11 + 2 * i, [a])
    s.upsert_evals(100, [mock.evaluation()])
    s.upsert_deployment(101, mock.deployment())
    return s


def _persistence(path, **kw) -> Persistence:
    p = Persistence(str(path), **kw)
    p.log.open()
    return p


def _register(p: Persistence, s: StateStore, index: int) -> None:
    """One node through the WAL and into the store, as the FSM does."""
    n = mock.node()
    p.record(index, "node_register", {"node": n})
    s.upsert_node(index, n)


def _idle(p: Persistence) -> None:
    p.wait_idle(CHILD_TIMEOUT_S + 10.0)
    t = p._snap_thread
    assert t is None or not t.is_alive(), "snapshot writer never ended"


def _read(path) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _on_disk(path, into: StateStore = None):
    """(highest index of the snapshot, the WAL tail's entries)."""
    p = Persistence(str(path))
    highest, entries = p.restore_into(StateStore() if into is None
                                      else into)
    p.log.close()
    return highest, entries


def _restored(path) -> StateStore:
    s = StateStore()
    for index, msg_type, payload, _ts in _on_disk(path, s)[1]:
        assert msg_type == "node_register"
        s.upsert_node(index, payload["node"])
    return s


def _pids_of_dump(monkeypatch, path) -> None:
    """dump_columnar appends the pid it runs in to `path`."""
    real = StateSnapshot.dump_columnar

    def dump(self):
        with open(path, "a") as f:
            f.write(f"{os.getpid()}\n")
        return real(self)

    monkeypatch.setattr(StateSnapshot, "dump_columnar", dump)


def test_child_written_snapshot_is_the_inline_ones_bytes(tmp_path, forks):
    s = _filled_store()
    extra = {"time_table": [[1, 2.0]]}
    child = _persistence(tmp_path / "child")
    inline = _persistence(tmp_path / "inline", background=False)
    for p in (child, inline):
        p.extra_provider = lambda: extra
    assert child.trigger_snapshot(s) is not None    # its writer thread
    assert inline.trigger_snapshot(s) is None       # written on the spot
    _idle(child)
    assert len(forks) == 1
    assert child.stats["snapshot_children"] == 1
    assert inline.stats["snapshot_children"] == 0
    assert inline.stats["snapshot_inline"] == 0     # no fork was wanted
    blob = _read(child.snapshot_path)
    assert blob == _read(inline.snapshot_path)
    assert child.stats["last_snapshot_format"] == 2
    assert child.stats["last_snapshot_child_s"] > 0.0
    assert child.stats["last_snapshot_fork_s"] > 0.0
    for p in (child, inline):
        p.log.close()
        assert _canon(_restored(p.data_dir).dump()) == _canon(s.dump())
        assert _on_disk(p.data_dir)[0] == s.latest_index()


@pytest.mark.parametrize("columnar", [True, False])
def test_both_formats_leave_the_process(tmp_path, forks, columnar):
    s = _filled_store()
    p = _persistence(tmp_path / "d", columnar=columnar)
    p.trigger_snapshot(s)
    _idle(p)
    p.log.close()
    assert p.stats["snapshot_children"] == 1
    assert p.stats["last_snapshot_format"] == (2 if columnar else 1)
    assert _canon(_restored(p.data_dir).dump()) == _canon(s.dump())


def test_entries_applied_while_the_child_writes_stay_in_the_tail(
        tmp_path, monkeypatch, forks):
    entered, gate = FORK.Event(), FORK.Event()
    real = StateSnapshot.dump_columnar

    def gated(self):
        entered.set()
        assert gate.wait(CHILD_TIMEOUT_S), "the test never opened the gate"
        return real(self)

    monkeypatch.setattr(StateSnapshot, "dump_columnar", gated)
    s = StateStore()
    p = _persistence(tmp_path / "d")
    try:
        for index in range(1, 6):
            _register(p, s, index)
        p.trigger_snapshot(s)
        assert entered.wait(CHILD_TIMEOUT_S), "the child never dumped"
        for index in range(6, 10):          # the applier goes on
            _register(p, s, index)
        assert not os.path.exists(p.snapshot_path)      # not published
    finally:
        gate.set()
    _idle(p)
    p.log.close()
    assert p.stats["snapshots"] == 1 and p.stats["snapshot_errors"] == 0
    snap = msgpack.unpackb(_read(p.snapshot_path), raw=False,
                           strict_map_key=False)
    assert snap["columnar"]["nodes"]["n"] == 5
    highest, entries = _on_disk(p.data_dir)
    assert highest == 5
    assert [e[0] for e in entries] == [6, 7, 8, 9]
    assert _canon(_restored(p.data_dir).dump()) == _canon(s.dump())


def test_the_dump_runs_in_a_child_or_inline_for_want_of_a_fork(
        tmp_path, monkeypatch, forks):
    pids = tmp_path / "pids"
    _pids_of_dump(monkeypatch, pids)
    s = _filled_store()
    child = _persistence(tmp_path / "child")
    child.trigger_snapshot(s)
    _idle(child)
    assert child.stats["snapshot_children"] == 1
    assert child.stats["snapshot_inline"] == 0
    (pid,) = pids.read_text().split()
    assert int(pid) == forks[0] != os.getpid()

    def no_fork():
        raise OSError(12, "Cannot allocate memory")

    monkeypatch.setattr(os, "fork", no_fork)
    pids.write_text("")
    inline = _persistence(tmp_path / "inline")
    inline.trigger_snapshot(s)
    _idle(inline)
    assert inline.stats["snapshot_children"] == 0
    assert inline.stats["snapshot_inline"] == 1
    assert inline.stats["background_snapshots"] == 1
    assert inline.stats["snapshot_errors"] == 0
    assert pids.read_text().split() == [str(os.getpid())]
    assert _read(inline.snapshot_path) == _read(child.snapshot_path)

    monkeypatch.delattr(os, "fork")         # a platform without one
    bare = _persistence(tmp_path / "bare")
    bare.trigger_snapshot(s)
    _idle(bare)
    assert bare.stats["snapshot_inline"] == 1
    assert _read(bare.snapshot_path) == _read(child.snapshot_path)
    for p in (child, inline, bare):
        p.log.close()


@pytest.mark.parametrize("fate", ["raises", "killed", "exits"])
def test_a_failed_child_publishes_nothing_and_the_next_one_does(
        tmp_path, monkeypatch, forks, caplog, fate):
    entered = FORK.Event()
    real = StateSnapshot.dump_columnar
    me = os.getpid()

    def doomed(self):
        if os.getpid() == me:
            return real(self)               # the first, inline snapshot
        entered.set()
        if fate == "raises":
            raise RuntimeError("the dump fell over")
        if fate == "exits":
            os._exit(7)
        time.sleep(CHILD_TIMEOUT_S)         # killed: waits for it

    s = StateStore()
    p = _persistence(tmp_path / "d")
    for index in range(1, 4):
        _register(p, s, index)
    p.snapshot(s)                           # inline: a state.snap to keep
    for index in range(4, 7):
        _register(p, s, index)
    before = _read(p.snapshot_path), _read(p.log.path)
    monkeypatch.setattr(StateSnapshot, "dump_columnar", doomed)
    with caplog.at_level(logging.ERROR, logger="nomad_tpu.persistence"):
        p.trigger_snapshot(s)
        assert entered.wait(CHILD_TIMEOUT_S), "the child never dumped"
        if fate == "killed":
            # the child can say it entered before the writer thread,
            # back from fork(), has noted its pid
            deadline = time.monotonic() + CHILD_TIMEOUT_S
            while not forks and time.monotonic() < deadline:
                time.sleep(0.001)
            os.kill(forks[-1], signal.SIGKILL)
        _idle(p)
    assert p.stats["snapshot_errors"] == 1
    assert p.stats["snapshots"] == 1 and p.stats["snapshot_children"] == 0
    assert (_read(p.snapshot_path), _read(p.log.path)) == before
    assert not os.path.exists(p.snapshot_path + ".tmp")
    said = caplog.text
    assert "snapshot write failed" in said
    assert {"raises": "the dump fell over", "killed": "status -9",
            "exits": "status 7"}[fate] in said

    monkeypatch.setattr(StateSnapshot, "dump_columnar", real)
    p.trigger_snapshot(s)                   # the next threshold retries
    _idle(p)
    p.log.close()
    assert p.stats["snapshots"] == 2 and p.stats["snapshot_children"] == 1
    assert p.stats["snapshot_errors"] == 1
    highest, entries = _on_disk(p.data_dir)
    assert highest == 6 and entries == []


def test_five_children_beside_a_thread_that_keeps_dispatching(
        tmp_path, forks):
    import jax
    import jax.numpy as jnp
    step = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.arange(64.0 * 64.0).reshape(64, 64) / 4096.0
    want = float(step(x))
    stop, state = threading.Event(), {"n": 0, "error": None}

    def dispatch():
        try:
            while not stop.is_set():
                assert float(step(x)) == want
                state["n"] += 1
        except BaseException as e:          # noqa: BLE001 — reported below
            state["error"] = e

    worker = threading.Thread(target=dispatch, daemon=True)
    worker.start()
    s = StateStore()
    p = _persistence(tmp_path / "d")
    try:
        for round_ in range(5):
            _register(p, s, round_ + 1)
            p.trigger_snapshot(s)
            _idle(p)
        done = state["n"]
        deadline = time.time() + CHILD_TIMEOUT_S
        while state["n"] <= done and state["error"] is None \
                and time.time() < deadline:
            time.sleep(0.01)                # the thread's NEXT dispatch
    finally:
        stop.set()
        worker.join(CHILD_TIMEOUT_S)
        p.log.close()
    assert state["error"] is None
    assert state["n"] > done
    assert len(forks) == 5
    assert p.stats["snapshot_children"] == 5
    assert p.stats["snapshots"] == 5 and p.stats["snapshot_errors"] == 0
    assert _on_disk(p.data_dir)[0] == 5


def test_the_span_says_what_the_fork_and_the_tail_cost(tmp_path, forks):
    from nomad_tpu.utils import stages
    seen = []
    s = StateStore()
    p = _persistence(tmp_path / "d")
    for index in range(1, 4):
        _register(p, s, index)
    stages.set_trace_hook(lambda st, sec, attrs: seen.append((st, attrs)))
    try:
        p.trigger_snapshot(s)
        _register(p, s, 4)
        tail = p.log.size()
        _idle(p)
    finally:
        stages.set_trace_hook(None)
        p.log.close()
    (attrs,) = [a for st, a in seen if st == "snapshot_write"]
    assert set(attrs) == {"entries", "bytes", "fork_ms", "child_s",
                          "tail_bytes", "locked_tail_bytes"}
    assert attrs["entries"] == 3
    assert attrs["bytes"] == os.path.getsize(p.snapshot_path)
    assert attrs["fork_ms"] > 0.0 and attrs["child_s"] > 0.0
    assert attrs["tail_bytes"] + attrs["locked_tail_bytes"] \
        == os.path.getsize(p.log.path) > 0
    assert p.log.size() == tail             # marks stay absolute


# -- RaftLog.truncate_prefix copies the tail outside the log's lock -----

def _log_with(tmp_path, entries: int) -> RaftLog:
    log = RaftLog(str(tmp_path / "raft.log"))
    log.open()
    for index in range(1, entries + 1):
        log.append(index, "noop", {"pad": "x" * 200})
    return log


def _indexes(log: RaftLog) -> list:
    return [e[0] for e in RaftLog(log.path).replay()]


def test_truncate_copies_under_the_lock_only_what_the_copy_missed(
        tmp_path, monkeypatch):
    log = _log_with(tmp_path, 4)
    mark = log.size()
    for index in range(5, 9):
        log.append(index, "noop", {"pad": "y" * 200})
    kept = log.size() - mark
    real_copy, calls = persistence_mod._copy, []

    def copy(src, dst, n):
        done = real_copy(src, dst, n)
        calls.append((n, done, log._l.locked()))
        if len(calls) == 1:                 # appended during the copy
            for index in range(9, 12):
                log.append(index, "noop", {"pad": "z" * 200})
        return done

    monkeypatch.setattr(persistence_mod, "_copy", copy)
    end = log.size()
    outside, locked = log.truncate_prefix(mark)
    raced = log.size() - end
    assert (outside, locked) == (kept, raced) and raced > 0
    assert calls == [(kept, kept, False), (None, raced, True)]
    log.append(12, "noop", {})
    log.close()
    assert _indexes(log) == list(range(5, 13))
    assert os.path.getsize(log.path) == log.size() - mark
    assert not os.path.exists(log.path + ".tmp")


def test_appends_racing_the_truncation_all_survive_in_order(tmp_path):
    log = _log_with(tmp_path, 300)
    mark = log.size()
    stop, last = threading.Event(), [300]

    def appender():
        while not stop.is_set():
            last[0] += 1
            log.append(last[0], "noop", {"pad": "r" * 200})

    t = threading.Thread(target=appender, daemon=True)
    t.start()
    try:
        marks = []
        for _ in range(6):
            while last[0] < 320 + 40 * len(marks):
                time.sleep(0.001)
            marks.append(log.size())
            log.truncate_prefix(marks[-1] if len(marks) % 2 else mark)
    finally:
        stop.set()
        t.join(30)
    log.close()
    got = _indexes(log)
    assert got and got == list(range(got[0], last[0] + 1))
    assert got[0] > 300
    assert os.path.getsize(log.path) == log.size() - log._trunc_shift


def test_a_stale_mark_truncates_nothing(tmp_path):
    log = _log_with(tmp_path, 6)
    old = log.size()
    log.append(7, "noop", {})
    new = log.size()
    assert log.truncate_prefix(new) == (0, 0)   # nothing behind the mark
    log.append(8, "noop", {})
    before = _read(log.path)
    for stale in (old, new, 0, -5):
        assert log.truncate_prefix(stale) == (0, 0)
        assert _read(log.path) == before
    assert log.size() == new + len(before)
    log.close()
    assert _indexes(log) == [8]


@pytest.mark.parametrize("dies_at", [1, 2])
def test_a_crash_between_the_fsyncs_leaves_the_old_log_whole(
        tmp_path, monkeypatch, dies_at):
    log = _log_with(tmp_path, 5)
    mark = log.size()
    log.append(6, "noop", {})
    before, size = _read(log.path), log.size()
    real_fsync, calls = os.fsync, []

    def fsync(fd):
        calls.append(fd)
        if len(calls) == dies_at:
            raise OSError(5, "Input/output error")
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", fsync)
    with pytest.raises(OSError):
        log.truncate_prefix(mark)
    monkeypatch.setattr(os, "fsync", real_fsync)
    assert _read(log.path) == before and log.size() == size
    assert not os.path.exists(log.path + ".tmp")
    assert not log._l.locked() and not log._trunc_l.locked()
    log.append(7, "noop", {})               # still the log
    assert _indexes(log) == [1, 2, 3, 4, 5, 6, 7]
    assert log.truncate_prefix(mark) == (log.size() - mark, 0)
    log.close()
    assert _indexes(log) == [6, 7]
