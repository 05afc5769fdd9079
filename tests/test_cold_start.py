"""Columnar snapshot & cold-start recovery pipeline (ISSUE 8):
round-trip parity with the legacy object snapshot, batched WAL replay
equivalence, crash tolerance, off-thread snapshot consistency,
group-fsync equivalence, and the recovery invariants (warm columnar
alloc index, primed resident node table)."""

import json
import multiprocessing
import os
import random
import time

import msgpack
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.models import Allocation, Evaluation
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.persistence import Persistence, RaftLog
from nomad_tpu.state import StateStore
from nomad_tpu.state.columnar import decode_table, encode_table


def _canon(d) -> str:
    return json.dumps(d, sort_keys=True, default=str)


def _pack_cycle(data: dict) -> dict:
    """Exercise the real file framing: msgpack encode + decode."""
    return msgpack.unpackb(msgpack.packb(data, use_bin_type=True),
                           raw=False, strict_map_key=False)


def _seeded_store(rng: random.Random, n_nodes=8, n_jobs=4,
                  allocs_per_job=25) -> StateStore:
    """A store touching every dumped table: nodes, jobs (+versions),
    evals, allocs (varied statuses/transitions/deployment bits),
    deployments, namespaces, ACL policies+tokens, CSI volumes, service
    registrations, periodic launches, scheduler config."""
    from nomad_tpu.acl import AclPolicy, AclToken
    from nomad_tpu.models import SchedulerConfiguration
    from nomad_tpu.models.alloc import (AllocDeploymentStatus,
                                        DesiredTransition)
    from nomad_tpu.models.namespace import Namespace

    s = StateStore()
    idx = 10
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.name = f"cold-node-{i}"
        idx += 1
        s.upsert_node(idx, n)
        nodes.append(n)
    jobs = []
    for j in range(n_jobs):
        job = mock.job()
        job.id = f"cold-job-{j}"
        idx += 1
        s.upsert_job(idx, job)
        if rng.random() < 0.5:      # a second version for job_versions
            job2 = job.copy()
            job2.task_groups[0].tasks[0].env = {"V": str(j)}
            idx += 1
            s.upsert_job(idx, job2)
        jobs.append(job)
    d = mock.deployment()
    d.job_id = jobs[0].id
    idx += 1
    s.upsert_deployment(idx, d)
    statuses = ["pending", "running", "complete", "failed", "lost"]
    desireds = ["run", "stop", "evict"]
    allocs = []
    for j, job in enumerate(jobs):
        for i in range(allocs_per_job):
            a = mock.alloc()
            a.id = f"alloc-{j}-{i}"
            a.job_id = job.id
            a.job = job
            a.node_id = rng.choice(nodes).id
            a.name = f"{job.id}.web[{i}]"
            a.client_status = rng.choice(statuses)
            a.desired_status = rng.choice(desireds)
            if rng.random() < 0.3:
                a.desired_transition = DesiredTransition(migrate=True)
            if rng.random() < 0.3:
                a.deployment_id = d.id
                a.deployment_status = AllocDeploymentStatus(
                    healthy=rng.random() < 0.5)
            allocs.append(a)
    idx += 1
    s.upsert_allocs(idx, allocs)
    evals = []
    for j in range(10):
        e = mock.evaluation()
        e.job_id = rng.choice(jobs).id
        evals.append(e)
    idx += 1
    s.upsert_evals(idx, evals)
    idx += 1
    s.upsert_namespaces(idx, [Namespace(name="prod",
                                        description="prod ns")])
    idx += 1
    s.upsert_acl_policies(idx, [AclPolicy(
        name="dev", rules='namespace "default" { policy = "read" }')])
    idx += 1
    s.upsert_acl_tokens(idx, [AclToken(
        accessor_id="acc-1", secret_id="sec-1", name="t",
        type="client", policies=["dev"])])
    idx += 1
    s.upsert_periodic_launch(idx, "default", jobs[0].id, 123.5)
    idx += 1
    s.set_scheduler_config(idx, SchedulerConfiguration())
    return s


class TestColumnarRoundTrip:
    def test_randomized_parity_columnar_vs_legacy(self):
        """Columnar restore ≡ legacy restore ≡ the original dump, on
        the FULL store state (randomized content over every table)."""
        for seed in range(5):
            rng = random.Random(seed)
            s = _seeded_store(rng)
            legacy = s.dump()
            col = _pack_cycle(s.dump_columnar())
            s_col = StateStore()
            s_col.restore(col)
            s_leg = StateStore()
            s_leg.restore(_pack_cycle(legacy))
            assert _canon(s_col.dump()) == _canon(s_leg.dump()), \
                f"seed {seed}: columnar restore diverged from legacy"
            assert _canon(s_col.dump()) == _canon(legacy), \
                f"seed {seed}: round trip diverged from original"
            # re-dumping columnar from a columnar restore round-trips
            again = StateStore()
            again.restore(_pack_cycle(s_col.dump_columnar()))
            assert _canon(again.dump()) == _canon(legacy)

    def test_legacy_snapshot_upgrades_to_columnar(self, tmp_path):
        """Old→new migration: a legacy-format snapshot file restores
        into a columnar-writing server, whose next snapshot is format
        2 and restores identically."""
        rng = random.Random(99)
        s = _seeded_store(rng)
        legacy_dir = str(tmp_path / "legacy")
        p = Persistence(legacy_dir, columnar=False, background=False)
        p.snapshot(s)
        srv = Server(ServerConfig(num_schedulers=0,
                                  data_dir=legacy_dir,
                                  snapshot_background=False))
        try:
            assert srv.persistence.stats["restore_format"] == 1
            assert _canon(srv.store.dump()) == _canon(s.dump())
            srv.persistence.snapshot(srv.store)     # now columnar
        finally:
            srv.shutdown()
        srv2 = Server(ServerConfig(num_schedulers=0,
                                   data_dir=legacy_dir))
        try:
            assert srv2.persistence.stats["restore_format"] == 2
            assert _canon(srv2.store.dump()) == _canon(s.dump())
        finally:
            srv2.shutdown()

    def test_pool_sharing_and_empty_containers(self):
        """Shared flyweights stay shared through the codec; empty
        dict/list fields come back as FRESH containers per row (no
        cross-row aliasing of task_states)."""
        job = mock.job()
        res = mock.alloc().allocated_resources
        allocs = []
        for i in range(10):
            a = mock.alloc()
            a.id = f"fly-{i}"
            a.job = job
            a.allocated_resources = res
            a.task_states = {}
            allocs.append(a)
        dec = decode_table(Allocation, _pack_cycle(
            {"t": encode_table(allocs)})["t"])
        out = dec.objs
        assert len({id(o.job) for o in out}) == 1
        assert len({id(o.allocated_resources) for o in out}) == 1
        assert len({id(o.task_states) for o in out}) == len(out)
        out[0].task_states["web"] = "poison"
        assert out[1].task_states == {}

    def test_forward_compat_missing_field_defaults(self):
        """A snapshot written before a field existed restores with the
        dataclass default (factories called per row)."""
        evals = [mock.evaluation() for _ in range(3)]
        enc = _pack_cycle({"t": encode_table(evals)})["t"]
        dropped = enc["fields"].pop("status")
        assert dropped is not None
        out = decode_table(Evaluation, enc).objs
        assert all(o.status == Evaluation().status for o in out)


class TestNodeTableColdBuild:
    def test_build_from_columns_parity(self):
        """The vectorized cold build produces a table identical to
        build_all on the restored snapshot (usage, row lists, port
        bits, registry)."""
        from nomad_tpu.ops.tables import NodeTable
        for seed in range(3):
            rng = random.Random(1000 + seed)
            s = _seeded_store(rng, n_nodes=12, n_jobs=3,
                              allocs_per_job=40)
            s2 = StateStore()
            s2.restore(_pack_cycle(s.dump_columnar()))
            cold = s2.pop_cold_columns()
            assert cold is not None
            snap = s2.snapshot()
            ref = NodeTable.build_all(snap)
            got = NodeTable.build_from_columns(snap, cold)
            assert got.ids == ref.ids
            assert np.array_equal(got.base_used, ref.base_used)
            assert got._net_bits == ref._net_bits
            assert np.array_equal(got.free_ports, ref.free_ports)
            for a, b in zip(ref.live_allocs, got.live_allocs):
                assert [x.id for x in a] == [x.id for x in b]
            assert set(got.alloc_by_id) == set(ref.alloc_by_id)


class TestRecoveryInvariants:
    def test_no_rebuilds_after_restore(self, tmp_path):
        """After a cold boot from a columnar snapshot: the first
        columnar read per job pays ZERO dense index rebuilds, and the
        first node_table() read pays ZERO full NodeTable builds (the
        primed table serves it)."""
        rng = random.Random(7)
        s = _seeded_store(rng)
        data_dir = str(tmp_path / "inv")
        p = Persistence(data_dir, background=False)
        p.snapshot(s)
        srv = Server(ServerConfig(num_schedulers=0, data_dir=data_dir))
        try:
            snap = srv.store.snapshot()
            jobs = {(a.namespace, a.job_id)
                    for a in srv.store.allocs()}
            for ns, job_id in jobs:
                cols = snap.job_alloc_columns(ns, job_id)
                assert cols is not None
                assert cols.n == len(snap.allocs_by_job(ns, job_id))
            assert srv.store.alloc_index.stats["rebuilds"] == 0
            assert snap.node_table() is not None
            assert srv.store.table_cache.stats["full_builds"] == 0
            assert srv.store.table_cache.stats.get("primes") == 1
        finally:
            srv.shutdown()

    def test_bulk_load_keeps_index_warm(self):
        """bulk_load_allocs no longer invalidates the columnar index:
        a fresh job's chunked load installs+extends an entry, and the
        read after the load pays zero rebuilds and matches a detached
        dense build row for row."""
        s = StateStore()
        n = mock.node()
        s.upsert_node(11, n)
        job = mock.batch_job()
        s.upsert_job(12, job)
        tg = job.task_groups[0].name
        idx = 12
        for chunk in range(3):
            allocs = [Allocation(
                id=f"bl-{chunk}-{i}", namespace="default",
                job_id=job.id, task_group=tg,
                name=f"{job.id}.{tg}[{chunk * 50 + i}]",
                node_id=n.id, eval_id="bl-eval",
                client_status="running", desired_status="run")
                for i in range(50)]
            idx += 1
            s.bulk_load_allocs(idx, allocs)
        cols = s.snapshot().job_alloc_columns("default", job.id)
        assert cols is not None and cols.n == 150
        assert s.alloc_index.stats["rebuilds"] == 0
        from nomad_tpu.state.alloc_index import JobAllocColumns
        dense = JobAllocColumns.build(
            s.snapshot().allocs_by_job("default", job.id))
        assert sorted(cols.ids) == sorted(dense.ids)
        # a delta after the bulk load still applies on top
        a2 = s.snapshot().allocs_by_job("default", job.id)[0]
        from dataclasses import replace
        idx += 1
        s.update_allocs_from_client(idx, [replace(
            a2, client_status="failed")])
        cols = s.snapshot().job_alloc_columns("default", job.id)
        r = cols.row_of[a2.id]
        assert cols.client[r] == 3      # CLIENT_FAILED_CODE
        assert s.alloc_index.stats["rebuilds"] == 0


def _replay_stream(server, jobs):
    """A WAL-shaped entry stream with deliberate same-job runs (forces
    batch flush partitioning) and interleaved types."""
    for k in range(6):
        for job in jobs:
            ev = mock.evaluation()
            ev.job_id = job.id
            server.raft_apply("eval_update", dict(evals=[ev]))
        # same-job pair back to back: the batcher must flush between
        ev1, ev2 = mock.evaluation(), mock.evaluation()
        ev1.job_id = ev2.job_id = jobs[0].id
        server.raft_apply("eval_update", dict(evals=[ev1]))
        server.raft_apply("eval_update", dict(evals=[ev2]))
        server.raft_apply("node_register", dict(node=mock.node()))


class TestBatchedWalReplay:
    def test_batched_equals_sequential(self, tmp_path, monkeypatch):
        """Replaying the same WAL with batching on vs off yields
        byte-identical store state (randomized streams incl. same-job
        conflict runs and alloc client updates)."""
        data_dir = str(tmp_path / "replay")
        srv = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                  snapshot_every=10_000))
        jobs = []
        for j in range(4):
            job = mock.batch_job()
            job.id = f"wal-job-{j}"
            srv.raft_apply("job_register", dict(job=job))
            jobs.append(job)
        node = mock.node()
        srv.raft_apply("node_register", dict(node=node))
        allocs = []
        for j, job in enumerate(jobs):
            a = mock.alloc()
            a.id = f"wal-alloc-{j}"
            a.job_id = job.id
            a.node_id = node.id
            allocs.append(a)
            srv.raft_apply("plan_results", dict(
                allocs_stopped=[], allocs_placed=[a],
                allocs_preempted=[]))
        _replay_stream(srv, jobs)
        # alloc client updates, including a same-job run
        from dataclasses import replace
        for j, a in enumerate(allocs):
            srv.raft_apply("alloc_client_update", dict(
                allocs=[replace(a, client_status="running")], evals=[]))
        srv.raft_apply("alloc_client_update", dict(
            allocs=[replace(allocs[0], client_status="complete")],
            evals=[]))
        srv.raft_apply("alloc_client_update", dict(
            allocs=[replace(allocs[0], client_status="failed")],
            evals=[]))
        srv.shutdown()
        # no snapshot was written (snapshot_every huge): everything
        # replays from the WAL on both boots
        assert not os.path.exists(os.path.join(data_dir, "state.snap"))

        monkeypatch.setenv("NOMAD_TPU_WAL_REPLAY_BATCH", "0")
        seq = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                  snapshot_every=10_000))
        seq_dump = seq.store.dump()
        seq_index = seq._raft_index
        seq.shutdown()
        monkeypatch.setenv("NOMAD_TPU_WAL_REPLAY_BATCH", "1")
        bat = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                  snapshot_every=10_000))
        try:
            assert _canon(bat.store.dump()) == _canon(seq_dump)
            assert bat._raft_index == seq_index
        finally:
            bat.shutdown()


class TestBackgroundSnapshot:
    def test_applier_commits_while_snapshot_in_flight(self, tmp_path):
        """The acceptance test: with serialization gated open on an
        event, raft applies keep committing; entries applied during
        the in-flight snapshot survive the next restart (WAL prefix
        truncation keeps the tail)."""
        data_dir = str(tmp_path / "bg")
        srv = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                  snapshot_every=5))
        # the dump runs in a forked child (PR 33): events it shares
        fork = multiprocessing.get_context("fork")
        gate = fork.Event()
        entered = fork.Event()
        from nomad_tpu.state.store import StateSnapshot
        real_dump = StateSnapshot.dump_columnar

        def gated_dump(self):
            entered.set()
            assert gate.wait(10), "snapshot writer never released"
            return real_dump(self)

        StateSnapshot.dump_columnar = gated_dump
        try:
            for _ in range(5):      # crosses snapshot_every => trigger
                srv.raft_apply("node_register", dict(node=mock.node()))
            assert entered.wait(10), "background snapshot never started"
            # the applier must NOT be blocked by the in-flight writer
            t0 = time.perf_counter()
            for _ in range(7):
                srv.raft_apply("node_register", dict(node=mock.node()))
            applied_during_flight = time.perf_counter() - t0
            assert len(srv.store.nodes()) == 12
            assert applied_during_flight < 5.0
        finally:
            gate.set()
            StateSnapshot.dump_columnar = real_dump
        srv.persistence.wait_idle()
        assert srv.persistence.stats["snapshots"] >= 1
        srv.shutdown()
        srv2 = Server(ServerConfig(num_schedulers=0, data_dir=data_dir))
        try:
            # snapshot covered 5 nodes; the 7 applied mid-flight came
            # back off the preserved WAL tail
            assert len(srv2.store.nodes()) == 12
        finally:
            srv2.shutdown()

    def test_stale_capture_never_replaces_newer_snapshot(self, tmp_path):
        """Racing snapshot writers: the one holding the OLDER capture
        must neither replace the newer snapshot file nor re-truncate
        the WAL at a stale offset (absolute marks + the monotone
        publish guard)."""
        s = StateStore()
        p = Persistence(str(tmp_path / "race"), background=False)
        p.log.open()
        s.upsert_node(11, mock.node())
        snap_old = s.snapshot()
        mark_old = p.log.size()
        p.log.append(12, "noop", {})
        s.upsert_node(12, mock.node())
        snap_new = s.snapshot()
        mark_new = p.log.size()
        assert mark_new > mark_old
        p._write_snapshot(snap_new, None, mark_new)  # newer lands first
        p._write_snapshot(snap_old, None, mark_old)  # stale: must no-op
        p.log.close()
        s2 = StateStore()
        p2 = Persistence(str(tmp_path / "race"))
        _highest, entries = p2.restore_into(s2)
        assert len(s2.nodes()) == 2     # the newer snapshot survived
        assert entries == []            # and the WAL was not re-cut

    def test_crash_mid_snapshot_recovers(self, tmp_path):
        """A leftover state.snap.tmp from a crash mid-write is ignored
        and cleaned; the prior snapshot + WAL restore cleanly."""
        data_dir = str(tmp_path / "crash")
        srv = Server(ServerConfig(num_schedulers=0, data_dir=data_dir,
                                  snapshot_background=False))
        for _ in range(4):
            srv.raft_apply("node_register", dict(node=mock.node()))
        srv.persistence.snapshot(srv.store)
        srv.raft_apply("node_register", dict(node=mock.node()))
        srv.shutdown()
        tmp = os.path.join(data_dir, "state.snap.tmp")
        with open(tmp, "wb") as f:
            f.write(b"\x00garbage half-written snapshot")
        srv2 = Server(ServerConfig(num_schedulers=0,
                                   data_dir=data_dir))
        try:
            assert len(srv2.store.nodes()) == 5
            assert not os.path.exists(tmp)
        finally:
            srv2.shutdown()


class TestGroupFsync:
    def _write_wal(self, tmp_path, name, group, entries, monkeypatch):
        """Record one committed BATCH of entries (the raft FSM batch
        shape — apply_replicated records per entry, the batch boundary
        calls commit_barrier once) and count fsyncs."""
        import nomad_tpu.server.persistence as pmod
        count = [0]
        real_fsync = os.fsync

        def counting_fsync(fd):
            count[0] += 1
            return real_fsync(fd)

        monkeypatch.setattr(pmod.os, "fsync", counting_fsync)
        try:
            p = Persistence(str(tmp_path / name), wal_fsync=True,
                            wal_group_fsync=group)
            p.log.open()
            for index, msg_type, payload in entries:
                p.record(index, msg_type, payload)
            p.commit_barrier()
            p.log.close()
        finally:
            monkeypatch.setattr(pmod.os, "fsync", real_fsync)
        return str(tmp_path / name), count[0]

    def test_group_fsync_equivalent_state_fewer_syncs(self, tmp_path,
                                                      monkeypatch):
        """Group-fsync ≡ per-entry fsync on replayed store state; the
        group path pays ONE fsync per committed batch instead of one
        per entry."""
        nodes = [mock.node() for _ in range(10)]
        entries = [(100 + i, "node_register", dict(node=n))
                   for i, n in enumerate(nodes)]
        d_entry, n_entry = self._write_wal(tmp_path, "entry", False,
                                           entries, monkeypatch)
        d_group, n_group = self._write_wal(tmp_path, "group", True,
                                           entries, monkeypatch)
        assert n_entry == 10        # one fsync per record
        assert n_group == 1         # one fsync per committed batch

        def replay_into_store(data_dir):
            s = StateStore()
            for idx, mt, payload, _ts in RaftLog(
                    os.path.join(data_dir, "raft.log")).replay():
                s.upsert_node(idx, payload["node"])
            return s

        s1 = replay_into_store(d_entry)
        s2 = replay_into_store(d_group)
        assert _canon(s1.dump()) == _canon(s2.dump())
        assert len(s1.nodes()) == 10


class TestRestoreIntoContract:
    def test_returns_tuple(self, tmp_path):
        """The documented contract matches the implementation (ISSUE 8
        satellite: the docstring used to claim a bare int)."""
        p = Persistence(str(tmp_path / "c"))
        out = p.restore_into(StateStore())
        assert isinstance(out, tuple) and len(out) == 2
        highest, entries = out
        assert highest == 0 and entries == []
        assert "(highest, entries)" in Persistence.restore_into.__doc__
