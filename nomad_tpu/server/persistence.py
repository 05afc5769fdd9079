"""Server persistence: write-ahead log + state snapshots.

Reference semantics: the Raft log (raft-boltdb) + FSM snapshots
(nomad/fsm.go Snapshot:1360 persists every table, Restore:1374 rebuilds
memdb; nomad/server.go:1214 setupRaft). Single-node round 1: the log is
an append-only file of msgpack-framed (index, type, payload) entries
written BEFORE the FSM applies them (WAL discipline); snapshots dump the
whole store and truncate the log. Restore = load snapshot + replay the
log tail. The encode/decode schema per apply type lives here so a
replicated log can reuse it unchanged.
"""

from __future__ import annotations

import gc
import logging
import os
import signal
import struct
import threading
import time
import traceback
import warnings
from typing import Any, BinaryIO, Dict, List, Optional, Tuple

import msgpack

from ..models import (Allocation, Deployment, Evaluation, Job, Node,
                      SchedulerConfiguration)
from ..models.alloc import DesiredTransition
from ..models.deployment import DeploymentStatusUpdate
from ..models.node import DrainStrategy
from ..utils import stages
from ..utils.codec import (ShareMemo, from_wire, rows_from_wire,
                           rows_to_wire, to_wire)
from ..utils.locks import make_lock

# payload field -> model type (list-wrapped == repeated)
SCHEMAS: Dict[str, Dict[str, Any]] = {
    "job_register": {"job": Job, "evals": [Evaluation]},
    "job_deregister": {"evals": [Evaluation]},
    "eval_update": {"evals": [Evaluation]},
    "eval_delete": {},
    "node_register": {"node": Node},
    "node_deregister": {},
    "node_status_update": {"evals": [Evaluation]},
    "node_eligibility_update": {},
    "node_drain_update": {"drain_strategy": DrainStrategy},
    "alloc_client_update": {"allocs": [Allocation], "evals": [Evaluation]},
    "plan_results": {"allocs_stopped": [Allocation],
                     "allocs_placed": [Allocation],
                     "allocs_preempted": [Allocation],
                     "deployment": Deployment,
                     "deployment_updates": [DeploymentStatusUpdate],
                     "evals": [Evaluation]},
    # group-commit applier: one entry carrying N plan_results payloads
    # (encode/decode recurse per group member — see below)
    "plan_group_results": {},
    # batched write ingest (ISSUE 19): one entry carrying N kind-tagged
    # sub-payloads (job_register / alloc_client_update /
    # alloc_desired_transition); encode/decode recurse per entry by its
    # "kind" key — see below
    "ingest_batch": {},
    "scheduler_config": {"config": SchedulerConfiguration},
    "deployment_status_update": {"update": DeploymentStatusUpdate,
                                 "job": Job, "evals": [Evaluation]},
    "deployment_promotion": {"evals": [Evaluation]},
    "alloc_desired_transition": {"transition": DesiredTransition,
                                 "evals": [Evaluation]},
    "job_stability": {},
    "scaling_event": {},
    "server_membership": {},
    "noop": {},
    "deployment_delete": {},
    "periodic_launch": {},
}


# the entries the plan applier commits (plan_commit's children —
# raft_lock_wait, wal_encode, wal_write, fsm_apply, event_publish — are
# reported for these alone, so the span tree stays true)
PLAN_ENTRIES = frozenset({"plan_results", "plan_group_results"})
# a plan's allocation lists: written as one record of constants, a
# table of shared objects and rows (utils/codec.py rows_to_wire)
PLAN_ROW_LISTS = frozenset(k for k, hint in SCHEMAS["plan_results"].items()
                           if hint == [Allocation])


def _register_acl_schemas() -> None:
    # deferred: nomad_tpu.acl imports jobspec which imports models —
    # registering lazily avoids a cycle at module import time
    from ..acl import AclPolicy, AclToken
    from ..models.csi import CSIVolume
    SCHEMAS.update({
        "acl_policy_upsert": {"policies": [AclPolicy]},
        "acl_policy_delete": {},
        "acl_token_upsert": {"tokens": [AclToken]},
        "acl_token_delete": {},
        "csi_volume_register": {"volumes": [CSIVolume]},
        "csi_volume_deregister": {},
        "csi_volume_claim": {},
        "csi_volume_release": {},
    })
    from .event_sink import EventSink
    SCHEMAS.update({
        "event_sink_upsert": {"sink": EventSink},
        "event_sink_delete": {},
        "event_sink_progress": {},
    })
    from ..models.services import ServiceRegistration
    SCHEMAS.update({
        "service_registration_upsert": {"services": [ServiceRegistration]},
        "service_registration_delete": {},
    })
    from ..models.namespace import Namespace
    SCHEMAS.update({
        "namespace_upsert": {"namespaces": [Namespace]},
        "namespace_delete": {},
    })


_register_acl_schemas()


def encode_payload(msg_type: str, payload: dict,
                   memo: Optional[ShareMemo] = None) -> dict:
    """The wire form of one raft entry's payload, for msgpack: the
    tree is packed and dropped, never edited, so within one call an
    object reached twice is walked once and its subtree reused by
    reference (utils/codec.py ShareMemo) — a plan's 1,000 allocations
    hang off one AllocatedResources and a few AllocMetrics, and the
    bytes are what a walk of every occurrence gives. A plan_results
    entry (a member of a plan_group_results entry too) writes each of
    its lists of Allocations as rows_to_wire's record, not as a list of
    dicts: the plan's job, which the applier hangs on every placement,
    is packed once. The memo never outlives the call; a caller passes
    its own to read the counts."""
    if memo is None:
        memo = ShareMemo()
    if msg_type == "plan_results":
        return {k: rows_to_wire(v, memo)
                if k in PLAN_ROW_LISTS and type(v) is list
                and set(map(type, v)) <= {Allocation}
                else to_wire(v, memo) for k, v in payload.items()}
    if msg_type == "plan_group_results":
        return {"groups": [encode_payload("plan_results", g, memo)
                           for g in payload.get("groups", [])]}
    if msg_type == "ingest_batch":
        # each sub-entry encodes under its own kind's schema; the
        # "kind" tag itself is a plain string and rides through
        return {"entries": [encode_payload(e.get("kind", ""), e, memo)
                            for e in payload.get("entries", [])]}
    return {k: to_wire(v, memo) for k, v in payload.items()}


def decode_payload(msg_type: str, data: dict) -> dict:
    """The payload a frame's wire form stands for. A repeated field
    decodes from a list (every entry written before PR 35, and every
    kind but a plan's since) or from rows_to_wire's record."""
    if msg_type == "plan_group_results":
        return {"groups": [decode_payload("plan_results", g)
                           for g in data.get("groups", [])]}
    if msg_type == "ingest_batch":
        return {"entries": [decode_payload(e.get("kind", ""), e)
                            for e in data.get("entries", [])]}
    schema = SCHEMAS.get(msg_type, {})
    out: dict = {}
    for k, v in data.items():
        hint = schema.get(k)
        if hint is None:
            out[k] = v
        elif isinstance(hint, list):
            out[k] = rows_from_wire(hint[0], v) if isinstance(v, dict) \
                else [from_wire(hint[0], x) for x in (v or [])]
        else:
            out[k] = from_wire(hint, v) if v is not None else None
    return out


def _copy(src: BinaryIO, dst: BinaryIO, n: Optional[int]) -> int:
    """Copy `n` bytes (None: to the end of `src`) in 1 MiB reads;
    returns the bytes copied."""
    done = 0
    while n is None or done < n:
        buf = src.read(1 << 20 if n is None else min(1 << 20, n - done))
        if not buf:
            break
        dst.write(buf)
        done += len(buf)
    return done


class RaftLog:
    """Append-only WAL of msgpack frames: [u32 length][payload]."""

    def __init__(self, path: str):
        self.path = path
        self._l = make_lock()
        self._f: Optional[BinaryIO] = None
        self._good_offset: Optional[int] = None
        self._dirty = False      # flushed-but-not-fsynced bytes pending
        self._trunc_shift = 0    # bytes dropped by truncate_prefix
        self._trunc_l = make_lock()     # one truncation; taken before _l

    def open(self) -> None:
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        # a torn tail from a crash must be truncated before appending,
        # or the garbage bytes poison every later frame on next replay
        if self._good_offset is not None and os.path.exists(self.path) \
                and os.path.getsize(self.path) > self._good_offset:
            with open(self.path, "r+b") as f:
                f.truncate(self._good_offset)
        self._f = open(self.path, "ab")

    def close(self) -> None:
        if self._f:
            self._f.close()
            self._f = None

    def append(self, index: int, msg_type: str, payload: dict,
               sync: bool = False) -> Tuple[int, int, int]:
        """Frame and write one entry; returns the encoder's counts
        (ShareMemo's objects, shared, rows). A plan entry reports the
        framing (wire form + packb) as stage wal_encode and, beside
        it, the write as stage wal_write: the log's lock (a snapshot
        writer's truncation holds it), write + flush, the fsync when
        this entry pays its own."""
        memo = ShareMemo()
        plan = msg_type in PLAN_ENTRIES
        with (stages.span("wal_encode") if plan
              else stages.NULL_SPAN) as sp:
            frame = msgpack.packb(
                {"i": index, "t": msg_type, "ts": time.time(),
                 "p": encode_payload(msg_type, payload, memo)},
                use_bin_type=True)
            sp.note(objects=memo.objects, shared=memo.shared,
                    rows=memo.rows, consts=memo.consts, table=memo.table,
                    bytes=len(frame))
        with (stages.span("wal_write", synced=sync) if plan
              else stages.NULL_SPAN):
            with self._l:
                self._f.write(struct.pack("<I", len(frame)))
                self._f.write(frame)
                self._f.flush()
                if sync:
                    os.fsync(self._f.fileno())
                    self._dirty = False
                else:
                    self._dirty = True
        return memo.objects, memo.shared, memo.rows

    def sync(self) -> None:
        """Group-fsync point: ONE fsync covers every append since the
        last sync (the WAL analog of the r9 group-commit applier — the
        raft FSM calls it once per committed apply batch)."""
        with self._l:
            if self._f is not None and self._dirty:
                self._f.flush()
                os.fsync(self._f.fileno())
                self._dirty = False

    def size(self) -> int:
        """Current ABSOLUTE stream position (bytes ever appended,
        including prefixes already truncated away) — the snapshot's
        truncation mark. Absolute marks stay valid even if another
        snapshot writer truncates the file between capture and use;
        `_trunc_shift` tracks the bytes removed so far."""
        with self._l:
            if self._f is not None:
                return self._trunc_shift + self._f.tell()
            phys = os.path.getsize(self.path) \
                if os.path.exists(self.path) else 0
            return self._trunc_shift + phys

    def truncate_prefix(self, mark: int) -> Tuple[int, int]:
        """Drop the log prefix before absolute position `mark` (covered
        by a completed snapshot), KEEPING the tail appended while the
        snapshot was serializing off-thread — a whole-file truncate
        here would lose entries the snapshot does not contain. A mark
        at or below an already-truncated prefix is a no-op, so two
        racing snapshot writers can never cut at a stale offset.

        The tail up to the size read at entry is copied and fsynced
        WITHOUT the log's lock (appends go on to the old file: at
        20 MB/s a snapshot's tail is hundreds of MB, and an append
        parked behind that copy is the writer's stall by another
        door); the lock is taken only for what was appended meanwhile,
        the second fsync and the swap. A crash at any point leaves the
        old file or the complete new one. Returns the bytes copied
        outside and inside the lock."""
        with self._trunc_l:
            with self._l:
                local = mark - self._trunc_shift
                if local <= 0 or not os.path.exists(self.path):
                    return 0, 0
                # every append flushes under _l: the file holds them all
                end = os.path.getsize(self.path)
            tmp = self.path + ".tmp"
            try:
                with open(self.path, "rb", buffering=0) as src, \
                        open(tmp, "wb") as dst:
                    src.seek(local)
                    outside = _copy(src, dst, end - local)
                    dst.flush()
                    os.fsync(dst.fileno())
                    with self._l:
                        locked = _copy(src, dst, None)
                        dst.flush()
                        os.fsync(dst.fileno())
                        was_open = self._f is not None
                        if was_open:
                            self._f.close()
                            self._f = None
                        os.replace(tmp, self.path)
                        self._trunc_shift += local
                        if was_open:
                            self._f = open(self.path, "ab")
                        self._dirty = False
            except BaseException:
                # the old file is whole and still the log
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
            return outside, locked

    def replay(self) -> List[Tuple[int, str, dict]]:
        """Read all entries; tolerates a torn final frame (crash)."""
        out: List[Tuple[int, str, dict]] = []
        self._good_offset = 0
        if not os.path.exists(self.path):
            return out
        with open(self.path, "rb") as f:
            while True:
                header = f.read(4)
                if len(header) < 4:
                    break
                (length,) = struct.unpack("<I", header)
                frame = f.read(length)
                if len(frame) < length:
                    break  # torn write at crash: drop the tail
                try:
                    entry = msgpack.unpackb(frame, raw=False)
                    decoded = decode_payload(entry["t"], entry["p"])
                except Exception:
                    break  # corrupt frame: treat like a torn tail
                out.append((entry["i"], entry["t"], decoded,
                            entry.get("ts", 0.0)))
                self._good_offset = f.tell()
        return out

class Persistence:
    """Snapshot + WAL pair under a data directory."""

    SNAPSHOT = "state.snap"
    WAL = "raft.log"
    # measured per-(arm, n_pad) dispatch costs (ops/select.py
    # DispatchCostModel.snapshot() format: {"<arm>@<n_pad>":
    # {"ewma_s": float, "samples": int}}), persisted as JSON next to
    # the state snapshot so a restarted server's routing/batching
    # decisions start measured instead of cold (ISSUE 7). Host+device
    # local by construction — never replicated, safe to delete
    COST_MODEL = "cost_model.json"

    # a snapshot is due after `snapshot_every` WAL entries OR this many
    # WAL bytes since the last one, whichever comes first. Entries:
    # 8,192 is hashicorp/raft's SnapshotThreshold, which upstream
    # Nomad runs with; the 1,024 of before put a whole-store dump of
    # 400k allocations — 5-7 s on a thread that shares the GIL, during
    # which scheduling all but stops — inside every 20 s of a stream
    # of small service evals (PR 27). Bytes: a stream of 1,000-
    # placement plans writes 2.6 MB an entry, and 8,192 of those would
    # be a 20 GB log to replay; 1 GiB keeps that regime's cadence where
    # 1,024 entries had it
    SNAPSHOT_WAL_BYTES = 1 << 30

    def __init__(self, data_dir: str, snapshot_every: int = 8192, *,
                 columnar: bool = True, background: bool = True,
                 wal_fsync: bool = False, wal_group_fsync: bool = True):
        self.data_dir = data_dir
        self.snapshot_every = snapshot_every
        # snapshot format 2 (state/columnar.py struct-of-arrays) vs the
        # legacy per-object dump; restore auto-detects either
        self.columnar = columnar
        # serialize + write snapshots off an O(1) MVCC store snapshot,
        # in a child forked by a background thread that waits for it,
        # so maybe_snapshot never stalls the commit path and the dump
        # never holds the workers' GIL
        self.background = background
        # WAL durability: fsync appends at all (off matches the
        # pre-r12 flush-only behavior), and whether a committed apply
        # batch pays ONE fsync (group) or one per entry
        self.wal_fsync = wal_fsync
        self.wal_group_fsync = wal_group_fsync
        os.makedirs(data_dir, exist_ok=True)
        self.log = RaftLog(os.path.join(data_dir, self.WAL))
        self._since_snapshot = 0
        self._bytes_at_snapshot = 0     # log.size() at the last trigger
        self._l = make_lock()
        self._snap_l = make_lock()      # one snapshot writer
        self._trigger_l = make_lock()
        self._snap_thread: Optional[threading.Thread] = None
        # absolute WAL mark of the newest PUBLISHED snapshot: a writer
        # whose capture is older must not replace it (a sync snapshot
        # racing a slow background writer), monotone under _snap_l
        self._published_mark = -1
        # counters are += read-modify-writes from the applier (trigger
        # path, under _trigger_l), the writer thread (under _snap_l),
        # and boot restore — no shared lock between them, so they get
        # their own
        self._stats_l = make_lock()
        # nomad-lint: guarded-by[_stats_l]
        self.stats: Dict[str, Any] = {
            "snapshots": 0, "background_snapshots": 0,
            "snapshot_skipped_inflight": 0, "last_snapshot_s": 0.0,
            "last_snapshot_format": 0, "snapshot_errors": 0,
            # background snapshots a forked child serialized, and those
            # the writer thread wrote itself for want of a fork; the
            # last fork's stall in this process and the last child's
            # own seconds
            "snapshot_children": 0, "snapshot_inline": 0,
            "last_snapshot_fork_s": 0.0, "last_snapshot_child_s": 0.0,
            "restore_s": 0.0, "restore_format": 0,
            # the WAL encoder (encode_payload): dataclass instances
            # walked, and subtrees reused because the payload reached
            # the same object again; allocations written as rows of a
            # plan's record
            "wal_objects": 0, "wal_shared": 0, "wal_rows": 0,
            # the WAL's absolute stream position (bytes ever
            # appended), and what it has taken since the last snapshot
            # was triggered: the two quantities the trigger compares
            # (maybe_snapshot), as of the last applied entry
            "wal_bytes": 0, "wal_bytes_since_snapshot": 0,
            "wal_entries_since_snapshot": 0,
        }
        # server-level state (e.g. the GC TimeTable) rides along in the
        # snapshot under "extra"; the provider is set by the Server
        self.extra_provider = None
        # set by the Server: returns the live cost-model snapshot dict;
        # written on every state snapshot and at shutdown
        self.cost_model_provider = None
        self.restored_extra: dict = {}

    @property
    def snapshot_path(self) -> str:
        return os.path.join(self.data_dir, self.SNAPSHOT)

    @property
    def cost_model_path(self) -> str:
        return os.path.join(self.data_dir, self.COST_MODEL)

    def load_cost_model(self) -> dict:
        import json
        try:
            with open(self.cost_model_path) as f:
                data = json.load(f)
            return data if isinstance(data, dict) else {}
        except (OSError, ValueError):
            return {}

    def save_cost_model(self) -> None:
        import json
        if self.cost_model_provider is None:
            return
        snap = self.cost_model_provider()
        if not snap:
            return
        tmp = self.cost_model_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=0, sort_keys=True)
        os.replace(tmp, self.cost_model_path)

    def restore_into(self, store
                     ) -> Tuple[int, List[Tuple[int, str, dict, float]]]:
        """Load the snapshot into the store and read the WAL tail.
        Returns ``(highest, entries)``: the snapshot's highest applied
        index (0 if fresh) and the decoded WAL entries for the caller
        to replay (each ``(index, msg_type, payload, ts)``). Both
        snapshot formats restore here — the columnar format-2 file and
        the legacy per-object dump (state/store.py restore
        auto-detects). A leftover ``state.snap.tmp`` from a crash
        mid-snapshot is ignored (os.replace is atomic, so the prior
        snapshot + un-truncated WAL are intact) and cleaned up."""
        t0 = time.perf_counter()
        highest = 0
        tmp = self.snapshot_path + ".tmp"
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:     # pragma: no cover — best effort
                pass
        if os.path.exists(self.snapshot_path):
            with open(self.snapshot_path, "rb") as f:
                data = msgpack.unpackb(f.read(), raw=False,
                                       strict_map_key=False)
            # snapshot index tuples were listified by msgpack
            self.restored_extra = data.pop("extra", {}) or {}
            with self._stats_l:
                self.stats["restore_format"] = int(data.get("format", 1))
            store.restore(data)
            highest = store.latest_index()
        entries = self.log.replay()
        self.log.open()
        with self._l:
            self._note_wal(self.log.size())
        with self._stats_l:
            self.stats["restore_s"] = time.perf_counter() - t0
        if stages.enabled:
            stages.add("restore", self.stats["restore_s"])
        return highest, entries

    def record(self, index: int, msg_type: str, payload: dict) -> None:
        objects, shared, rows = self.log.append(
            index, msg_type, payload,
            sync=self.wal_fsync and not self.wal_group_fsync)
        with self._stats_l:
            self.stats["wal_objects"] += objects
            self.stats["wal_shared"] += shared
            self.stats["wal_rows"] += rows

    @property
    def group_fsync(self) -> bool:
        """Whether the commit barrier pays an fsync in this
        configuration (else every entry pays its own, or none does)."""
        return self.wal_fsync and self.wal_group_fsync

    def commit_barrier(self) -> None:
        """Group-fsync boundary: called once per committed apply batch
        (raft.py _fsm_loop; the dev-mode apply calls it per entry —
        there the entry IS the commit unit). One fsync covers every
        record() since the last barrier."""
        if self.group_fsync:
            self.log.sync()

    def maybe_snapshot(self, store) -> None:
        """Called AFTER the FSM applied the entry — a snapshot capture
        here includes it, so dropping the covered WAL prefix is safe.
        Only TRIGGERS the snapshot: the capture is an O(1) MVCC root +
        WAL mark, and serialization/writing run on a background thread
        (snapshot_background), so the applier never blocks on a dump
        of a large store."""
        with self._l:
            self._since_snapshot += 1
            size = self.log.size()
            due = self._since_snapshot >= self.snapshot_every or \
                size - self._bytes_at_snapshot >= self.SNAPSHOT_WAL_BYTES
            if due:
                self._since_snapshot = 0
                self._bytes_at_snapshot = size
            self._note_wal(size)
        if due:
            self.trigger_snapshot(store)

    def _note_wal(self, size: int) -> None:
        """The trigger's two quantities into `stats` (under _l)."""
        with self._stats_l:
            self.stats["wal_bytes"] = size
            self.stats["wal_bytes_since_snapshot"] = \
                size - self._bytes_at_snapshot
            self.stats["wal_entries_since_snapshot"] = self._since_snapshot

    def trigger_snapshot(self, store) -> Optional[threading.Thread]:
        """Capture (MVCC snapshot, extra, WAL mark) NOW; serialize and
        write in a forked child that a writer thread waits for. Returns
        that thread, or None when the write ran inline (background off)
        or was skipped because one is already in flight (the next
        threshold retriggers)."""
        with self._trigger_l:
            t = self._snap_thread
            if t is not None and t.is_alive():
                with self._stats_l:
                    self.stats["snapshot_skipped_inflight"] += 1
                return None
            snap = store.snapshot()
            extra = self.extra_provider() \
                if self.extra_provider is not None else None
            mark = self.log.size()
            if not self.background:
                self._write_snapshot(snap, extra, mark)
                return None
            t = threading.Thread(target=self._write_snapshot,
                                 args=(snap, extra, mark, True),
                                 daemon=True, name="snapshot-writer")
            self._snap_thread = t
            t.start()
            with self._stats_l:
                self.stats["background_snapshots"] += 1
            return t

    def snapshot(self, store) -> None:
        """Synchronous snapshot (shutdown, snapshot-install reseed,
        tests): waits out any in-flight background writer, then writes
        inline."""
        self.wait_idle()
        with self._trigger_l:
            snap = store.snapshot()
            extra = self.extra_provider() \
                if self.extra_provider is not None else None
            mark = self.log.size()
        self._write_snapshot(snap, extra, mark)

    def wait_idle(self, timeout_s: float = 30.0) -> None:
        """Join an in-flight background snapshot writer (shutdown)."""
        with self._trigger_l:
            t = self._snap_thread
        if t is not None and t.is_alive():
            t.join(timeout_s)

    def _write_snapshot(self, snap, extra: Optional[dict],
                        wal_mark: int, in_child: bool = False) -> None:
        """Serialize + atomically publish one captured snapshot, then
        drop the WAL prefix it covers (entries appended after the
        capture survive in the tail). `in_child` (the background
        writer's thread): the serialization runs in a forked child and
        this thread only waits for it, so that a whole-store dump never
        holds the serving process's GIL."""
        t0 = time.perf_counter()
        tmp = self.snapshot_path + ".tmp"
        try:
            # the writer's own time, the wait for a sibling writer and
            # for the child included: the interval last_snapshot_s
            # reports. `entries` is the raft index the capture covers
            with stages.span("snapshot_write",
                             entries=snap.latest_index()) as sp, \
                    self._snap_l:
                if wal_mark < self._published_mark:
                    # a newer capture already published while this one
                    # waited: replacing it would pair an OLDER snapshot
                    # with a MORE-truncated WAL and lose the gap
                    sp.cancel()
                    return
                try:
                    wrote = self._serialize_in_child(snap, extra, tmp) \
                        if in_child else None
                    if wrote is None:
                        wrote = self._serialize(snap, extra, tmp)
                    os.replace(tmp, self.snapshot_path)
                except BaseException:
                    # no half-written tmp outlives its writer
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                    raise
                tail, locked_tail = self.log.truncate_prefix(wal_mark)
                self._published_mark = wal_mark
                sp.note(bytes=wrote["bytes"],
                        fork_ms=wrote.get("fork_s", 0.0) * 1e3,
                        child_s=wrote.get("seconds", 0.0),
                        tail_bytes=tail, locked_tail_bytes=locked_tail)
                with self._stats_l:
                    self.stats["snapshots"] += 1
                    self.stats["last_snapshot_s"] = \
                        time.perf_counter() - t0
                    self.stats["last_snapshot_format"] = wrote["format"]
                    if "fork_s" in wrote:
                        self.stats["snapshot_children"] += 1
                        self.stats["last_snapshot_fork_s"] = \
                            wrote["fork_s"]
                        self.stats["last_snapshot_child_s"] = \
                            wrote["seconds"]
                    elif in_child:
                        self.stats["snapshot_inline"] += 1
                try:
                    self.save_cost_model()
                except OSError:     # pragma: no cover — best effort
                    pass
        except Exception:
            # a failed snapshot must not kill the applier or the writer
            # thread; state.snap and the WAL keep everything, the next
            # threshold retries
            logging.getLogger("nomad_tpu.persistence").exception(
                "snapshot write failed")
            with self._stats_l:
                self.stats["snapshot_errors"] += 1

    def _serialize(self, snap, extra: Optional[dict], tmp: str) -> dict:
        """Dump the captured root, pack it and write it to `tmp`,
        fsynced: the one function that makes a snapshot's bytes,
        whichever process runs it."""
        data = snap.dump_columnar() if self.columnar else snap.dump()
        if extra is not None:
            data["extra"] = extra
        blob = msgpack.packb(data, use_bin_type=True)
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        return {"bytes": len(blob), "format": int(data.get("format", 1))}

    def _serialize_in_child(self, snap, extra: Optional[dict],
                            tmp: str) -> Optional[dict]:
        """Run `_serialize` in a forked child — this thread alone, over
        a copy-on-write image of the captured root — and wait for it
        with the GIL released. Returns the child's report plus
        `seconds` (the child's own) and `fork_s` (this process's stall
        inside os.fork), or None where the platform cannot fork: the
        caller then serializes inline. A child that raised, exited
        non-zero or was killed raises here.

        The child takes no lock of the program's, opens no span, logs
        nothing, touches no JAX object, and leaves by os._exit: no
        atexit hook, no flush of a buffer it inherited."""
        if not hasattr(os, "fork"):
            return None
        # what the child imports lazily must be loaded already: an
        # import lock some other thread held at the fork stays held
        from ..state import columnar  # noqa: F401
        r, w = os.pipe()
        t0 = time.perf_counter()
        try:
            with warnings.catch_warnings():
                # expected here, and silenced for this call alone:
                # CPython 3.12's multi-threaded fork warning and JAX's
                # from its at-fork hook
                warnings.filterwarnings(
                    "ignore", category=DeprecationWarning,
                    message=r"This process .* is multi-threaded, "
                            r"use of fork\(\)")
                warnings.filterwarnings(
                    "ignore", category=RuntimeWarning,
                    message=r"os\.fork\(\) was called")
                pid = os.fork()
        except OSError:
            os.close(r)
            os.close(w)
            return None
        if pid == 0:
            code = 1
            try:
                os.close(r)
                gc.disable()
                # a signal meant for the server must end this child,
                # not run the server's handler in it
                signal.signal(signal.SIGINT, signal.SIG_DFL)
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                c0 = time.perf_counter()
                report = self._serialize(snap, extra, tmp)
                report["seconds"] = time.perf_counter() - c0
                code = 0
            except BaseException:
                report = {"error": traceback.format_exc()}
            try:
                os.write(w, msgpack.packb(report))
            finally:
                os._exit(code)
        fork_s = time.perf_counter() - t0
        os.close(w)
        with open(r, "rb", buffering=0) as pipe:
            raw = pipe.readall()
        _, status = os.waitpid(pid, 0)
        report = msgpack.unpackb(raw, raw=False) if raw else {}
        if status != 0 or "error" in report or "bytes" not in report:
            raise RuntimeError(
                "snapshot child %d ended with status %s: %s" % (
                    pid, os.waitstatus_to_exitcode(status),
                    report.get("error", "no report")))
        report["fork_s"] = fork_s
        return report
