"""Mesh-sharded resident node table: the r7 delta machinery made
mesh-native (ROADMAP "Device-sharded state: break the single-chip
ceiling").

The single-device mirror (ops/device_table.py DeviceNodeTable) made
steady-state dispatch cheap on ONE chip: columns resident across evals,
advanced by batched row scatters. The mesh path (parallel/sharded.py)
had none of that — every non-capacity column was re-uploaded host ->
device on every dispatch, which caps the scale ladder at whatever one
chip's H2D bandwidth tolerates. This module keeps the hot columns —
capacity, used, free_ports — *sharded-resident* over the mesh
(`NamedSharding` over the `nodes` axis) and advances them with the same
delta protocol:

  - cold start / node-set rebuild: ONE sharded H2D per column
    (`jax.device_put(col, NamedSharding(mesh, P("nodes", ...)))` — jax
    splits the transfer per device), counted as a `reshard_upload`.
  - alloc-delta refreshes: the cache's DeviceNodeTable journals every
    refresh's touched row indices (`delta_log`); this mirror catches up
    from its version to the request table's version by scatter-setting
    the journaled rows from the CURRENT host columns, as a sharded jit
    program — each shard scatters only the rows it owns. `.set` with
    host-latest values makes replay order-free and bit-identical to a
    rebuild by construction.
  - per-eval plan overlays apply as sparse `.at[rows].add` over the
    resident used column, on device, like the single-chip mirror.

MVCC staleness: the (mirror identity, version) token carried by every
NodeTable gates reuse exactly like the single-device path — a snapshot
older than the resident state falls back to dense shipping, a journal
gap (rebuild, ring truncation, cache replacement) triggers one
contiguous re-upload.

Fold-to-rebuild: scattered-row debt since the last contiguous upload is
tracked per mirror; the governor's `mesh.reshard_debt` watermark
(ServerConfig.mesh_reshard_debt_high) reclaims by re-uploading once,
replacing the scatter history.

Kill switches: `NOMAD_TPU_MESH_RESIDENT=0` (env, wins) or
`ServerConfig.mesh_resident=False` fall back to the capacity-only
per-eval upload path — the bisection escape hatch.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np

from ..utils.locks import make_lock
from ..ops.device_table import (DeviceTableState, SPARSE_MAX_FRAC,
                                _bucket_rows, _overlay_add, _scatter_set,
                                enable_row_journal, note_device_op_failure)

MESH_RESIDENT_ENV = "NOMAD_TPU_MESH_RESIDENT"

# ServerConfig.mesh_resident lands here (server/core.py configure());
# the env kill switch wins over it either way
_RESIDENT_CFG = True


def configure(resident: bool) -> None:
    global _RESIDENT_CFG
    _RESIDENT_CFG = bool(resident)


def resident_enabled() -> bool:
    v = os.environ.get(MESH_RESIDENT_ENV)
    if v is not None:
        return v not in ("0", "off", "no")
    return _RESIDENT_CFG


def pad_for_mesh(mesh, n: int) -> int:
    """Pad N so it divides evenly over the mesh, VPU-lane aligned —
    the one padding rule shared by the sharded dispatcher and this
    resident table (their shapes must agree or residency never hits)."""
    shards = mesh.devices.size
    per = -(-n // shards)
    per = max(8, per)
    return per * shards


class ShardedDeviceNodeTable:
    """The mesh-resident mirror one process-wide ShardedSelect owns.

    Tracks ONE (host mirror, version) pair — the latest NodeTableCache
    generation it served. `arrays_for(table)` returns sharded device
    columns for that table, advancing by journal replay when the table
    is ahead, or None for stale snapshots (dense fallback)."""

    def __init__(self, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        # a companion now exists: host mirrors start journaling row
        # indices (entries before this read as a gap -> one re-upload)
        enable_row_journal()
        self.mesh = mesh
        self.node_sharding = NamedSharding(mesh, P("nodes"))
        self.node2_sharding = NamedSharding(mesh, P("nodes", None))
        self.replicated = NamedSharding(mesh, P())
        self._jax = jax
        self._l = make_lock()
        self._state: Optional[DeviceTableState] = None
        self._mirror = None         # the host cache's DeviceNodeTable
        self._version = -1
        self._epoch = -1
        self.delta_debt = 0         # rows scattered since last upload
        self.stats: Dict[str, int] = {
            "reshard_uploads": 0, "reshard_bytes": 0,
            "delta_scatters": 0, "delta_rows": 0,
            "resident_hits": 0, "stale_misses": 0, "journal_gaps": 0,
            "overlay_dispatches": 0, "folds": 0,
        }

    # -- kernel-side access --------------------------------------------
    def arrays_for(self, table) -> Optional[DeviceTableState]:
        """Sharded device columns for `table`, or None when this table
        is a stale snapshot (the resident state moved past it — dense
        fallback, like the single-device mirror). A table ahead of the
        resident version catches the mirror up by journal replay; a
        gap or a new cache generation pays one contiguous sharded
        re-upload."""
        mirror = getattr(table, "device_mirror", None)
        token = getattr(table, "device_version", -1)
        if mirror is None or token < 0:
            return None
        with self._l:
            st = self._state
            if st is None or self._mirror is not mirror \
                    or self._epoch != mirror.epoch:
                return self._upload_locked(table, mirror, token)
            if token == self._version:
                self.stats["resident_hits"] += 1
                return st
            if token < self._version:
                # older snapshot than the resident state: MVCC says it
                # must not read newer columns
                self.stats["stale_misses"] += 1
                return None
            entries = mirror.deltas_since(self._version)
            if entries is None:
                self.stats["journal_gaps"] += 1
                return self._upload_locked(table, mirror, token)
            # drop journal entries past the request's version: the
            # mirror may already be ahead of this table's snapshot
            rows_l = [r for v, r in entries if v <= token and len(r)]
            rows = (np.unique(np.concatenate(rows_l)) if rows_l
                    else np.zeros(0, np.int32))
            if len(rows) > st.n * SPARSE_MAX_FRAC:
                # wide delta: one contiguous upload beats scattering
                # most of the table
                return self._upload_locked(table, mirror, token)
            if len(rows):
                try:
                    st = self._scatter_locked(st, table, rows)
                except Exception:
                    # a failed device op must not poison scheduling —
                    # counted as a failure, not as a stale miss
                    note_device_op_failure("sharded_table.scatter")
                    self._state = None
                    return None
                self._state = st
            self._version = token
            self.stats["resident_hits"] += 1
            return self._state

    def _scatter_locked(self, st: DeviceTableState, table,
                        rows: np.ndarray) -> DeviceTableState:
        m = len(rows)
        idx = rows.astype(np.int32)
        from ..analysis import sanitizer
        if sanitizer.enabled():
            sanitizer.check_rows("sharded_table.scatter", idx, st.n)
        b = _bucket_rows(m)
        if b > m:
            # pad with repeats of the first row carrying its own value:
            # duplicate .set with an identical payload is deterministic
            idx = np.concatenate([idx, np.full(b - m, idx[0], np.int32)])
        used_rows = table.base_used[idx].astype(np.float32)
        port_rows = table.free_ports[idx].astype(np.float32)
        # row payloads ride replicated; the resident operands are
        # sharded, so XLA partitions the scatter — each shard sets only
        # the rows it owns
        put = self._jax.device_put
        used, ports = _scatter_set(st.used, st.free_ports,
                                   put(idx, self.replicated),
                                   put(used_rows, self.replicated),
                                   put(port_rows, self.replicated))
        self.delta_debt += m
        self.stats["delta_scatters"] += 1
        self.stats["delta_rows"] += m
        return DeviceTableState(st.version, st.epoch, st.n, st.n_pad,
                                st.capacity, used, ports)

    def _upload_locked(self, table, mirror, token) -> DeviceTableState:
        """One contiguous sharded H2D per column (capacity, used,
        free_ports) — the cold-start / catch-up-miss path, and the
        shard-aware `build_from_columns` upload at cold start
        (NodeTableCache.prefetch_device)."""
        from ..utils import stages
        with stages.span("h2d", upload=True):
            n = table.n
            n_pad = pad_for_mesh(self.mesh, n)
            d = table.base_used.shape[1]
            cap = np.zeros((n_pad, d), np.float32)
            cap[:n] = table.capacity
            used = np.zeros((n_pad, d), np.float32)
            used[:n] = table.base_used
            ports = np.zeros(n_pad, np.float32)
            ports[:n] = table.free_ports
            put = self._jax.device_put
            st = DeviceTableState(token, mirror.epoch, n, n_pad,
                                  put(cap, self.node2_sharding),
                                  put(used, self.node2_sharding),
                                  put(ports, self.node_sharding))
        self._state = st
        self._mirror = mirror
        self._version = token
        self._epoch = mirror.epoch
        self.delta_debt = 0
        self.stats["reshard_uploads"] += 1
        self.stats["reshard_bytes"] += cap.nbytes + used.nbytes \
            + ports.nbytes
        return st

    def overlay_used(self, st: DeviceTableState, rows, deltas):
        """used0 = resident used + sparse per-eval plan overlay,
        computed on the mesh. Returns a sharded device array (async),
        st.used itself for an empty overlay, or None when the overlay
        is too dense to be worth scattering."""
        m = len(rows)
        if m == 0:
            return st.used
        if m > st.n * SPARSE_MAX_FRAC:
            return None
        idx = np.asarray(rows, np.int32)
        vals = np.asarray(deltas, np.float32)
        from ..analysis import sanitizer
        if sanitizer.enabled():
            sanitizer.check_rows("sharded_table.overlay", idx, st.n)
            sanitizer.check_finite("sharded_table.overlay", deltas=vals)
        b = _bucket_rows(m)
        if b > m:
            idx = np.concatenate([idx, np.zeros(b - m, np.int32)])
            vals = np.concatenate(
                [vals, np.zeros((b - m, vals.shape[1]), np.float32)])
        put = self._jax.device_put
        self.stats["overlay_dispatches"] += 1
        return _overlay_add(st.used, put(idx, self.replicated),
                            put(vals, self.replicated))

    # -- governor integration ------------------------------------------
    def fold(self, table, version: Optional[int] = None) -> dict:
        """Reclaim (mesh.reshard_debt watermark): replace the scatter
        history with one contiguous sharded re-upload from the current
        host table."""
        with self._l:
            mirror = getattr(table, "device_mirror", None)
            token = getattr(table, "device_version", -1)
            if version is not None and version != token:
                return {"folded": False, "reason": "stale table"}
            if self._state is None or mirror is None:
                self.delta_debt = 0
                return {"folded": False, "reason": "not materialized"}
            if token < self._version:
                return {"folded": False, "reason": "stale table"}
            debt = self.delta_debt
            self._upload_locked(table, mirror, token)
            self.stats["folds"] += 1
            return {"folded": True, "debt_cleared": debt}

    def debt(self) -> int:
        return self.delta_debt

    def device_bytes(self) -> int:
        """Bytes the resident columns pin across the mesh (shape
        metadata only — reading .nbytes never syncs a device)."""
        with self._l:
            st = self._state
        if st is None:
            return 0
        total = 0
        for arr in (st.capacity, st.used, st.free_ports):
            total += int(getattr(arr, "nbytes", 0))
        return total

    def snapshot(self) -> dict:
        with self._l:
            return {"materialized": self._state is not None,
                    "version": self._version,
                    "reshard_debt": self.delta_debt, **self.stats}
