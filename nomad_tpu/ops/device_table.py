"""Device-resident node table: the dense columns pinned on device,
maintained by incremental scatter deltas.

BENCH_r05 showed the system host-bound AROUND the kernel (163.8k
placements/s in-kernel vs 12.3k e2e): every eval re-shipped the full
(N, D) capacity/used columns to the device — at 50k nodes that is two
~800 KB H2D transfers per dispatch.
This module keeps ONE device copy per NodeTableCache and advances it
with batched row scatters:

  - `capacity` is immutable per node-set epoch: uploaded once, reused
    by every dispatch until a node registration/status flip rebuilds
    the host table (epoch bump -> fresh upload).
  - `used` / `free_ports` advance by `.at[rows].set(new_rows)` — the
    rows a plan apply touched, shipped as (idx, values) pairs instead
    of the whole column. `.set` (not `.add`) with the host-computed
    values makes the mirror bit-identical to the host shadow by
    construction: no float-order concerns, and parity is checkable row
    for row.
  - per-eval plan overlays (`ProposedIndex.plan_delta`) apply on
    device as a sparse `.at[rows].add(deltas)` over the resident
    `used`, so the kernel's `used0` never crosses the bus densely.

MVCC: the mirror tracks ONE version — the cache's latest. Every
NodeTable version carries a (mirror, version) token; a kernel dispatch
uses the device arrays only when the token still matches, otherwise it
falls back to shipping dense columns (stale snapshots pay, the steady
state doesn't). Scatter dispatches are ASYNC (jax's deferred
execution): the cache never blocks on them, so the device applies
table deltas while the host builds the next eval's masks — the
double-buffered delta application of the pipelined worker loop.

Delta debt + fold-to-rebuild: every scatter pads its row block to a
power-of-two bucket (bounds XLA recompiles) and appends device work;
the cumulative scattered-row count since the last full upload is the
mirror's *delta debt*. When debt crosses the governor watermark, one
contiguous re-upload (`fold`) is cheaper than the scatter history it
replaces — the reclaim policy registered in nomad_tpu/governor/.

`NOMAD_TPU_TABLE_DELTA=0` disables both the host delta path and this
mirror (every refresh becomes a cold rebuild) — the bisection escape
hatch.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Tuple

import numpy as np
from ..utils.locks import make_lock

LOG = logging.getLogger("nomad_tpu.device_table")

TABLE_DELTA_ENV = "NOMAD_TPU_TABLE_DELTA"

# Device ops that raised and were absorbed into a host/dense path, by
# site. Scheduling survives such a failure by design, but it must not
# look like an ordinary stale miss: every absorbing site reports here
# (traceback logged), ops/select.device_stats_snapshot exports the
# dict, and chip_smoke.py requires it empty.
DEVICE_OP_FAILURES: Dict[str, int] = {}
_FAIL_L = make_lock()


def note_device_op_failure(site: str) -> None:
    """Call from an except block: count and log the absorbed failure."""
    LOG.exception("device op failed at %s; continuing on the host path",
                  site)
    with _FAIL_L:
        DEVICE_OP_FAILURES[site] = DEVICE_OP_FAILURES.get(site, 0) + 1


# overlay/scatter row blocks above this fraction of the table fall back
# to dense shipping — scattering most of the table costs more than one
# contiguous transfer
SPARSE_MAX_FRAC = 0.5
DELTA_LOG_MAX = 256
# widest delta worth journaling row indices for: a companion mirror
# re-uploads contiguously past this anyway (SPARSE_MAX_FRAC), so wider
# entries journal a None sentinel instead of pinning huge index arrays
JOURNAL_ROWS_MAX = 16384

# row-index journaling engages only once a companion mirror exists
# (the mesh-sharded resident table registers itself on construction);
# a single-chip deployment never pays the index-array memory — its
# journal entries carry None sentinels, which any late-arriving
# companion reads as a gap (one re-upload, then arrays flow)
_ROW_JOURNAL = False


def enable_row_journal() -> None:
    global _ROW_JOURNAL
    _ROW_JOURNAL = True


def delta_enabled() -> bool:
    """The bisection escape hatch: NOMAD_TPU_TABLE_DELTA=0 forces the
    old rebuild-per-refresh path (host and device alike)."""
    return os.environ.get(TABLE_DELTA_ENV, "1") not in ("0", "off", "no")


def _pad_n(n: int) -> int:
    # kept in lockstep with ops/select._pad_n (the kernel's node-axis
    # padding rule); duplicated to keep this module import-light
    p = 8
    while p < n:
        p *= 2
    return p


def _bucket_rows(m: int) -> int:
    b = 8
    while b < m:
        b *= 2
    return b


class DeviceTableState:
    """Immutable snapshot of the mirror's device arrays. Readers grab
    one reference and use it without locking; scatter updates replace
    the whole state object, never mutate it (jax arrays are functional
    anyway — this just makes the version/array pairing atomic)."""

    __slots__ = ("version", "epoch", "n", "n_pad", "capacity", "used",
                 "free_ports")

    def __init__(self, version: int, epoch: int, n: int, n_pad: int,
                 capacity, used, free_ports):
        self.version = version
        self.epoch = epoch
        self.n = n
        self.n_pad = n_pad
        self.capacity = capacity
        self.used = used
        self.free_ports = free_ports


FEAS_ENTRIES_MAX = 64


class FeasMaskStore:
    """Device-resident combined feasibility masks (ISSUE 17).

    One per mirror, keyed by the stack's feasibility cache key. Entries
    are versioned by the node-attr index (ids_epoch, version) — the
    authority on WHICH nodes the mask covers and WHEN it was last
    correct — not by the mirror's own version, which advances on alloc
    deltas that don't touch feasibility. `put` uploads the full padded
    mask on first sight / epoch change and row-scatters on incremental
    attr updates; `resident` hands the array to the dispatch only when
    the request's token still names the entry exactly."""

    def __init__(self):
        self._l = make_lock()
        # feas_key -> {"arr", "n", "n_pad", "epoch", "version"}
        self._entries: Dict[object, dict] = {}
        # rows scattered atop parked masks by per-eval residue
        # (ISSUE 20) since the last fold/reset — the governor's
        # feas.residue_rows watermark; fold() zeroes it
        self.residue_debt = 0
        self.stats: Dict[str, int] = {
            "uploads": 0, "scatters": 0, "hits": 0, "stale": 0,
            "residue_scatters": 0, "residue_rows": 0, "folds": 0,
        }

    def peek(self, key) -> Optional[Tuple[int, int]]:
        """(ids_epoch, version) of the resident entry, or None. The
        compiler uses this to journal only the rows changed since."""
        with self._l:
            e = self._entries.get(key)
            return None if e is None else (e["epoch"], e["version"])

    def put(self, key, mask: np.ndarray, epoch: int, version: int,
            rows) -> Optional[Tuple]:
        """Park `mask` (table-space bool[n]) on device and return the
        token (key, epoch, version, n) a request attaches to dispatch
        against it, or None if the upload failed. `rows` — table rows
        changed since this entry's previous version within the same
        epoch — selects the jitted row-scatter patch over the full
        upload; None forces the upload."""
        n = len(mask)
        n_pad = _pad_n(n)
        tok = (key, epoch, version, n)
        # snapshot the decision inputs under the lock; the device work
        # (upload or jitted scatter) runs OUTSIDE it — parking a mask
        # must not serialize concurrent readers behind a dispatch
        with self._l:
            e = self._entries.get(key)
            if e is not None and e["epoch"] == epoch \
                    and e["version"] == version and e["n"] == n:
                return tok  # already current
            patchable = (
                e is not None and e["epoch"] == epoch
                and e["n"] == n and rows is not None
                and len(rows) <= n * SPARSE_MAX_FRAC)
            base = e["arr"] if patchable else None
            base_ver = e["version"] if patchable else None
        kind = "none"
        try:
            if patchable and len(rows) == 0:
                # version advanced but no row's verdict context
                # changed: stamp the entry, no device work
                arr = base
            elif patchable:
                idx = np.fromiter(rows, np.int32, len(rows))
                b = _bucket_rows(len(idx))
                if b > len(idx):
                    # pad with a repeat of the first row: duplicate
                    # `.set` indices land the same value, harmless
                    idx = np.concatenate(
                        [idx, np.full(b - len(idx), idx[0],
                                      np.int32)])
                arr = _feas_scatter(base, idx, mask[idx].astype(bool))
                kind = "scatters"
            else:
                padded = np.zeros(n_pad, bool)
                padded[:n] = mask
                import jax
                arr = jax.device_put(padded)
                kind = "uploads"
        except Exception:
            note_device_op_failure("feas_mask.put")
            return None
        with self._l:
            if patchable:
                # a concurrent put moved the entry while we patched its
                # snapshot: our base is stale, drop this park (the next
                # eval re-parks from its own fresher mask)
                e2 = self._entries.get(key)
                if e2 is None or e2["version"] != base_ver \
                        or e2["epoch"] != epoch:
                    return None
            if kind != "none":
                self.stats[kind] += 1
            self._entries[key] = {"arr": arr, "n": n, "n_pad": n_pad,
                                  "epoch": epoch, "version": version}
            while len(self._entries) > FEAS_ENTRIES_MAX:
                self._entries.pop(next(iter(self._entries)))
            return tok

    def resident(self, token, n_pad: int):
        """The device array for `token`, or None when the entry moved
        on (or the kernel's padding disagrees) — caller falls back to
        packing the host mask."""
        if token is None:
            return None
        key, epoch, version, n = token
        with self._l:
            e = self._entries.get(key)
            if e is None or e["epoch"] != epoch \
                    or e["version"] != version or e["n_pad"] != n_pad:
                self.stats["stale"] += 1
                return None
            self.stats["hits"] += 1
            return e["arr"]

    def apply_residue(self, arr, rows: np.ndarray, vals: np.ndarray):
        """Reproduce the host mask's residue mutations (CSI claims,
        quota caps, preferred-node restriction) on the parked device
        mask with ONE jitted row-scatter — per-eval, never stored, so
        the resident entry itself stays the pre-residue combined mask
        and the token keeps surviving. Returns the scattered array or
        None (caller falls back to packing the host mask)."""
        m = len(rows)
        if m == 0:
            return arr
        try:
            idx = np.asarray(rows, dtype=np.int32)
            v = np.asarray(vals, dtype=bool)
            b = _bucket_rows(m)
            if b > m:
                # pad with a repeat of the first row: duplicate `.set`
                # indices land the same value, harmless
                idx = np.concatenate(
                    [idx, np.full(b - m, idx[0], np.int32)])
                v = np.concatenate([v, np.full(b - m, v[0], bool)])
            out = _feas_scatter(arr, idx, v)
        except Exception:
            note_device_op_failure("feas_mask.apply_residue")
            return None
        with self._l:
            self.stats["residue_scatters"] += 1
            self.stats["residue_rows"] += m
            self.residue_debt += m
        return out

    def fold(self) -> dict:
        """Governor reclaim (governor_feas_residue_high): drop the
        parked entries and zero the residue debt — the next eval
        re-parks a fresh combined mask instead of compounding scatter
        work atop a long-lived base."""
        with self._l:
            dropped = len(self._entries)
            self._entries.clear()
            debt = self.residue_debt
            self.residue_debt = 0
            self.stats["folds"] += 1
        return {"feas_entries_dropped": dropped,
                "residue_debt_cleared": debt}

    def debt(self) -> int:
        with self._l:
            return self.residue_debt

    def snapshot(self) -> dict:
        with self._l:
            return {"entries": len(self._entries),
                    "residue_debt": self.residue_debt, **self.stats}


class DeviceNodeTable:
    """The device-resident mirror one NodeTableCache owns.

    Lazy: holds no device memory (and triggers no jax init) until a
    kernel first asks for arrays via `arrays_for`. Until then,
    `note_delta`/`note_rebuild` just advance the version counter so a
    later materialization starts from the right table."""

    def __init__(self):
        self._l = make_lock()
        self._state: Optional[DeviceTableState] = None
        self.version = 0            # latest host table version (token)
        self.epoch = 0              # node-set generation
        self.delta_debt = 0         # rows scattered since last upload
        # replay journal: (version, touched-row indices) per delta,
        # recorded whether or not THIS mirror is materialized — a
        # companion mirror on another device topology (the mesh-sharded
        # resident table, parallel/sharded_table.py) catches its copy
        # up by scatter-setting the union of journaled rows from the
        # latest host table (`.set` with host values makes replay
        # order-free and idempotent). Bounded ring: a companion that
        # fell further behind than DELTA_LOG_MAX entries re-uploads.
        self.delta_log: List[Tuple[int, np.ndarray]] = []
        self.stats: Dict[str, int] = {
            "uploads": 0, "scatters": 0, "folds": 0,
            "overlay_dispatches": 0, "stale_misses": 0,
        }
        # device-resident compiled feasibility masks (ISSUE 17): keyed
        # by the stack's feas cache key, versioned by the attr index —
        # deliberately NOT by this mirror's version/epoch, because node
        # attribute changes and alloc deltas advance independently
        self.feas = FeasMaskStore()

    # -- cache-side bookkeeping (called under the cache's lock) --------
    def note_rebuild(self) -> int:
        """A node-set rebuild invalidated the columns: bump the epoch,
        drop the device arrays (re-materialized lazily from the new
        table), return the new version token."""
        with self._l:
            self.epoch += 1
            self.version += 1
            self._state = None
            self.delta_debt = 0
            self.delta_log.clear()
            return self.version

    def note_delta(self, table, rows) -> int:
        """Advance the mirror past an alloc-delta refresh: `rows` are
        the host-table indices the refresh touched. When materialized,
        dispatch the row scatter asynchronously (no block — the device
        chews it while the host moves on); otherwise only the version
        advances. Returns the new version token."""
        with self._l:
            self.version += 1
            # journal the touched rows even while lazy: companion
            # mirrors (the mesh-sharded resident table) replay them.
            # Wide deltas journal a sentinel — replaying them would
            # cost more than the contiguous re-upload they force — and
            # without a registered companion no index arrays are built
            self.delta_log.append(
                (self.version,
                 np.fromiter(rows, np.int32, len(rows))
                 if _ROW_JOURNAL and len(rows) <= JOURNAL_ROWS_MAX
                 else None))
            if len(self.delta_log) > DELTA_LOG_MAX:
                del self.delta_log[:len(self.delta_log) - DELTA_LOG_MAX]
            st = self._state
            if st is None:
                return self.version
            if rows:
                try:
                    # nomad-lint: allow[lock-discipline] scatter stays under _l to pair arrays with the version token; jax dispatch is async (never blocks)
                    st = self._scatter(st, table, rows)
                except Exception:
                    # a failed device op must not poison scheduling;
                    # drop the mirror, dense fallback takes over —
                    # counted as a failure, not as a stale miss
                    note_device_op_failure("device_table.scatter")
                    st = None
            if st is not None:
                st = DeviceTableState(self.version, self.epoch, st.n,
                                      st.n_pad, st.capacity, st.used,
                                      st.free_ports)
            self._state = st
            return self.version

    def deltas_since(self, version: int) -> Optional[List[Tuple[int,
                                                                np.ndarray]]]:
        """The journal entries bridging (version, self.version], or None
        when the journal can't (caller re-uploads): the gap predates the
        retained ring, a rebuild cleared the log, or a bridging entry
        was too wide to journal (sentinel)."""
        with self._l:
            if version > self.version:
                return None
            if version == self.version:
                return []
            need = self.version - version
            ent = [e for e in self.delta_log if e[0] > version]
            if len(ent) != need or any(r is None for _v, r in ent):
                return None
            return ent

    def _scatter(self, st: DeviceTableState, table,
                 rows) -> DeviceTableState:
        import jax

        m = len(rows)
        if m > st.n * SPARSE_MAX_FRAC:
            # wide delta: one contiguous upload beats a scatter of most
            # of the table (counts as a fold, resets the debt)
            return self._upload(table, epoch=st.epoch, fold=True)
        idx = np.fromiter(rows, np.int32, m)
        from ..analysis import sanitizer
        if sanitizer.enabled():
            # OOB guard BEFORE padding: on TPU `.at[rows]` silently
            # drops out-of-range rows — the corruption would be mute
            sanitizer.check_rows("device_table.scatter", idx, st.n)
        b = _bucket_rows(m)
        if b > m:
            # pad with repeats of the first row carrying its own value:
            # duplicate .set with an identical payload is deterministic
            idx = np.concatenate([idx, np.full(b - m, idx[0], np.int32)])
        from ..utils import stages

        # dispatch cost only — the scatter itself is async; the
        # interesting signal is rows shipped vs a dense column
        with stages.span("h2d", rows=m):
            used_rows = table.base_used[idx].astype(np.float32)
            port_rows = table.free_ports[idx].astype(np.float32)
            if sanitizer.enabled():
                sanitizer.check_finite("device_table.scatter",
                                       used_rows=used_rows,
                                       port_rows=port_rows)
            used, ports = _scatter_set(st.used, st.free_ports, idx,
                                       used_rows, port_rows)
        self.delta_debt += m
        self.stats["scatters"] += 1
        del jax  # imported for the side effect of a clear failure mode
        return DeviceTableState(st.version, st.epoch, st.n, st.n_pad,
                                st.capacity, used, ports)

    def _upload(self, table, epoch: int, fold: bool) -> DeviceTableState:
        import jax

        from ..utils import stages

        with stages.span("h2d", upload=True):
            n = table.n
            n_pad = _pad_n(n)
            d = table.base_used.shape[1]
            cap = np.zeros((n_pad, d), np.float32)
            cap[:n] = table.capacity
            used = np.zeros((n_pad, d), np.float32)
            used[:n] = table.base_used
            ports = np.zeros(n_pad, np.float32)
            ports[:n] = table.free_ports
            st = DeviceTableState(self.version, epoch, n, n_pad,
                                  jax.device_put(cap),
                                  jax.device_put(used),
                                  jax.device_put(ports))
        # the journal (delta_log) survives uploads on purpose: it is
        # the companion mirrors' replay record, not this mirror's
        # scatter history — only a node-set rebuild invalidates it
        self.delta_debt = 0
        self.stats["folds" if fold else "uploads"] += 1
        return st

    def fold(self, table, version: Optional[int] = None) -> dict:
        """Governor reclaim (fold-to-rebuild): replace the scatter
        history with one contiguous re-upload from the current host
        table. `table` must be the version the mirror tracks (the
        cache passes its latest). No-op when never materialized."""
        with self._l:
            if version is not None and version != self.version:
                return {"folded": False, "reason": "stale table"}
            debt = self.delta_debt
            if self._state is None:
                self.delta_debt = 0
                return {"folded": False, "reason": "not materialized"}
            # nomad-lint: allow[lock-discipline] upload must be atomic with the version token; jax dispatch is async (never blocks under _l)
            self._state = self._upload(table, epoch=self.epoch,
                                       fold=True)
            return {"folded": True, "debt_cleared": debt}

    # -- kernel-side access --------------------------------------------
    def arrays_for(self, table) -> Optional[DeviceTableState]:
        """The device arrays for `table`, or None when the mirror has
        moved past it (stale snapshot -> dense fallback). First valid
        call materializes the mirror from this table (full upload)."""
        token = getattr(table, "device_version", -1)
        with self._l:
            if token != self.version:
                self.stats["stale_misses"] += 1
                return None
            st = self._state
            if st is None:
                try:
                    # nomad-lint: allow[lock-discipline] lazy materialization must pair arrays with the version token; dispatch is async
                    st = self._upload(table, epoch=self.epoch,
                                      fold=False)
                except Exception:
                    note_device_op_failure("device_table.upload")
                    return None
                self._state = st
            return st

    def overlay_used(self, st: DeviceTableState, rows: np.ndarray,
                     deltas: np.ndarray):
        """used0 = resident used + sparse per-eval plan overlay,
        computed on device. Returns a device array (async), or None
        when the overlay is too dense to be worth scattering."""
        m = len(rows)
        if m == 0:
            return st.used
        if m > st.n * SPARSE_MAX_FRAC:
            return None
        idx = np.asarray(rows, np.int32)
        vals = np.asarray(deltas, np.float32)
        from ..analysis import sanitizer
        if sanitizer.enabled():
            sanitizer.check_rows("device_table.overlay", idx, st.n)
            sanitizer.check_finite("device_table.overlay", deltas=vals)
        b = _bucket_rows(m)
        if b > m:
            idx = np.concatenate([idx, np.zeros(b - m, np.int32)])
            vals = np.concatenate(
                [vals, np.zeros((b - m, vals.shape[1]), np.float32)])
        self.stats["overlay_dispatches"] += 1
        return _overlay_add(st.used, idx, vals)

    # -- governor accounting -------------------------------------------
    def debt(self) -> int:
        return self.delta_debt

    def log_len(self) -> int:
        return len(self.delta_log)

    def device_bytes(self) -> int:
        """Bytes the materialized mirror pins on device (capacity +
        used + free_ports buffer sizes; 0 while lazy). Shape metadata
        only — reading .nbytes never syncs the device."""
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
            return {"version": self.version, "epoch": self.epoch,
                    "materialized": self._state is not None,
                    "delta_debt": self.delta_debt,
                    "delta_log": len(self.delta_log), **self.stats}


def resident_request_args(mirror, req, n_pad: int,
                          metric_prefix: str) -> Optional[dict]:
    """Resident replacements for a request's table-shaped kernel inputs
    (capacity, used0, free_ports), shared by the single-device mirror
    (SelectKernel._resident_args) and the mesh-sharded one
    (ShardedSelect.resident_args) — ONE place owns the MVCC gate, the
    overlay fallback, and the free_ports identity rule. `mirror` is
    anything exposing arrays_for/overlay_used. Returns None for stale
    tables, shape mismatches, or overlays too wide to scatter, counting
    `<metric_prefix>_fallback` / `<metric_prefix>_dispatch`."""
    t = req.table
    if t is None or req.used_base_rows is None:
        return None
    from ..utils import metrics
    state = mirror.arrays_for(t)
    if state is None or state.n_pad != n_pad:
        metrics.incr_counter(metric_prefix + "_fallback")
        return None
    used0 = mirror.overlay_used(state, req.used_base_rows,
                                req.used_base_deltas)
    if used0 is None:
        metrics.incr_counter(metric_prefix + "_fallback")
        return None
    out = {"capacity": state.capacity, "used0": used0}
    if req.free_ports is not None and \
            req.free_ports is getattr(t, "free_ports", None):
        out["free_ports"] = state.free_ports
    feas = getattr(mirror, "feas", None)
    tok = getattr(req, "feas_token", None)
    if feas is not None and tok is not None:
        arr = feas.resident(tok, n_pad)
        if arr is not None:
            res = getattr(req, "feas_residue", None)
            if res is not None and len(res[0]):
                # ISSUE 20: the token survived residue mutations —
                # re-apply them on device as one sparse scatter
                # instead of re-uploading the combined mask
                arr = feas.apply_residue(arr, res[0], res[1])
                if arr is not None:
                    metrics.incr_counter(metric_prefix + "_feas_residue")
            if arr is not None:
                out["feasible"] = arr
                metrics.incr_counter(metric_prefix + "_feas_resident")
    metrics.incr_counter(metric_prefix + "_dispatch")
    return out


# jitted scatter kernels: compiled per (n_pad, row-bucket) shape — both
# axes are power-of-two bucketed, so the compile count stays bounded
_JIT_CACHE: Dict[str, object] = {}


def _jit(name: str, fn):
    import jax

    hit = _JIT_CACHE.get(name)
    if hit is None:
        # the program carries `name` on the device (jit_scatter_set on
        # the profiler's XLA Modules line), not the local `fn`
        fn.__name__ = fn.__qualname__ = name
        hit = jax.jit(fn)
        _JIT_CACHE[name] = hit
    return hit


def _scatter_set(used, ports, idx, used_rows, port_rows):
    from ..analysis.sanitizer import traces
    traces.note("scatter_set", (tuple(used.shape), len(idx)))
    def fn(u, p, i, ur, pr):
        return u.at[i].set(ur), p.at[i].set(pr)
    return _jit("scatter_set", fn)(used, ports, idx, used_rows,
                                   port_rows)


def _overlay_add(used, idx, vals):
    from ..analysis.sanitizer import traces
    traces.note("overlay_add", (tuple(used.shape), len(idx)))
    def fn(u, i, v):
        return u.at[i].add(v)
    return _jit("overlay_add", fn)(used, idx, vals)


def _feas_scatter(mask, idx, vals):
    from ..analysis.sanitizer import traces
    traces.note("feas_scatter", (tuple(mask.shape), len(idx)))
    def fn(m, i, v):
        return m.at[i].set(v)
    return _jit("feas_scatter", fn)(mask, idx, vals)
