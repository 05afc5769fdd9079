"""Vectorized spread / distinct-property scoring inputs (ISSUE 20).

`_spread_inputs` and `_distinct_prop_inputs` were the last per-eval
O(N)-Python stages on the select path: every eval re-walked the
proposed-alloc lists per spread attribute (ProposedIndex.
property_counts) and every table rebuild re-ran the O(N) Python
dictionary encoding (NodeTable.attr_codes). This module replaces both
with array passes:

  - `attr_codes_fast` derives the table's dictionary encoding from the
    write-through interned columns (state/node_attr_index.py) — one
    np.take through the index->table permutation plus an np.unique to
    reproduce attr_codes' first-encounter-order numbering EXACTLY, so
    downstream kernel state is bit-identical. The interned column
    survives table rebuilds (it is maintained per changed row), so a
    node update no longer costs an O(N) re-encode per attribute;
  - `property_counts_vec` turns the per-alloc Python walk into one
    scatter-add over the proposed rows' attribute codes
    (np.add.at), with desired-percent deltas broadcast per unique
    value by the caller;
  - `distinct_uncontended` folds distinct_hosts/distinct_property into
    a plan-time verdict for single-placement evals: one vectorized
    check over the proposed node/property codes replaces the in-kernel
    per-step gating when no proposed alloc contends (the state ships
    only when it can actually fire).

Everything is gated by the ISSUE 20 residue kill switch
(`NOMAD_TPU_FEAS_RESIDUE=0` / ServerConfig.feas_residue=false restores
the scalar builds), and the scalar twins stay in
scheduler/stack.py + ops/tables.py as the fallback and parity
reference (tests/test_feas_residue.py pins 1k-seed bit-parity).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

STATS: Dict[str, int] = {
    "spread_score_evals": 0,   # vectorized count/present builds
    "vector_builds": 0,        # spread/distinct input sets built vectorized
    "scalar_builds": 0,        # ... built on the scalar fallback
    "codes_vec_builds": 0,     # attr_codes derived from interned columns
    "codes_fallbacks": 0,      # attr_codes fell back to the O(N) encode
    "distinct_folds": 0,       # distinct state folded to plan-time verdict
}


def enabled() -> bool:
    from ..scheduler import feasible_compiler
    return feasible_compiler.residue_enabled()


def note_build() -> None:
    """Count one eval's spread/distinct input build for the active arm
    (called by the stack after both paths; the spread_inputs span times
    the build)."""
    if enabled():
        STATS["vector_builds"] += 1
    else:
        STATS["scalar_builds"] += 1


def stats() -> Dict[str, int]:
    return dict(STATS)


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


# -- dictionary encoding off the interned columns ----------------------

# the targets the attr index interns (feasible_compiler._resolve's
# column gate); anything else stays on the table's own encoder
_COLUMN_TARGETS = ("${node.unique.id}", "${node.datacenter}",
                   "${node.unique.name}", "${node.class}")


def _interned_codes(table, attribute: str, snapshot):
    """(codes i32[N], values) in the table's first-encounter-order
    numbering, derived from the write-through interned column, or None
    (caller falls back to NodeTable.attr_codes)."""
    if not (attribute in _COLUMN_TARGETS
            or attribute.startswith("${attr.")
            or attribute.startswith("${meta.")):
        return None
    store = getattr(snapshot, "_store", None) if snapshot is not None \
        else None
    if store is None:
        return None
    cache = getattr(store, "attr_index", None)
    if cache is None or not cache.enabled:
        return None
    if cache.needs_build():
        cache.build_install(snapshot)
    with cache.lock:
        idx = cache.synced(snapshot)
        if idx is None:
            return None
        col = idx.column(attribute)
        if col.overflow:
            return None
        perm, _inv = idx.perm_for(table.ids)
        if perm is None:
            return None
        # snapshot the aligned codes under the lock; the numbering
        # pass below is pure array work on the copy
        col_t = col.codes[:idx.n][perm].copy()
        values_src = list(col.values)
    n = table.n
    pos = np.flatnonzero(col_t >= 0)
    if pos.size == 0:
        return np.zeros(n, dtype=np.int32), []
    cds = col_t[pos]
    # attr_codes numbers values by first encounter in table-row order;
    # np.unique(return_index) hands us each intern code's first
    # position, and ranking those positions reproduces the numbering
    uniq, first = np.unique(cds, return_index=True)
    order = np.argsort(first, kind="stable")
    lut = np.empty(len(values_src), dtype=np.int32)
    lut[uniq[order]] = np.arange(len(uniq), dtype=np.int32)
    values = [values_src[int(c)] for c in uniq[order]]
    codes = np.full(n, len(values), dtype=np.int32)
    codes[pos] = lut[cds]
    return codes, values


def attr_codes_fast(table, attribute: str, snapshot
                    ) -> Tuple[np.ndarray, List[str]]:
    """NodeTable.attr_codes semantics, preferring the interned-column
    derivation. The result lands in the table's own cache under the
    same key, so ProposedIndex.property_counts' identity check
    (`tvals is values`) keeps holding for every later consumer."""
    hit = table._attr_codes_cache.get(attribute)
    if hit is not None:
        return hit
    built = _interned_codes(table, attribute, snapshot)
    if built is None:
        STATS["codes_fallbacks"] += 1
        return table.attr_codes(attribute)
    STATS["codes_vec_builds"] += 1
    table._attr_codes_cache[attribute] = built
    return built


def attr_present_mask(table, attribute: str, snapshot
                      ) -> Optional[np.ndarray]:
    """bool[N]: the node carries a value for `attribute` — presence
    read straight off the interned column (code != -1), or None to
    fall back to the per-node walk. Backs the CSI plugin-attr residue
    mask so a table rebuild costs O(1) numpy, not O(N) Python."""
    built = _interned_codes(table, attribute, snapshot)
    if built is None:
        return None
    codes, values = built
    return codes != len(values)


# -- proposed-alloc counts as one scatter ------------------------------

def property_counts_vec(proposed, tcodes: np.ndarray, n_values: int,
                        tg_name: Optional[str]
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """(counts f32[C+1], present bool[C+1]) — the vectorized twin of
    ProposedIndex.property_counts for the identity-mapped case: one
    gather of the proposed rows' codes and one np.add.at. Index C is
    the missing-attribute bucket (never counted, like the scalar
    walk's `continue`)."""
    rows, tgs = proposed.prop_arrays()
    counts = np.zeros(n_values + 1, dtype=np.float32)
    if rows.size:
        if tg_name is not None:
            rows = rows[tgs == tg_name]
        cds = tcodes[rows]
        cds = cds[cds != n_values]
        if cds.size:
            np.add.at(counts, cds, np.float32(1.0))
    present = counts > 0
    STATS["spread_score_evals"] += 1
    return counts, present


# -- plan-time distinct fold -------------------------------------------

def distinct_uncontended(mask: np.ndarray, job_count: np.ndarray,
                         distinct_props: List[Dict]) -> bool:
    """True when a SINGLE placement's distinct_hosts/distinct_property
    gates can never fire on any feasible node — the per-eval plan-time
    verdict (one scatter's worth of vectorized reads over the proposed
    node/property counts) that lets the request drop the per-step
    kernel state entirely. Only valid for count==1: multi-placement
    batches self-collide in-kernel and need the live counters."""
    if mask.any() and np.any(job_count[mask] != 0):
        return False
    for dp in distinct_props:
        counts, codes = dp["counts"], dp["codes"]
        if mask.any() and np.any(counts[codes[mask]] + 1.0 > dp["limit"]):
            return False
    return True
