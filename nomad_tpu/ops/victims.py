"""The victims' columns and the whole-fleet victim selection program.

Preemption asks one question of every full node at once: which of its
lower-priority residents would have to go for one more instance of the
ask to fit, and how good a home would the node then be
(scheduler/preemption.go: filterAndGroupPreemptibleAllocs,
basicResourceDistance, filterSuperset; rank.go preemptionScore). The
answer used to be gathered from Python `Allocation` lists, node by node,
before every select. Here it is two things:

`VictimColumns` — per node row, the residents that carry a job as padded
slots: (priority, cpu, memory, disk, group code, max_parallel), one
float32 array [6, n_pad, slots] on the device, and on the host only the
slot -> Allocation lists the plan needs victims' ids from. One object a
table version (the columns are functional: a refresh scatters the rows a
commit touched into a NEW array, so a snapshot an eval still reads keeps
its own). Built on the first demand (a cluster that never preempts never
pays) and advanced from the nearest version that had them.

`select_victims` — `_select_victims_fn`, ONE jitted program over
[n_pad, slots]: the priority-delta filter, lowest band first, greedy
closest distance with every node stepping in lockstep, the superset
drop, bin-pack after eviction and the logistic score. Its `used_after`
and `pre_score` stay on the device and feed the select that follows;
the host fetches two counters, and later the winners' slots.

float32 throughout, where the per-node `Preemptor` runs Python floats:
resources are whole MHz / MB (exact below 2^24), so fits, sums and
victim sets are the same; scores differ by rounding alone
(tests/test_victims_program.py states the tolerance).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.locks import make_lock
from .device_table import _bucket_rows, _jit, _pad_n

F_PRIO, F_CPU, F_MEM, F_DISK, F_GRP, F_MP = range(6)
N_FIELDS = 6
SLOTS_MIN = 8
# widest row the columns hold (ServerConfig.preempt_rows_max lowers it);
# a node with more job-carrying residents keeps an empty row and takes
# the per-node Preemptor (the matrix would pad every other node to its
# width)
SLOTS_MAX = 256
# (group code, count) pairs the program takes for the max_parallel
# penalty: the groups the plan has ALREADY preempted from. More than
# this and the round takes the per-node path
GROUP_COUNTS_MAX = 16
PRIORITY_DELTA = 10.0
MAX_PARALLEL_PENALTY = 50.0
# victims the program picks on one node before it hands the node to the
# host (the per-node Preemptor): everything after the greedy loop works
# on [n_pad, PICKS_MAX] columns written down pick by pick, not on
# [n_pad, slots] (a sort and three gathers that wide took 36 ms of the
# chip a dispatch at 16,384 x 128; PERF.md section 6, PR 34)
PICKS_MAX = 16

# (namespace, job id, task group) -> code, for groups that carry a
# max_parallel alone (code 0 = none). Append-only and shared by every
# version: a code never changes its meaning
_GROUPS: Dict[Tuple[str, str, str], int] = {}
_L = make_lock()


def _group_code(key: Tuple[str, str, str]) -> int:
    code = _GROUPS.get(key)
    if code is None:
        with _L:
            code = _GROUPS.setdefault(key, len(_GROUPS) + 1)
    return code


def group_codes(keys) -> List[int]:
    """Codes of the groups among `keys` that any column knows (a group
    nobody interned sits in no slot)."""
    return [_GROUPS[k] for k in keys if k in _GROUPS]


def _slots_for(widest: int, cap: int) -> int:
    """Slot count for a fleet whose fullest row holds `widest`: a
    quarter of headroom, a power of two, at most `cap`."""
    want = widest + widest // 4 + 1
    s = 1
    while s < max(want, SLOTS_MIN) and s * 2 <= cap:
        s *= 2
    return s


def _row(snapshot, node_id: str):
    """One node's slots: its live allocations that carry a job, in the
    store's own order (the order the per-node Preemptor walks, so ties
    break alike), each with its six fields."""
    from ..state.alloc_index import alloc_max_parallel, alloc_usage_vec
    allocs, fields, mp_groups = [], [], None
    for a in snapshot.allocs_by_node(node_id):
        job = a.job
        if job is None or a.terminal_status():
            continue
        u = alloc_usage_vec(a)
        mp = alloc_max_parallel(a)
        code = 0
        if mp > 0:
            key = (a.namespace, a.job_id, a.task_group)
            code = _group_code(key)
            if mp_groups is None:
                mp_groups = set()
            mp_groups.add(key)
        allocs.append(a)
        fields.append((float(job.priority), u[0], u[1], u[2],
                       float(code), float(mp)))
    return tuple(allocs), fields, mp_groups


def _empty(n_rows: int, slots: int) -> np.ndarray:
    out = np.zeros((N_FIELDS, n_rows, slots), np.float32)
    out[F_PRIO] = np.inf          # no resident: never eligible
    return out


class VictimColumns:
    """The columns of ONE table version (see the module's docstring)."""

    __slots__ = ("n", "n_pad", "slots", "cols", "rows", "over",
                 "mp_groups", "refreshed")

    def __init__(self, n, n_pad, slots, cols, rows, over, mp_groups,
                 refreshed):
        self.n = n
        self.n_pad = n_pad
        self.slots = slots
        self.cols = cols                # device f32[6, n_pad, slots]
        self.rows = rows                # per node: tuple of Allocations
        self.over = over                # rows wider than `slots`
        self.mp_groups = mp_groups      # row -> groups with max_parallel
        self.refreshed = refreshed      # rows re-derived to get here

    @classmethod
    def build(cls, table, snapshot,
              slots_max: int = SLOTS_MAX) -> "VictimColumns":
        import jax
        n = table.n
        n_pad = _pad_n(n)
        rows: List[tuple] = []
        flat: List[tuple] = []
        counts = np.zeros(n, np.int32)
        mp_groups: Dict[int, frozenset] = {}
        for i, node_id in enumerate(table.ids):
            allocs, fields, mp = _row(snapshot, node_id)
            rows.append(allocs)
            counts[i] = len(allocs)
            flat.extend(fields)
            if mp:
                mp_groups[i] = frozenset(mp)
        slots = _slots_for(int(counts.max()) if n else 0,
                           max(1, min(slots_max, SLOTS_MAX)))
        dense = _empty(n_pad, slots)
        over = frozenset(np.nonzero(counts > slots)[0].tolist())
        if flat:
            fa = np.asarray(flat, np.float32)
            r_idx = np.repeat(np.arange(n), counts)
            offs = np.concatenate(([0], np.cumsum(counts)[:-1]))
            s_idx = np.arange(len(flat)) - np.repeat(offs, counts)
            keep = s_idx < slots
            if over:
                keep &= ~np.isin(r_idx, list(over))
            dense[:, r_idx[keep], s_idx[keep]] = fa[keep].T
        return cls(n, n_pad, slots, jax.device_put(dense), rows, over,
                   mp_groups, n)

    def advance(self, table, snapshot, touched) -> "VictimColumns":
        """The columns of a later version of the same node set: the
        `touched` rows re-derived from `snapshot`, the rest shared."""
        touched = sorted(touched)
        m = len(touched)
        if m == 0:
            return VictimColumns(self.n, self.n_pad, self.slots,
                                 self.cols, self.rows, self.over,
                                 self.mp_groups, 0)
        rows = self.rows[:]
        over = set(self.over)
        mp_groups = dict(self.mp_groups)
        b = _bucket_rows(max(m, ROWS_FLOOR))
        vals = _empty(b, self.slots)
        ids = table.ids
        for p, i in enumerate(touched):
            allocs, fields, mp = _row(snapshot, ids[i])
            rows[i] = allocs
            if mp:
                mp_groups[i] = frozenset(mp)
            else:
                mp_groups.pop(i, None)
            if len(allocs) > self.slots:
                over.add(i)
                continue
            over.discard(i)
            if fields:
                vals[:, p, :len(fields)] = np.asarray(fields,
                                                      np.float32).T
        idx = np.asarray(touched, np.int32)
        if b > m:
            # pad with repeats of the first row carrying its own
            # values: a duplicate .set of one payload is deterministic
            idx = np.concatenate([idx, np.full(b - m, idx[0], np.int32)])
            vals[:, m:] = vals[:, :1]
        cols = _victims_scatter(self.cols, idx, vals)
        return VictimColumns(self.n, self.n_pad, self.slots, cols, rows,
                             frozenset(over), mp_groups, m)

    def slot_of(self, row: int, alloc) -> int:
        """The slot `alloc` sits in on `row`, or -1."""
        aid = alloc.id
        for s, a in enumerate(self.rows[row]):
            if a is alloc or a.id == aid:
                return s
        return -1

    def device_bytes(self) -> int:
        return int(getattr(self.cols, "nbytes", 0))


def _victims_scatter(cols, idx, vals):
    from ..analysis.sanitizer import traces
    traces.note("victims_scatter", (tuple(cols.shape), len(idx)))

    def fn(c, i, v):
        return c.at[:, i].set(v)
    return _jit("victims_scatter", fn)(cols, idx, vals)


# -- the program --------------------------------------------------------

def _distance(num, den):
    """basic_resource_distance over a LEADING axis of three (cpu,
    memory, disk): each term (den - num) / den where den > 0, else 0;
    the scalar's sum order (memory, cpu, then disk)."""
    import jax.numpy as jnp
    pos = den > 0.0
    t = jnp.where(pos, (den - num) / jnp.where(pos, den, 1.0), 0.0)
    t = t * t
    return jnp.sqrt(t[1] + t[0] + t[2])


def _select_victims_fn(cols, capacity, used0, mask, ask, job_prio,
                       dead_rows, dead_slots, grp_codes, grp_counts,
                       ovr_rows, ovr_pre, ovr_freed, ovr_score,
                       with_counts: bool, picks_max: int):
    """Victim sets and scores of every candidate node at once.

    cols f32[6, N, S]; capacity / used0 f32[N, D]; mask bool[N] (static
    feasibility); ask f32[D]; job_prio f32; dead_* i32[K]: slots the
    plan already stops or preempts and the placing job's own (never a
    candidate; their usage is in `used0` or out of it as the plan has
    it); grp_* [G]: preemptions the plan already holds per group, for
    the max_parallel penalty; ovr_*: rows the host evaluated itself
    (wider than S), laid over the result. A padding index of N drops.

    Returns (pre_score f32[N] — the logistic, 0 where no eviction —,
    used_after f32[N, D], score f32[N] (-1: no fit by eviction), freed
    f32[N, D], kept bool[N, picks_max] and perm i32[N, picks_max]: the
    victims' slots are perm where kept, in victim order, counters
    i32[4]: candidates, victims,
    eligible slots, unfinished nodes; unfinished bool[N]: nodes still
    short of the ask after picks_max picks, which the host evaluates
    itself and hands back as `ovr_*`).

    The three resources ride a LEADING axis ([3, N, S], [3, N]): a
    trailing axis of three would be padded to a vector register's
    width on the chip."""
    import jax
    import jax.numpy as jnp

    n, s_w = cols.shape[1], cols.shape[2]
    d = capacity.shape[1]
    inf = jnp.float32(np.inf)
    prio = cols[F_PRIO].at[dead_rows, dead_slots].set(inf, mode="drop")
    c3 = cols[F_CPU:F_DISK + 1]                     # [3, N, S]
    ask3 = ask[:3]
    cap3 = capacity[:, :3].T                        # [3, N]
    used3 = used0[:, :3].T

    fits = jnp.all(used0 + ask[None, :] <= capacity + 1e-6, axis=1)
    cand = mask & ~fits
    # filterAndGroupPreemptibleAllocs: a victim's job is at least 10
    # lower in priority (an empty slot's priority is +inf)
    eligible = (job_prio - prio >= PRIORITY_DELTA) & cand[:, None]

    if with_counts:
        grp, mp = cols[F_GRP], cols[F_MP]
        cnp = jnp.zeros_like(grp)
        for k in range(grp_codes.shape[0]):
            cnp = cnp + jnp.where(grp == grp_codes[k], grp_counts[k], 0.0)
        penalty = jnp.where((mp > 0) & (cnp >= mp),
                            (cnp + 1.0 - mp) * MAX_PARALLEL_PENALTY, 0.0)
    else:
        penalty = jnp.zeros((n, s_w), jnp.float32)

    remaining0 = cap3 - used3
    slot_ids = jnp.arange(s_w, dtype=jnp.int32)[None, :]
    t_w = min(picks_max, s_w)
    pick_ids = jnp.arange(t_w, dtype=jnp.int32)[None, :]

    def cond(state):
        return jnp.any(state[3]) & (state[4] < t_w)

    def body(state):
        needed, avail, selected, alive, step, ok, seq = state
        open_ = eligible & ~selected
        alive = alive & jnp.any(open_, axis=1)   # exhausted: no fit
        # the lowest band still unselected; each ascending group is
        # consumed to exhaustion before the next
        band = jnp.min(jnp.where(open_, prio, inf), axis=1)
        in_band = open_ & (prio == band[:, None])
        dist = _distance(c3, needed[:, :, None]) + penalty
        dist = jnp.where(in_band, dist, inf)
        # argmin keeps the first minimum: the scalar loop's strict `<`
        pick = jnp.argmin(dist, axis=1).astype(jnp.int32)
        hit = (slot_ids == pick[:, None]) & alive[:, None]
        selected = selected | hit
        pv3 = jnp.sum(jnp.where(hit[None], c3, 0.0), axis=2)    # [3, N]
        # the pick, written down in the order picked: the rest of the
        # program reads these [N, PICKS_MAX] columns, not [N, S]
        here = (pick_ids == step) & alive[:, None]
        seq = (jnp.where(here, pick[:, None], seq[0]),
               jnp.where(here, band[:, None], seq[1]),
               jnp.where(here[None], pv3[:, :, None], seq[2]))
        avail = avail + pv3
        needed = needed - pv3
        met = jnp.all(avail >= ask3[:, None], axis=0) & alive
        return (needed, avail, selected, alive & ~met, step + 1,
                ok | met, seq)

    state = (jnp.broadcast_to(ask3[:, None], (3, n)), remaining0,
             jnp.zeros((n, s_w), bool), jnp.any(eligible, axis=1),
             jnp.int32(0), jnp.zeros(n, bool),
             (jnp.zeros((n, t_w), jnp.int32),
              jnp.zeros((n, t_w), jnp.float32),
              jnp.zeros((3, n, t_w), jnp.float32)))
    _needed, _avail, selected, unfinished, _step, ok, seq = \
        jax.lax.while_loop(cond, body, state)
    seq_slot, seq_prio, seq3 = seq
    nvict = jnp.sum(selected, axis=1).astype(jnp.int32)
    picked = pick_ids < nvict[:, None]

    # filterSuperset: by distance to the ask, descending, stable over
    # the order picked; then the shortest prefix that still meets it
    dfull = _distance(ask3[:, None, None], seq3)
    key1 = jnp.where(picked, -dfull, inf)
    _k1, _k2, perm, pr_s, s_cpu, s_mem, s_disk = jax.lax.sort(
        (key1, jnp.broadcast_to(pick_ids, (n, t_w)), seq_slot, seq_prio,
         seq3[0], seq3[1], seq3[2]), dimension=1, num_keys=2)
    cum3 = jnp.cumsum(jnp.where(picked[None],
                                jnp.stack([s_cpu, s_mem, s_disk]), 0.0),
                      axis=2)                               # [3, N, T]
    met_pref = jnp.all(remaining0[:, :, None] + cum3
                       >= ask3[:, None, None], axis=0) & picked
    keep = jnp.where(jnp.any(met_pref, axis=1),
                     jnp.argmax(met_pref, axis=1).astype(jnp.int32) + 1,
                     nvict)
    kept = pick_ids < keep[:, None]         # the prefix, in victim order
    at_keep = pick_ids == jnp.maximum(keep - 1, 0)[:, None]
    freed3 = jnp.sum(jnp.where(at_keep[None], cum3, 0.0), axis=2)

    # ScoreFitBinPack over the usage after eviction + the ask
    node_cpu, node_mem = cap3[0], cap3[1]
    util_cpu = used3[0] - freed3[0] + ask3[0]
    util_mem = used3[1] - freed3[1] + ask3[1]
    free_cpu = jnp.where(node_cpu != 0.0, 1.0 - util_cpu
                         / jnp.where(node_cpu != 0.0, node_cpu, 1.0), 0.0)
    free_mem = jnp.where(node_mem != 0.0, 1.0 - util_mem
                         / jnp.where(node_mem != 0.0, node_mem, 1.0), 0.0)
    total = jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem)
    binpack = jnp.clip(20.0 - total, 0.0, 18.0) / 18.0

    # netPriority + the logistic preemptionScore over the kept set
    pr_s = jnp.where(kept, pr_s, 0.0)
    mx = jnp.max(pr_s, axis=1)
    tot = jnp.sum(pr_s, axis=1)
    netp = jnp.where(mx != 0.0, mx + tot / jnp.where(mx != 0.0, mx, 1.0),
                     0.0)
    logistic = 1.0 / (1.0 + jnp.exp(0.0048 * (netp - 2048.0)))

    pre = jnp.where(ok, logistic, 0.0)
    score = jnp.where(ok, (binpack + logistic) / 2.0, -1.0)
    freed = jnp.zeros((n, d), jnp.float32).at[:, :3].set(
        jnp.where(ok[:, None], freed3.T, 0.0))
    keep = jnp.where(ok, keep, 0)
    kept = kept & ok[:, None]
    # the rows the host evaluated itself (only among candidates: the
    # host hands over no others)
    pre = pre.at[ovr_rows].set(ovr_pre, mode="drop")
    score = score.at[ovr_rows].set(ovr_score, mode="drop")
    freed = freed.at[ovr_rows].set(ovr_freed, mode="drop")
    used_after = jnp.maximum(used0 - freed, 0.0)
    counters = jnp.stack([jnp.sum(cand).astype(jnp.int32),
                          jnp.sum(keep).astype(jnp.int32),
                          jnp.sum(eligible).astype(jnp.int32),
                          jnp.sum(unfinished).astype(jnp.int32)])
    return (pre, used_after, score, freed, kept, perm, counters,
            unfinished)


def _select_victim_rows_fn(pre, kept, perm, rows):
    """The winners' rows of the selection, for the plan."""
    return pre[rows], kept[rows], perm[rows]


_PROGRAM: List[object] = []


def _program():
    """The jitted program. Its device name, jit__select_victims_fn,
    has `select` in it: that files it with the placement programs of
    the profiler's XLA Modules line."""
    if not _PROGRAM:
        import jax
        _PROGRAM.append(jax.jit(_select_victims_fn,
                                static_argnames=("with_counts",
                                                 "picks_max")))
    return _PROGRAM[0]


# the least every index list is padded to: a wider bucket is another
# compile, and what varies eval by eval (the winners, the slots a plan
# takes out, the rows a commit touched) should not meet one in a
# serving window
IDX_FLOOR = 64
ROWS_FLOOR = 256


def _pad_idx(values, fill: int, floor: int = IDX_FLOOR) -> np.ndarray:
    out = np.full(_bucket_rows(max(len(values), floor)), fill, np.int32)
    out[:len(values)] = values
    return out


class VictimSelection:
    """One dispatch's results, still on the device."""

    __slots__ = ("pre_score", "used_after", "score", "freed", "kept",
                 "perm", "counters", "unfinished", "capacity", "n")

    def __init__(self, outs, capacity, n):
        (self.pre_score, self.used_after, self.score, self.freed,
         self.kept, self.perm, self.counters, self.unfinished) = outs
        self.capacity = capacity
        self.n = n

    def winners(self, rows) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(pre_score, victims' slots) of `rows`, fetched."""
        import jax
        idx = _pad_idx(rows, 0)
        out = _jit("_select_victim_rows_fn", _select_victim_rows_fn)(
            self.pre_score, self.kept, self.perm, idx)
        # nomad-lint: allow[host-sync] the winners' slots: the one fetch a preempting select adds, reported inside select_finish
        pre, kept, perm = jax.device_get(out)
        return pre[:len(rows)], [perm[k][kept[k]].tolist()
                                 for k in range(len(rows))]

    def fetch_all(self):
        """Every row's (pre_score, score, freed, victims' slots) on
        the host: the round's host API (tests, the mesh route)."""
        import jax
        # nomad-lint: allow[host-sync] the host API's whole fetch (tests, the mesh route): inside the preempt span
        pre, score, freed, kept, perm = jax.device_get(
            (self.pre_score, self.score, self.freed, self.kept,
             self.perm))
        n = self.n
        return (pre[:n], score[:n], freed[:n],
                [perm[i][kept[i]].tolist() for i in range(n)])


def _table_arrays(table, used: Optional[np.ndarray], proposed, n_pad: int):
    """(capacity, used0) for the program: off the table's device mirror
    with the plan's overlay scattered on the device while the mirror
    still holds this version, else padded from the host."""
    if used is None and proposed is not None and proposed.table is table:
        mirror = getattr(table, "device_mirror", None)
        if mirror is not None:
            state = mirror.arrays_for(table)
            if state is not None and state.n_pad == n_pad:
                rows, deltas = proposed.used_sparse()
                used0 = mirror.overlay_used(state, rows, deltas)
                if used0 is not None:
                    return state.capacity, used0
    if used is None:
        used = proposed.used()
    n, d = table.capacity.shape
    cap = np.zeros((n_pad, d), np.float32)
    cap[:n] = table.capacity
    u0 = np.zeros((n_pad, d), np.float32)
    u0[:n] = used
    return cap, u0


def select_victims(vc: VictimColumns, table, mask, ask, job_priority,
                   dead, group_counts, overrides, *, used=None,
                   proposed=None) -> VictimSelection:
    """Dispatch the program over `vc` (asynchronous: nothing is fetched
    here). `dead`: (row, slot) pairs; `group_counts`: {code: count};
    `overrides`: {row: (pre_score, score, freed[D])} for rows the host
    evaluated; `used` dense from the host, or `proposed` to take it off
    the device mirror."""
    from ..analysis.sanitizer import traces
    n_pad, d = vc.n_pad, table.capacity.shape[1]
    capacity, used0 = _table_arrays(table, used, proposed, n_pad)
    m = np.zeros(n_pad, bool)
    m[:vc.n] = mask
    ask4 = np.zeros(d, np.float32)
    ask4[:len(ask)] = ask
    dead_rows = _pad_idx([r for r, _s in dead], n_pad)
    dead_slots = _pad_idx([s for _r, s in dead], 0)
    with_counts = bool(group_counts)
    codes = np.full(GROUP_COUNTS_MAX, -1.0, np.float32)
    counts = np.zeros(GROUP_COUNTS_MAX, np.float32)
    for k, (code, cnt) in enumerate(group_counts.items()):
        codes[k], counts[k] = code, cnt
    ovr_rows = _pad_idx(list(overrides), n_pad, 8)
    k_o = len(ovr_rows)
    ovr_pre = np.zeros(k_o, np.float32)
    ovr_score = np.full(k_o, -1.0, np.float32)
    ovr_freed = np.zeros((k_o, d), np.float32)
    for k, (pre, score, freed) in enumerate(overrides.values()):
        ovr_pre[k], ovr_score[k] = pre, score
        ovr_freed[k, :len(freed)] = freed
    traces.note("select_victims", (n_pad, vc.slots, len(dead_rows), k_o,
                                   with_counts))
    outs = _program()(
        vc.cols, capacity, used0, m, ask4, np.float32(job_priority),
        dead_rows, dead_slots, codes, counts,
        ovr_rows, ovr_pre, ovr_freed, ovr_score, with_counts=with_counts,
        picks_max=PICKS_MAX)
    return VictimSelection(outs, capacity, vc.n)
