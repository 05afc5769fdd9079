"""The fused placement kernel.

One device dispatch replaces the reference's entire per-placement
iterator chain (stack.go Select -> feasible -> BinPack -> scorers ->
Limit -> MaxScore) AND the outer per-alloc loop: a `lax.scan` places all
`count` instances of a task group sequentially *on device*, with each
step seeing the previous steps' placements (usage, anti-affinity
collisions, spread histograms, distinct-hosts/-property counts carried
through the scan). Score semantics mirror:

  - bin-pack / spread fit    structs/funcs.go ScoreFitBinPack:174 (/18)
  - job anti-affinity        rank.go:502  (-(collisions+1)/desired_count)
  - reschedule penalty       rank.go:564  (-1 on penalty nodes)
  - node affinity            rank.go:637  (sum(w*match)/sum|w|)
  - spread                   spread.go:110 (targeted + even-spread boost)
  - normalization            rank.go:696  (mean over *fired* scorers)
  - selection                select.go MaxScoreIterator -> full argmax
                             (no log2(n) sampling: the whole node axis
                             is scored at once, SURVEY.md §2.6)

Shapes are padded to buckets to bound recompilation:
  N -> next power of two; steps K -> bucket; spreads S, distinct-property
  P, codes C -> fixed maxima. Padded lanes carry zero weight.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from ..utils import stages
from ..utils.locks import make_lock

# JIT shape-cache bound (governor accounting): every distinct
# (steps, spreads, distinct, lane) shape bucket compiles and caches an
# XLA executable; maxsize turns the open-ended dict into a true
# shape-LRU so a long-running server's kernel cache stays bounded and
# evictions free the executables with the dropped reference
KERNEL_CACHE_MAX = int(os.environ.get("NOMAD_TPU_KERNEL_CACHE_MAX",
                                      "128"))

S_MAX = 4       # max spread stanzas per task group
P_MAX = 4       # max distinct_property constraints
C_MAX = 64      # max distinct attribute values per spread/property axis
NEG_INF = -1e30
TOP_K = 5       # ScoreMetaData entries kept (reference kheap topK)
CHUNK_J = 256   # max instances placed on one node per chunked step
KWAY_W = 32     # winners per phase at small tables (floor for _kway_w)
VICTIMS_SCAN_STEPS = 64   # least scan length of a request that carries
                          # the victims' columns (SelectRequest.victims)
KWAY_STEPS = 256  # phases per dispatch: ~56 cover a 10k batch; the out
                  # buffers are [steps, 2w+...] ints copied back on every
                  # dispatch. Not re-measured on a local chip.


def _kway_steps(w: int) -> int:
    """Phase budget per dispatch. Wide phases need fewer steps for the
    same count, and the out buffers ([steps, 2w+...] ints) are copied
    back on every dispatch — half the rows at w>=128 halves that
    transfer; overflow continues from the device-resident carry. The
    budget has not been re-measured on a local chip."""
    return KWAY_STEPS if w <= KWAY_W else 128


def _kway_w(n_pad: int) -> int:
    """Winners per K-way phase, scaled with the table. On a
    near-homogeneous table the waterline rule yields chunk≈1 per
    winner, so a batch takes ~count/W sequential phases; at 65536 rows
    top_k(N, 257) costs barely more than top_k(N, 33) while cutting
    phases 8x (round-5 profile: 10k placements @50k nodes spent 0.6 s
    in ~320 phases at W=32)."""
    if n_pad <= 4096:
        return 32
    return 128      # sweep @65536 rows: W 64-256 all ~0.21 s for a 10k
                    # batch (steps scale down, per-phase cost up); 512+
                    # regress on the [W, CHUNK_J] stream block


def _pad_n(n: int) -> int:
    p = 8
    while p < n:
        p *= 2
    return p


def _bucket_k(k: int) -> int:
    """Scan length bucket: each bucket is its own XLA compile, and a
    padded step costs one masked pass over the node axis. Powers of two
    up to 1024, then multiples of 1024. The bucket sizes have not been
    re-measured on a local chip."""
    if k <= 1024:
        b = 1
        while b < k:
            b *= 2
        return b
    return min(-(-k // 1024) * 1024, 65536)


@dataclasses.dataclass
class SelectRequest:
    """Host-side inputs for placing `count` instances of one task group."""
    ask: np.ndarray                  # f32[D] cpu/mem/disk[/mbits] per instance
    count: int
    feasible: np.ndarray             # bool[N] all static checks combined
    capacity: np.ndarray             # f32[N,D]
    used: np.ndarray                 # f32[N,D] live + plan overlay
    desired_count: float             # anti-affinity denominator (tg count)
    tg_collisions: np.ndarray        # i32[N] proposed allocs of job+tg
    job_count: np.ndarray            # i32[N] proposed allocs of job
    distinct_hosts: bool = False
    penalty: Optional[np.ndarray] = None        # bool[N]
    affinity: Optional[np.ndarray] = None       # f32[N] weighted sum
    affinity_sum_weights: float = 0.0
    algorithm: str = "binpack"       # "binpack" | "spread"
    scan_exclusive: bool = False     # reserved-port ask: one instance/node/scan
    port_need: float = 0.0
    free_ports: Optional[np.ndarray] = None     # f32[N]
    port_ok: Optional[np.ndarray] = None        # bool[N]
    # device dimension (scheduler/devices.py): placements-remaining
    # slots per node (consumed 1 per placement), the "devices" scorer
    # column, and whether that scorer fires (any ask has affinities)
    dev_slots: Optional[np.ndarray] = None      # f32[N]
    dev_score: Optional[np.ndarray] = None      # f32[N]
    dev_fires: bool = False
    # preemption competition (rank.go:415-448 + PreemptionScoringIterator
    # :714): nodes whose fit comes from evicting victims carry the
    # logistic preemption score as an extra fired scorer; `used` must
    # already reflect the hypothetical evictions for those nodes.
    # 0 = no preemption on this node (the logistic is never exactly 0).
    pre_score: Optional[np.ndarray] = None      # f32[N]
    # spreads: list of dicts with codes i32[N], counts f32[C+1],
    #          present bool[C+1], desired f32[C+1] (-1 == none),
    #          has_implicit, implicit_desired, weight, has_targets
    spreads: List[Dict] = dataclasses.field(default_factory=list)
    sum_spread_weights: float = 0.0
    # distinct_property: list of dicts with codes i32[N], counts f32[C+1],
    #          limit f32
    distinct_props: List[Dict] = dataclasses.field(default_factory=list)
    # nodes actually under consideration (ready + in the eval's DCs);
    # the resident table holds ALL nodes, so metrics must not count
    # down/foreign-DC rows as evaluated (AllocMetric semantics)
    n_considered: Optional[int] = None
    # device-resident dispatch (ops/device_table.py): the host
    # NodeTable whose mirror token may let this dispatch reuse the
    # device copies of capacity/used/free_ports, plus the per-eval
    # plan overlay in sparse (rows, deltas) form so `used0` is
    # computed ON DEVICE from the resident base instead of shipping
    # the dense column. Only set when `used` is exactly
    # base_used + scatter(deltas at rows) — preemption overlays and
    # private tables leave it None (dense fallback).
    table: Optional[object] = None
    used_base_rows: Optional[np.ndarray] = None   # i32[M]
    used_base_deltas: Optional[np.ndarray] = None  # f32[M,D]
    # device-resident combined feasibility mask (ISSUE 17): token into
    # the mirror's FeasMaskStore, set by the stack only when `feasible`
    # reaches the dispatch unmutated (no CSI/preferred residue). Any
    # path that swaps `feasible` must clear it.
    feas_token: Optional[Tuple] = None
    # sparse residue atop the parked mask (ISSUE 20): (rows i32[M],
    # vals bool[M]) reproducing the host mask's CSI-claim/quota/
    # preferred-node mutations on device via one jitted scatter, so
    # the token survives residue instead of forcing a dense re-upload.
    # Only meaningful beside feas_token; cleared with it.
    feas_residue: Optional[Tuple[np.ndarray, np.ndarray]] = None
    # the victims' program's selection, still on the device
    # (ops/victims.VictimSelection): its `used_after`, `pre_score` and
    # `capacity` replace the host's columns in the dispatch, so what
    # the second select of a preempting eval ranks never crosses the
    # bus. Such a request runs the scan arm alone and solo (the other
    # arms re-score on the host from `used` and `pre_score`); `used`
    # stays the usage BEFORE eviction and `pre_score` None.
    victims: Optional[object] = None


@dataclasses.dataclass
class SelectResult:
    """Result of one multi-placement kernel dispatch."""
    node_idx: np.ndarray             # i32[K] chosen node per step (-1 none)
    final_score: np.ndarray          # f32[K]
    scores: Dict[str, np.ndarray]    # component -> f32[K]
    top_idx: np.ndarray              # i32[K, TOP_K]
    top_scores: np.ndarray           # f32[K, TOP_K]
    nodes_evaluated: int
    nodes_filtered: int
    exhausted_dim: np.ndarray        # i32[K, D] counts per DIM_NAMES dim
    placed: int


def _select_scan_fn(capacity, used0, feasible, ask, k_valid,
                 tg_coll0, job_count0, distinct_hosts_flag, scan_exclusive,
                 penalty, affinity_norm, desired_count,
                 port_need, free_ports, port_ok,
                 dev_slots0, dev_score, dev_fires, pre_score,
                 sp_codes, sp_counts0, sp_present0, sp_desired,
                 sp_weight, sp_has_targets, sp_valid, sum_spread_w,
                 dp_codes, dp_counts0, dp_limit, dp_valid,
                 *, k_steps: int, spread_alg: bool, s_live: int, p_live: int):
    """The fused kernel. Shapes:
    capacity/used0 f32[N,3]; feasible bool[N]; ask f32[3];
    sp_* [S, ...] with code axis C+1; dp_* [P, ...].
    Returns per-step choices, scores, metrics, and the final usage state.
    """
    n = capacity.shape[0]
    cap_cpu = jnp.maximum(capacity[:, 0], 1e-9)
    cap_mem = jnp.maximum(capacity[:, 1], 1e-9)

    def step(carry, step_i):
        (used, tg_coll, job_cnt, scan_placed, free_p, dev_slots,
         sp_counts, sp_present, dp_counts) = carry

        # ---- feasibility beyond the static mask -----------------------
        feas = feasible
        feas &= jnp.where(distinct_hosts_flag > 0, job_cnt == 0, True)
        # reserved-port asks make instances mutually exclusive per node
        # within this scan (the same host port would collide)
        feas &= jnp.where(scan_exclusive > 0, scan_placed == 0, True)
        feas &= free_p >= port_need
        feas &= port_ok
        feas &= dev_slots >= 1.0
        # distinct_property: count(value)+1 <= limit, missing attr fails
        for p in range(p_live):
            codes = dp_codes[p]
            cnt = dp_counts[p][codes]
            missing = codes == dp_counts.shape[-1] - 1
            ok = (cnt + 1.0 <= dp_limit[p]) & ~missing
            feas &= jnp.where(dp_valid[p], ok, True)

        # ---- fit (AllocsFit over the node axis) -----------------------
        after = used + ask[None, :]
        fit_dims = after <= capacity + 1e-6
        fit = jnp.all(fit_dims, axis=1)

        # ---- bin-pack / spread fit score ------------------------------
        free_cpu = 1.0 - after[:, 0] / cap_cpu
        free_mem = 1.0 - after[:, 1] / cap_mem
        total = jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem)
        if spread_alg:
            fit_score = jnp.clip(total - 2.0, 0.0, 18.0)
        else:
            fit_score = jnp.clip(20.0 - total, 0.0, 18.0)
        binpack = fit_score / 18.0

        # ---- job anti-affinity ---------------------------------------
        coll = tg_coll.astype(jnp.float32)
        anti_fires = coll > 0
        anti = jnp.where(anti_fires,
                         -(coll + 1.0) / jnp.maximum(desired_count, 1.0),
                         0.0)

        # ---- reschedule penalty --------------------------------------
        pen_fires = penalty
        pen = jnp.where(pen_fires, -1.0, 0.0)

        # ---- node affinity -------------------------------------------
        aff_fires = affinity_norm != 0.0
        aff = affinity_norm

        # ---- device affinity ("devices" scorer, rank.go:456) ---------
        dev = jnp.where(dev_fires > 0, dev_score, 0.0)

        # ---- preemption scorer (rank.go:714 logistic) ----------------
        pre_fires = pre_score != 0.0

        # ---- spread ---------------------------------------------------
        spread_total = jnp.zeros(n, dtype=jnp.float32)
        for s in range(s_live):
            codes = sp_codes[s]
            c_axis = sp_counts.shape[-1]
            missing = codes == c_axis - 1
            used_cnt = sp_counts[s][codes] + 1.0
            desired = sp_desired[s][codes]
            has_desired = desired >= 0.0
            w = sp_weight[s] / jnp.maximum(sum_spread_w, 1e-9)
            targeted = jnp.where(
                has_desired,
                (desired - used_cnt) / jnp.maximum(desired, 1e-9) * w,
                -1.0)
            # even-spread scoring (spread.go evenSpreadScoreBoost)
            pres = sp_present[s]
            cnts = sp_counts[s]
            big = 1e30
            min_cnt = jnp.min(jnp.where(pres, cnts, big))
            max_cnt = jnp.max(jnp.where(pres, cnts, -big))
            any_present = jnp.any(pres)
            cur = sp_counts[s][codes]
            even = jnp.where(
                min_cnt == 0.0,
                -1.0,
                (min_cnt - cur) / jnp.maximum(min_cnt, 1e-9))
            at_min = cur == min_cnt
            even = jnp.where(
                at_min,
                jnp.where(min_cnt == max_cnt, -1.0,
                          jnp.where(min_cnt == 0.0, 1.0,
                                    (max_cnt - min_cnt) /
                                    jnp.maximum(min_cnt, 1e-9))),
                even)
            even = jnp.where(any_present, even, 0.0)
            even = jnp.where(missing, -1.0, even)
            contrib = jnp.where(sp_has_targets[s],
                                jnp.where(missing, -1.0, targeted), even)
            spread_total += jnp.where(sp_valid[s], contrib, 0.0)
        spread_fires = spread_total != 0.0

        # ---- normalization (mean over fired scorers) ------------------
        fired = (1.0 + anti_fires.astype(jnp.float32)
                 + pen_fires.astype(jnp.float32)
                 + aff_fires.astype(jnp.float32)
                 + spread_fires.astype(jnp.float32)
                 + jnp.where(dev_fires > 0, 1.0, 0.0)
                 + pre_fires.astype(jnp.float32))
        final = (binpack + anti + pen + aff + spread_total + dev
                 + pre_score) / fired

        # ---- masked argmax -------------------------------------------
        ok = feas & fit
        masked = jnp.where(ok, final, NEG_INF)
        choice = jnp.argmax(masked)
        valid = (masked[choice] > NEG_INF / 2) & (step_i < k_valid)
        choice_out = jnp.where(valid, choice, -1)

        # diagnostics (top-k meta, per-dimension exhaustion) only on the
        # first and failing steps — a full top_k + [N,D] scan per step
        # dominates large tables; per-instance scores are exact always
        def _meta(_):
            top_scores, top_idx = jax.lax.top_k(masked, TOP_K)
            prefix_ok = jnp.cumprod(fit_dims.astype(jnp.int32), axis=1)
            earlier_ok = jnp.concatenate(
                [jnp.ones((n, 1), dtype=bool),
                 prefix_ok[:, :-1].astype(bool)], axis=1)
            first_fail = feas[:, None] & earlier_ok & ~fit_dims
            return (top_idx.astype(jnp.int32), top_scores,
                    first_fail.sum(axis=0).astype(jnp.int32),
                    ok.sum().astype(jnp.int32))

        def _no_meta(_):
            return (jnp.full((TOP_K,), -1, jnp.int32),
                    jnp.full((TOP_K,), NEG_INF, jnp.float32),
                    jnp.full((capacity.shape[1],), -1, jnp.int32),
                    jnp.int32(-1))

        top_idx, top_scores, exhausted, ok_count = jax.lax.cond(
            (step_i == 0) | ~valid, _meta, _no_meta, operand=None)

        # ---- carry updates (the placement happens here) ---------------
        inc = jnp.where(valid, 1, 0)
        incf = inc.astype(jnp.float32)
        used = used.at[choice].add(incf * ask)
        tg_coll = tg_coll.at[choice].add(inc)
        job_cnt = job_cnt.at[choice].add(inc)
        scan_placed = scan_placed.at[choice].add(inc)
        free_p = free_p.at[choice].add(-incf * port_need)
        dev_slots = dev_slots.at[choice].add(-incf)
        chosen_sp_codes = sp_codes[:, choice]           # [S]
        sp_counts = sp_counts.at[jnp.arange(sp_counts.shape[0]),
                                 chosen_sp_codes].add(incf)
        sp_present = sp_present.at[jnp.arange(sp_counts.shape[0]),
                                   chosen_sp_codes].set(
            sp_present[jnp.arange(sp_counts.shape[0]),
                       chosen_sp_codes] | valid)
        chosen_dp_codes = dp_codes[:, choice]
        dp_counts = dp_counts.at[jnp.arange(dp_counts.shape[0]),
                                 chosen_dp_codes].add(incf)

        out = (choice_out.astype(jnp.int32),
               jnp.where(valid, masked[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, binpack[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, anti[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, pen[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, aff[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, spread_total[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, dev[jnp.maximum(choice, 0)], 0.0),
               jnp.where(valid, pre_score[jnp.maximum(choice, 0)], 0.0),
               top_idx, top_scores,
               exhausted, ok_count)
        return (used, tg_coll, job_cnt, scan_placed, free_p, dev_slots,
                sp_counts, sp_present, dp_counts), out

    carry0 = (used0, tg_coll0, job_count0,
              jnp.zeros(n, dtype=jnp.int32), free_ports, dev_slots0,
              sp_counts0, sp_present0, dp_counts0)
    carry, outs = jax.lax.scan(step, carry0, jnp.arange(k_steps))
    return carry, outs


_select_scan = partial(
    jax.jit, static_argnames=("k_steps", "spread_alg", "s_live",
                              "p_live"))(_select_scan_fn)

# positional order of _select_scan_fn's array arguments (the batched
# dispatcher calls it positionally under vmap)
_SCAN_ARGS = (
    "capacity", "used0", "feasible", "ask", "k_valid",
    "tg_coll0", "job_count0", "distinct_hosts_flag", "scan_exclusive",
    "penalty", "affinity_norm", "desired_count",
    "port_need", "free_ports", "port_ok",
    "dev_slots0", "dev_score", "dev_fires", "pre_score",
    "sp_codes", "sp_counts0", "sp_present0", "sp_desired",
    "sp_weight", "sp_has_targets", "sp_valid", "sum_spread_w",
    "dp_codes", "dp_counts0", "dp_limit", "dp_valid")


@lru_cache(maxsize=KERNEL_CACHE_MAX)
def _scan_batched_jit(k_steps: int, spread_alg: bool, s_live: int,
                      p_live: int):
    """The vmapped scan: B independent lanes over ONE shared capacity
    table (in_axes=None keeps it unstacked/resident) — the small-count
    arm of multi-eval batching. Covers the FULL scoring surface
    (spreads, distinct-property, reserved ports) unlike the K-way arm,
    because it is literally the scan kernel with a lane axis."""
    # the def's name is the program's on the device
    # (jit__select_scan_many on the profiler's XLA Modules line)
    def _select_scan_many(*args):
        return _select_scan_fn(*args, k_steps=k_steps,
                               spread_alg=spread_alg,
                               s_live=s_live, p_live=p_live)
    in_axes = tuple(None if name == "capacity" else 0
                    for name in _SCAN_ARGS)
    return jax.jit(jax.vmap(_select_scan_many, in_axes=in_axes))


def _local_final_score(after, cap_cpu, cap_mem, coll, penalty, affinity,
                       desired_count, spread_alg: bool,
                       dev_score=0.0, dev_fires=0.0, pre_score=0.0):
    """Node-local score (binpack/spread fit + anti-affinity + penalty +
    affinity + device affinity, normalized over fired scorers).
    Shape-polymorphic over the leading axes: after[..., D],
    cap/coll/penalty/affinity/dev_score[...]. This is the spread-free
    subset of the scan step's scoring, shared with the chunked kernel
    (semantics: rank.go BinPack/JobAntiAffinity/NodeReschedulingPenalty/
    NodeAffinity/device scoring:456/ScoreNormalization)."""
    free_cpu = 1.0 - after[..., 0] / cap_cpu
    free_mem = 1.0 - after[..., 1] / cap_mem
    total = jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem)
    if spread_alg:
        fit_score = jnp.clip(total - 2.0, 0.0, 18.0)
    else:
        fit_score = jnp.clip(20.0 - total, 0.0, 18.0)
    binpack = fit_score / 18.0
    collf = coll.astype(jnp.float32)
    anti_fires = collf > 0
    anti = jnp.where(anti_fires,
                     -(collf + 1.0) / jnp.maximum(desired_count, 1.0), 0.0)
    pen = jnp.where(penalty, -1.0, 0.0)
    aff_fires = affinity != 0.0
    dev = jnp.where(dev_fires > 0, dev_score, 0.0)
    pre_fires = pre_score != 0.0
    fired = (1.0 + anti_fires.astype(jnp.float32)
             + penalty.astype(jnp.float32)
             + aff_fires.astype(jnp.float32)
             + jnp.where(dev_fires > 0, 1.0, 0.0)
             + pre_fires.astype(jnp.float32))
    final = (binpack + anti + pen + affinity + dev + pre_score) / fired
    return final, binpack, anti, pen


def _select_chunked_fn(capacity, used0, feasible, ask, k_valid,
                    tg_coll0, penalty, affinity_norm, desired_count,
                    port_need, free_ports, port_ok,
                    dev_slots0, dev_score, dev_fires, pre_score,
                    *, max_steps: int, spread_alg: bool):
    """Chunked greedy placement for node-local scoring (no spread, no
    distinct-hosts/-property, no reserved-port exclusivity). Exactly
    equivalent to the one-instance-per-step scan: because every score
    term is a function of the candidate node's own state, placing an
    instance on the argmax node leaves every other node's score fixed —
    so the greedy sequence keeps choosing the same node until its own
    score is overtaken by the runner-up. Each while-loop step therefore
    places a whole chunk (up to CHUNK_J) on the argmax node: the chunk
    length is the number of consecutive sub-placements that still beat
    the runner-up under the scan's argmax tie rule (lowest index wins).

    This turns the O(count) sequential scan into O(nodes-touched +
    overtake-events) steps — the difference between 1.4 s and ~50 ms for
    a 10k-instance batch job (BASELINE ladder #2).

    Returns per-step (choice, chunk, top_idx/top_scores, exhausted,
    feasible-count) buffers plus the final carry for host-side
    continuation when max_steps is exhausted.
    """
    n = capacity.shape[0]
    cap_cpu = jnp.maximum(capacity[:, 0], 1e-9)
    cap_mem = jnp.maximum(capacity[:, 1], 1e-9)
    arange_j = jnp.arange(CHUNK_J, dtype=jnp.float32)

    def cond(state):
        (_used, _coll, _freep, _dev, remaining, step, alive, *_outs) = state
        return (remaining > 0) & alive & (step < max_steps)

    def body(state):
        (used, coll, free_p, dev_slots, remaining, step, _alive,
         out_choice, out_chunk, out_ti, out_ts, out_exh, out_feas) = state

        feas = feasible & (free_p >= port_need) & port_ok & \
            (dev_slots >= 1.0)
        after = used + ask[None, :]
        fit_dims = after <= capacity + 1e-6
        fit = jnp.all(fit_dims, axis=1)

        final, _b, _a, _p = _local_final_score(
            after, cap_cpu, cap_mem, coll, penalty, affinity_norm,
            desired_count, spread_alg, dev_score, dev_fires, pre_score)
        ok = feas & fit
        masked = jnp.where(ok, final, NEG_INF)
        # winner + runner-up as two argmax reductions — a full top_k
        # over the node axis per step dominates large tables
        choice = jnp.argmax(masked)
        valid = masked[choice] > NEG_INF / 2
        masked2 = masked.at[choice].set(NEG_INF)
        runner_idx = jnp.argmax(masked2)
        runner_val = masked2[runner_idx]

        # diagnostics (top-k score meta + per-dimension exhaustion) are
        # only materialized on the first step and on failing steps; the
        # host reuses the dispatch-level snapshot for later chunks
        def _meta(_):
            top_scores, top_idx = jax.lax.top_k(masked, TOP_K)
            prefix_ok = jnp.cumprod(fit_dims.astype(jnp.int32), axis=1)
            earlier_ok = jnp.concatenate(
                [jnp.ones((n, 1), dtype=bool),
                 prefix_ok[:, :-1].astype(bool)], axis=1)
            first_fail = feas[:, None] & earlier_ok & ~fit_dims
            return (top_idx.astype(jnp.int32), top_scores,
                    first_fail.sum(axis=0).astype(jnp.int32),
                    ok.sum().astype(jnp.int32))

        def _no_meta(_):
            return (jnp.full((TOP_K,), -1, jnp.int32),
                    jnp.full((TOP_K,), NEG_INF, jnp.float32),
                    jnp.full((capacity.shape[1],), -1, jnp.int32),
                    jnp.int32(-1))

        top_idx, top_scores, exhausted, feas_count = jax.lax.cond(
            (step == 0) | ~valid, _meta, _no_meta, operand=None)

        # max instances that physically fit on the chosen node
        free_dims = capacity[choice] - used[choice]
        per_dim = jnp.where(ask > 0, jnp.floor((free_dims + 1e-6) / ask), 1e9)
        m_fit = jnp.min(per_dim)
        m_port = jnp.where(port_need > 0,
                           jnp.floor(free_p[choice] / port_need), 1e9)
        a_max = jnp.minimum(
            jnp.minimum(jnp.minimum(m_fit, m_port), dev_slots[choice]),
            remaining.astype(jnp.float32))

        # score of the choice after each sub-placement a (state used_c +
        # a*ask, then + ask for the instance itself — the scan scores on
        # `after`); runner-up scores are frozen (node-locality)
        after_j = used[choice][None, :] + (arange_j[:, None] + 1.0) * ask
        coll_j = coll[choice].astype(jnp.float32) + arange_j
        final_j, _, _, _ = _local_final_score(
            after_j, cap_cpu[choice], cap_mem[choice], coll_j,
            penalty[choice], affinity_norm[choice],
            desired_count, spread_alg, dev_score[choice], dev_fires,
            pre_score[choice])
        # argmax tie rule: lowest index wins, so the choice survives a
        # tie with the runner-up only if its index is lower
        wins = (final_j > runner_val) | \
               ((final_j == runner_val) & (choice < runner_idx))
        prefix = jnp.cumprod(wins.astype(jnp.int32))
        chunk = jnp.minimum(jnp.maximum(prefix.sum().astype(jnp.float32),
                                        1.0), a_max)
        chunk = jnp.where(valid, chunk, 0.0)
        chunk_i = chunk.astype(jnp.int32)

        # indexed scatters: chunk is 0 on invalid steps, so the adds
        # are no-ops without O(N) select masks
        used = used.at[choice].add(chunk * ask)
        coll = coll.at[choice].add(chunk_i)
        free_p = free_p.at[choice].add(-chunk * port_need)
        dev_slots = dev_slots.at[choice].add(-chunk)

        out_choice = out_choice.at[step].set(
            jnp.where(valid, choice, -1).astype(jnp.int32))
        out_chunk = out_chunk.at[step].set(chunk_i)
        out_ti = out_ti.at[step].set(top_idx)
        out_ts = out_ts.at[step].set(top_scores)
        out_exh = out_exh.at[step].set(exhausted)
        out_feas = out_feas.at[step].set(feas_count)

        return (used, coll, free_p, dev_slots, remaining - chunk_i,
                step + 1, valid,
                out_choice, out_chunk, out_ti, out_ts, out_exh, out_feas)

    d = capacity.shape[1]
    state0 = (used0, tg_coll0, free_ports, dev_slots0, k_valid,
              jnp.int32(0), jnp.bool_(True),
              jnp.full(max_steps, -1, jnp.int32),
              jnp.zeros(max_steps, jnp.int32),
              jnp.full((max_steps, TOP_K), -1, jnp.int32),
              jnp.full((max_steps, TOP_K), NEG_INF, jnp.float32),
              jnp.zeros((max_steps, d), jnp.int32),
              jnp.zeros(max_steps, jnp.int32))
    out = jax.lax.while_loop(cond, body, state0)
    (used, coll, free_p, dev_slots, remaining, steps, _alive,
     out_choice, out_chunk, out_ti, out_ts, out_exh, out_feas) = out
    return ((used, coll, free_p, dev_slots),
            (out_choice, out_chunk, out_ti, out_ts, out_exh, out_feas,
             remaining, steps))


_select_chunked = partial(
    jax.jit, static_argnames=("max_steps", "spread_alg"))(
        _select_chunked_fn)


@lru_cache(maxsize=KERNEL_CACHE_MAX)
def _chunked_batched_jit(max_steps: int, spread_alg: bool):
    """The vmapped chunked kernel: B node-local lanes over ONE shared
    capacity table in a single dispatch. The while_loop batches to
    max-steps-over-lanes iterations, so a batch of small-count evals
    costs about as many node passes as its slowest lane — the chunk-ok
    arm of multi-eval batching (the scan arm covers spread/distinct
    lanes)."""
    def _select_chunked_many(*args):    # the program's device name
        return _select_chunked_fn(*args, max_steps=max_steps,
                                  spread_alg=spread_alg)
    in_axes = tuple(None if name == "capacity" else 0
                    for name in _CHUNKED_ARGS)
    return jax.jit(jax.vmap(_select_chunked_many, in_axes=in_axes))


def _segment_cummin(starts, x):
    """Running minimum of x along its one axis, begun anew wherever
    `starts` is set."""
    def combine(a, b):
        (a_start, a_min), (b_start, b_min) = a, b
        return (a_start | b_start,
                jnp.where(b_start, b_min, jnp.minimum(a_min, b_min)))
    return jax.lax.associative_scan(combine, (starts, x))[1]


# the K-way payload's header row (the int payload's last): what the
# host asks of a dispatch before it reads the sequence
KWAY_PLACED, KWAY_REMAINING, KWAY_PHASES, KWAY_TAIL_PHASES, \
    KWAY_LAST_PLACED = range(5)
# the float payload's columns before its TOP_K meta scores
_KWAY_SCORE_COLS = ("final", "binpack", "job-anti-affinity",
                    "node-reschedule-penalty", "node-affinity",
                    "devices", "preemption")


def _kway_sequence(out_widx, out_chunk, out_start, out_ti, out_ts, out_exh,
                   remaining, steps, tails,
                   capacity, used0, ask, tg_coll0, penalty, affinity_norm,
                   desired_count, dev_score, dev_fires, pre_score,
                   *, spread_alg: bool, k_out: int):
    """The phases' (winners, chunks) as the per-instance greedy
    sequence, in one pass over the `k_out` placed instances — no loop
    over phases or winners.

    Within a phase every winner's next score beats the waterline, so
    the scan's order there is the merge of the winners' score streams:
    pop the stream whose CURRENT head is largest, ties to the lowest
    node index. The streams are not monotonic (a bin-pack score rises
    as its node fills), so sorting the scores is not that merge. But:

      Lemma. The merge pops element j of stream k in the order of the
      key (-m_k(j), node_k, j), where m_k(j) = min over i <= j of
      s_k(i) is the stream's running minimum.

      Proof. m_k does not rise along a stream, so for any t the
      elements with m >= t are a prefix of every stream, each scoring
      >= t. Take the first pop of an element x with m < t while such a
      prefix has elements left: all before x in its stream were of the
      prefix, so x itself scores < t, and the stream with prefix left
      has a head scoring >= t — the merge pops the larger head, not x.
      So everything with m >= t goes before anything with m < t. Those
      of one m are, in each stream, a run whose first scores exactly m
      (the minimum is reached there) and whose rest score >= m: once
      all above m are gone the heads are such firsts and heads below
      m, the pop takes the lowest node among the firsts, and that
      stream's next heads (>= m, on the lowest node) keep winning
      until its run ends. Hence node, then j.

    So: each element's (phase, winner, offset) by a search on the
    running sum of the chunks; its score from _local_final_score — the
    function the phase body and the scan rank by — at the winner's
    `start + offset` earlier placements of this dispatch; the running
    minimum along each stream (a stream's elements are contiguous); one
    sort by (phase, -m, node, offset). The meta rows (top-k, exhausted
    dimensions) are those of the last phase that made them, the first
    and the failing ones; what was not placed carries the last phase's.

    Returns (packed_i [k_out + 1, 1 + TOP_K + D], packed_f [k_out,
    len(_KWAY_SCORE_COLS) + TOP_K]): node, top_idx, exhausted_dim per
    instance with the header row (KWAY_*) last; the scores and
    top_scores. ONE int payload + one float payload: every
    device->host copy is its own transfer op with a fixed cost on top
    of the bytes."""
    max_steps, w = out_chunk.shape
    chunks = out_chunk.reshape(-1)
    ends = jnp.cumsum(chunks)
    placed = ends[-1]
    e = jnp.arange(k_out, dtype=jnp.int32)
    live = e < placed
    flat = jnp.minimum(jnp.searchsorted(ends, e, side="right"),
                       chunks.shape[0] - 1).astype(jnp.int32)
    offset = e - (ends[flat] - chunks[flat])
    phase = jnp.where(live, flat // w, max_steps)       # the dead sort last
    node = jnp.maximum(out_widx.reshape(-1)[flat], 0)
    prior = out_start.reshape(-1)[flat] + offset

    after = used0[node] \
        + (prior + 1).astype(jnp.float32)[:, None] * ask[None, :]
    affinity = affinity_norm[node]
    pre = pre_score[node]
    final, binpack, anti, pen = _local_final_score(
        after, jnp.maximum(capacity[node, 0], 1e-9),
        jnp.maximum(capacity[node, 1], 1e-9),
        tg_coll0[node].astype(jnp.float32) + prior.astype(jnp.float32),
        penalty[node], affinity, desired_count, spread_alg,
        dev_score[node], dev_fires, pre)
    dev = jnp.where(dev_fires > 0, dev_score[node], 0.0)

    run_min = _segment_cummin((offset == 0) | ~live, final)
    order = jnp.lexsort((offset, node, -run_min, phase))

    # a phase's meta row: the last at or before it that was materialized
    # (rows past `steps` were never written and read as the defaults)
    made = jax.lax.cummax(jnp.where(out_exh[:, 0] >= 0,
                                    jnp.arange(max_steps), 0))
    meta = made[jnp.where(live, phase[order], jnp.maximum(steps - 1, 0))]

    scores = (final, binpack, anti, pen, affinity, dev, pre)
    packed_f = jnp.concatenate(
        [jnp.where(live, col[order], 0.0)[:, None] for col in scores]
        + [out_ts[meta]], axis=1)
    packed_i = jnp.concatenate(
        [jnp.where(live, node[order], -1)[:, None], out_ti[meta],
         out_exh[meta]], axis=1)
    last_placed = jnp.where(
        steps > 0, out_chunk[jnp.maximum(steps - 1, 0)].sum(), 0)
    head = jnp.stack([placed, remaining, steps, tails, last_placed])
    header = jnp.zeros(packed_i.shape[1], jnp.int32).at[:len(head)].set(
        head)                                       # in KWAY_* order
    return jnp.concatenate([packed_i, header[None, :]]), packed_f


def _kway_core(capacity, used0, feasible, ask, k_valid,
               tg_coll0, penalty, affinity_norm, desired_count,
               port_need, free_ports, port_ok,
               dev_slots0, dev_score, dev_fires, pre_score,
               *, max_steps: int, spread_alg: bool, w: int, k_out: int):
    """K-way chunked greedy placement for node-local scoring: each phase
    takes the top-W nodes and gives EACH the number of sub-placements
    that keep its own score above the (W+1)-th node's score (the
    waterline), under the scan's argmax tie rule. Greedy only ever picks
    the current argmax, and scores are node-local, so until every winner
    falls below the waterline the argmax stays inside the winner set —
    the multiset of placements per phase is exactly the greedy one, and
    _kway_sequence puts it in the greedy order before anything leaves
    the device. A phase whose winner chunks would overshoot the
    remaining count degenerates to placing only on the single best node,
    preserving exactness for the tail.

    Returns the carry (for a continuation when max_steps runs out) and
    the per-instance sequence of the `k_out` (a _bucket_k of the ask's
    count) placements this dispatch can hold, as one int and one float
    payload (_kway_sequence).

    Phases ~ count/(W * avg-chunk) instead of the 2-way kernel's
    count/avg-chunk steps — an order of magnitude fewer sequential
    device steps for big batches, and out buffers to match."""
    n = capacity.shape[0]
    cap_cpu = jnp.maximum(capacity[:, 0], 1e-9)
    cap_mem = jnp.maximum(capacity[:, 1], 1e-9)
    arange_j = jnp.arange(CHUNK_J, dtype=jnp.float32)

    def cond(state):
        (_used, _coll, _freep, _dev, remaining, step, alive, *_o) = state
        return (remaining > 0) & alive & (step < max_steps)

    def body(state):
        (used, coll, free_p, dev_slots, remaining, step, _alive, tails,
         out_widx, out_chunk, out_start, out_ti, out_ts, out_exh) = state

        # named scopes: op metadata only, so a profile shows which
        # phase of a step the device time went to
        with jax.named_scope("mask_score"):
            feas = feasible & (free_p >= port_need) & port_ok & \
                (dev_slots >= 1.0)
            after = used + ask[None, :]
            fit_dims = after <= capacity + 1e-6
            fit = jnp.all(fit_dims, axis=1)
            final, _b, _a, _p = _local_final_score(
                after, cap_cpu, cap_mem, coll, penalty, affinity_norm,
                desired_count, spread_alg, dev_score, dev_fires,
                pre_score)
            ok = feas & fit
            masked = jnp.where(ok, final, NEG_INF)

        with jax.named_scope("top_k"):
            tv, ti = jax.lax.top_k(masked, w + 1)
        wl_val = tv[w]
        wl_idx = ti[w]
        widx = ti[:w]
        wvalid = tv[:w] > NEG_INF / 2
        valid = wvalid[0]

        # diagnostics on the first and failing phases only
        def _meta(_):
            top_scores, top_idx = jax.lax.top_k(masked, TOP_K)
            prefix_ok = jnp.cumprod(fit_dims.astype(jnp.int32), axis=1)
            earlier_ok = jnp.concatenate(
                [jnp.ones((n, 1), dtype=bool),
                 prefix_ok[:, :-1].astype(bool)], axis=1)
            first_fail = feas[:, None] & earlier_ok & ~fit_dims
            return (top_idx.astype(jnp.int32), top_scores,
                    first_fail.sum(axis=0).astype(jnp.int32),
                    ok.sum().astype(jnp.int32))

        def _no_meta(_):
            return (jnp.full((TOP_K,), -1, jnp.int32),
                    jnp.full((TOP_K,), NEG_INF, jnp.float32),
                    jnp.full((capacity.shape[1],), -1, jnp.int32),
                    jnp.int32(-1))

        top_idx, top_scores, exhausted, _feas_count = jax.lax.cond(
            (step == 0) | ~valid, _meta, _no_meta, operand=None)

        # physical capacity per winner
        free_dims = capacity[widx] - used[widx]                 # [W, D]
        per_dim = jnp.where(ask[None, :] > 0,
                            jnp.floor((free_dims + 1e-6) / ask[None, :]),
                            1e9)
        m_fit = jnp.min(per_dim, axis=1)
        m_port = jnp.where(port_need > 0,
                           jnp.floor(free_p[widx] / port_need), 1e9)
        a_max = jnp.minimum(jnp.minimum(m_fit, m_port), dev_slots[widx])
        a_max = jnp.minimum(a_max, jnp.float32(CHUNK_J))

        # per-winner scores after each sub-placement  [W, CHUNK_J]
        after_j = used[widx][:, None, :] \
            + (arange_j[None, :, None] + 1.0) * ask[None, None, :]
        coll_j = coll[widx].astype(jnp.float32)[:, None] + arange_j[None, :]
        final_j, _, _, _ = _local_final_score(
            after_j, cap_cpu[widx][:, None], cap_mem[widx][:, None],
            coll_j, penalty[widx][:, None], affinity_norm[widx][:, None],
            desired_count, spread_alg, dev_score[widx][:, None], dev_fires,
            pre_score[widx][:, None])
        wins = (final_j > wl_val) | \
               ((final_j == wl_val) & (widx[:, None] < wl_idx))
        prefix = jnp.cumprod(wins.astype(jnp.int32), axis=1)
        chunk = jnp.minimum(
            jnp.maximum(prefix.sum(axis=1).astype(jnp.float32), 1.0),
            a_max)
        chunk = jnp.where(wvalid, chunk, 0.0)

        # overshoot fallback: the tail phase degenerates to the 2-way
        # rule — place on the best node only, chunked against the
        # RUNNER-UP's score (not the waterline: with W winners zeroed
        # out, the runner-up is the true greedy competitor)
        total = chunk.sum()
        runner_val = tv[1]
        runner_idx = ti[1]
        wins0 = (final_j[0] > runner_val) | \
                ((final_j[0] == runner_val) & (widx[0] < runner_idx))
        chunk0 = jnp.minimum(
            jnp.maximum(jnp.cumprod(wins0.astype(jnp.int32)).sum()
                        .astype(jnp.float32), 1.0), a_max[0])
        first_only = jnp.zeros_like(chunk).at[0].set(
            jnp.minimum(chunk0, remaining.astype(jnp.float32)))
        overshoot = total > remaining.astype(jnp.float32)
        chunk = jnp.where(overshoot, first_only, chunk)
        chunk = jnp.where(valid, chunk, jnp.zeros_like(chunk))
        chunk_i = chunk.astype(jnp.int32)

        # winner indices are distinct, so scatter-add is well-defined;
        # invalid lanes carry chunk 0 (no-op adds on a real node row)
        with jax.named_scope("commit"):
            safe_w = jnp.maximum(widx, 0)
            # what each winner already took in THIS dispatch: its
            # stream's scores start that far past used0 / tg_coll0
            out_start = out_start.at[step].set(
                coll[safe_w] - tg_coll0[safe_w])
            used = used.at[safe_w].add(chunk[:, None] * ask[None, :])
            coll = coll.at[safe_w].add(chunk_i)
            free_p = free_p.at[safe_w].add(-chunk * port_need)
            dev_slots = dev_slots.at[safe_w].add(-chunk)

            out_widx = out_widx.at[step].set(
                jnp.where(chunk_i > 0, widx, -1).astype(jnp.int32))
            out_chunk = out_chunk.at[step].set(chunk_i)
            out_ti = out_ti.at[step].set(top_idx)
            out_ts = out_ts.at[step].set(top_scores)
            out_exh = out_exh.at[step].set(exhausted)

        return (used, coll, free_p, dev_slots,
                remaining - chunk_i.sum(), step + 1, valid,
                tails + (valid & overshoot).astype(jnp.int32),
                out_widx, out_chunk, out_start, out_ti, out_ts, out_exh)

    d = capacity.shape[1]
    state0 = (used0, tg_coll0, free_ports, dev_slots0, k_valid,
              jnp.int32(0), jnp.bool_(True), jnp.int32(0),
              jnp.full((max_steps, w), -1, jnp.int32),
              jnp.zeros((max_steps, w), jnp.int32),
              jnp.zeros((max_steps, w), jnp.int32),
              jnp.full((max_steps, TOP_K), -1, jnp.int32),
              jnp.full((max_steps, TOP_K), NEG_INF, jnp.float32),
              jnp.zeros((max_steps, d), jnp.int32))
    out = jax.lax.while_loop(cond, body, state0)
    (used, coll, free_p, dev_slots, remaining, steps, _alive, tails,
     out_widx, out_chunk, out_start, out_ti, out_ts, out_exh) = out
    with jax.named_scope("sequence"):
        payload = _kway_sequence(
            out_widx, out_chunk, out_start, out_ti, out_ts, out_exh,
            remaining, steps, tails,
            capacity, used0, ask, tg_coll0, penalty, affinity_norm,
            desired_count, dev_score, dev_fires, pre_score,
            spread_alg=spread_alg, k_out=k_out)
    return ((used, coll, free_p, dev_slots), payload)


_select_kway = partial(jax.jit, static_argnames=("max_steps",
                                                 "spread_alg", "w",
                                                 "k_out"))(_kway_core)

# Multi-eval batching (SURVEY §2.6 row 1: "batch multiple evals per
# device dispatch"): B independent placement problems over ONE shared
# node-capacity table run as a single dispatch, amortizing the
# per-dispatch latency across the whole eval batch.
_KWAY_BATCH_AXES = (None,) + (0,) * 15


@partial(jax.jit, static_argnames=("max_steps", "spread_alg", "w",
                                   "k_out"))
def _select_kway_batched(capacity, used0, feasible, ask, k_valid,
                         tg_coll0, penalty, affinity_norm, desired_count,
                         port_need, free_ports, port_ok,
                         dev_slots0, dev_score, dev_fires, pre_score,
                         *, max_steps: int, spread_alg: bool, w: int,
                         k_out: int):
    fn = partial(_kway_core, max_steps=max_steps, spread_alg=spread_alg,
                 w=w, k_out=k_out)
    return jax.vmap(fn, in_axes=_KWAY_BATCH_AXES)(
        capacity, used0, feasible, ask, k_valid,
        tg_coll0, penalty, affinity_norm, desired_count,
        port_need, free_ports, port_ok,
        dev_slots0, dev_score, dev_fires, pre_score)


# Kinds for each packed argument: how its leading axis shards over a
# node-axis mesh (parallel/sharded.py). "node"=[N], "node2"=[N,d],
# "code"=[S,N] style, "rep"=replicated small state, "scalar"=0-d.
PACK_SHARD_KINDS = {
    "capacity": "node2", "used0": "node2", "feasible": "node",
    "ask": "rep", "k_valid": "scalar",
    "tg_coll0": "node", "job_count0": "node",
    "distinct_hosts_flag": "scalar", "scan_exclusive": "scalar",
    "penalty": "node", "affinity_norm": "node", "desired_count": "scalar",
    "port_need": "scalar", "free_ports": "node", "port_ok": "node",
    "dev_slots0": "node", "dev_score": "node", "dev_fires": "scalar",
    "pre_score": "node",
    "sp_codes": "code", "sp_counts0": "rep", "sp_present0": "rep",
    "sp_desired": "rep", "sp_weight": "rep", "sp_has_targets": "rep",
    "sp_valid": "rep", "sum_spread_w": "scalar",
    "dp_codes": "code", "dp_counts0": "rep", "dp_limit": "rep",
    "dp_valid": "rep",
}

MAX_SCAN_STEPS = 65536
# counts at or below this take the vmapped-scan arm of select_many
SCAN_BATCH_MAX = 256
# max lanes one micro-batch gateway fire ships in a single vmapped
# dispatch (server/worker.py MicroBatchGateway). Together with
# _pad_and_stack's power-of-two lane padding this bounds the distinct
# (arm, n_pad, lanes) trace signatures micro-batching can mint to
# {2, 4, 8, 16} per shape bucket — the lint.recompiles gauge stays
# bounded no matter how occupancy fluctuates per window
GATEWAY_MAX_LANES = 16

# process-wide sharded dispatcher (see get_shared_sharded)

_SHARED_SHARDED = None
_SHARED_SHARDED_LOCK = make_lock()


def get_shared_sharded():
    """The ONE process-wide ShardedSelect, created on first demand when
    mesh routing is configured (NOMAD_TPU_MESH=1 forces it; auto
    engages on multi-device accelerator backends), else None.
    Process-wide because PlacementEngines (and their kernels) are
    rebuilt per eval — the mesh and the mesh-resident node table
    (parallel/sharded_table.py) must outlive them or the 'resident
    across evals' property is fiction. The env gate is re-read per
    call, so tests flipping NOMAD_TPU_MESH get the answer they asked
    for while the dispatcher (and its resident state) persists."""
    want = os.environ.get("NOMAD_TPU_MESH", "auto")
    if want in ("0", "off", "no"):
        return None
    n_dev = len(jax.devices())
    force = want in ("1", "on", "force")
    auto = (want == "auto" and n_dev > 1
            and jax.default_backend() != "cpu")
    if n_dev > 1 and (force or auto):
        global _SHARED_SHARDED
        # check-then-set under a lock: the cold-start prefetch thread
        # (NodeTableCache.prefetch_device) races the first worker eval
        # here, and a losing duplicate would pin a second resident
        # column set across the mesh while splitting the stats
        with _SHARED_SHARDED_LOCK:
            if _SHARED_SHARDED is None:
                from ..parallel.sharded import ShardedSelect, make_mesh
                _SHARED_SHARDED = ShardedSelect(make_mesh())
        return _SHARED_SHARDED
    return None


def mesh_stats_snapshot() -> Dict[str, object]:
    """Mesh residency economics for the governor's mesh.* gauges, the
    telemetry device.* family, and the bench artifact: device count,
    resident bytes (total and per device), reshard uploads/bytes,
    delta scatters, resident hits/stale misses, and the capacity-cache
    fallback accounting. Empty dict until a mesh dispatcher exists —
    readers treat absence as 'mesh off'."""
    sh = _SHARED_SHARDED
    if sh is None:
        return {}
    return sh.stats_snapshot()


def pack_request(req: SelectRequest, n_pad: int):
    """Pad/pack a SelectRequest into the _select_scan argument dict
    (keys match the kernel's parameter names; PACK_SHARD_KINDS describes
    each argument's sharding axis). Shared by the single-device kernel
    wrapper and the mesh-sharded dispatcher."""
    if req.count > MAX_SCAN_STEPS:
        raise ValueError(
            f"count={req.count} exceeds the scan cap of {MAX_SCAN_STEPS}; "
            f"split the placement batch")
    n = len(req.feasible)
    # device economics (ISSUE 11): every pack ships n_pad rows for n
    # live ones — the pad-waste ratio the validation campaign reads
    _note_pack(n, n_pad)

    def pad1(a, fill=0.0, dtype=np.float32):
        out = np.full(n_pad, fill, dtype=dtype)
        out[:n] = a
        return out

    def pad2(a):
        out = np.zeros((n_pad, a.shape[1]), dtype=np.float32)
        out[:n] = a
        return out

    if req.affinity is not None and req.affinity_sum_weights > 0:
        affinity_norm = pad1(req.affinity / req.affinity_sum_weights)
    else:
        affinity_norm = np.zeros(n_pad, dtype=np.float32)

    s_live = min(len(req.spreads), S_MAX)
    c_axis = C_MAX + 1
    sp_codes = np.full((S_MAX, n_pad), C_MAX, dtype=np.int32)
    sp_counts = np.zeros((S_MAX, c_axis), dtype=np.float32)
    sp_present = np.zeros((S_MAX, c_axis), dtype=bool)
    sp_desired = np.full((S_MAX, c_axis), -1.0, dtype=np.float32)
    sp_weight = np.zeros(S_MAX, dtype=np.float32)
    sp_has_targets = np.zeros(S_MAX, dtype=bool)
    sp_valid = np.zeros(S_MAX, dtype=bool)
    for s, sp in enumerate(req.spreads[:S_MAX]):
        m = len(sp["codes"])
        sp_codes[s, :m] = np.minimum(sp["codes"], C_MAX)
        c = min(len(sp["counts"]), c_axis)
        sp_counts[s, :c] = sp["counts"][:c]
        sp_present[s, :c] = sp["present"][:c]
        sp_desired[s, :c] = sp["desired"][:c]
        sp_weight[s] = sp["weight"]
        sp_has_targets[s] = sp["has_targets"]
        sp_valid[s] = True

    p_live = min(len(req.distinct_props), P_MAX)
    dp_codes = np.full((P_MAX, n_pad), C_MAX, dtype=np.int32)
    dp_counts = np.zeros((P_MAX, c_axis), dtype=np.float32)
    dp_limit = np.zeros(P_MAX, dtype=np.float32)
    dp_valid = np.zeros(P_MAX, dtype=bool)
    for p, dp in enumerate(req.distinct_props[:P_MAX]):
        m = len(dp["codes"])
        dp_codes[p, :m] = np.minimum(dp["codes"], C_MAX)
        c = min(len(dp["counts"]), c_axis)
        dp_counts[p, :c] = dp["counts"][:c]
        dp_limit[p] = dp["limit"]
        dp_valid[p] = True

    # scalars stay host-side numpy: a jnp scalar would be committed to
    # the default backend and force device-to-device transfers when the
    # router sends the dispatch to the other backend
    args = dict(
        capacity=pad2(req.capacity),
        used0=pad2(req.used),
        feasible=pad1(req.feasible, False, bool),
        ask=np.asarray(req.ask, np.float32),
        k_valid=np.int32(req.count),
        tg_coll0=pad1(req.tg_collisions, 0, np.int32),
        job_count0=pad1(req.job_count, 0, np.int32),
        distinct_hosts_flag=np.float32(1.0 if req.distinct_hosts else 0.0),
        scan_exclusive=np.float32(1.0 if req.scan_exclusive else 0.0),
        penalty=pad1(req.penalty if req.penalty is not None
                     else np.zeros(n, bool), False, bool),
        affinity_norm=affinity_norm,
        desired_count=np.float32(req.desired_count),
        port_need=np.float32(req.port_need),
        free_ports=pad1(req.free_ports if req.free_ports is not None
                        else np.full(n, 1e9, np.float32)),
        port_ok=pad1(req.port_ok if req.port_ok is not None
                     else np.ones(n, bool), False, bool),
        dev_slots0=pad1(req.dev_slots if req.dev_slots is not None
                        else np.full(n, 1e9, np.float32)),
        dev_score=pad1(req.dev_score if req.dev_score is not None
                       else np.zeros(n, np.float32)),
        dev_fires=np.float32(1.0 if req.dev_fires else 0.0),
        pre_score=pad1(req.pre_score if req.pre_score is not None
                       else np.zeros(n, np.float32)),
        sp_codes=sp_codes, sp_counts0=sp_counts, sp_present0=sp_present,
        sp_desired=sp_desired, sp_weight=sp_weight,
        sp_has_targets=sp_has_targets, sp_valid=sp_valid,
        sum_spread_w=np.float32(req.sum_spread_weights),
        dp_codes=dp_codes, dp_counts0=dp_counts, dp_limit=dp_limit,
        dp_valid=dp_valid,
    )
    statics = dict(spread_alg=(req.algorithm == "spread"),
                   s_live=s_live, p_live=p_live)
    return args, statics


def _note_trace(arm: str, n_pad: int, **statics) -> bool:
    """Report this dispatch's compile key to the recompile counter
    (analysis/sanitizer.py): a NEW (arm, shape-bucket, statics) tuple
    means XLA traced and compiled. Always on — the cost is one set
    lookup — so the `nomad.lint.recompiles` governor gauge sees storms
    in production, not just under the sanitizer. Returns True when the
    signature is fresh (this dispatch pays the compile): the caller
    passes that to cost_model.observe so a compile wall is NEVER
    blended into a steady-state EWMA — per-key first-sample
    replacement can absorb only ONE compile, but one (arm, n_pad) key
    folds many lane/step buckets that each compile separately (the r11
    warm-loop pollution: three batched lane widths pushed
    chunked_batched@2048 to 72 ms 'steady state' and demoted every
    lane)."""
    from ..analysis.sanitizer import traces
    return traces.note(arm, (n_pad,) + tuple(sorted(statics.items())))


def _sanitize_request(req: SelectRequest) -> None:
    """NOMAD_TPU_SANITIZE=1 boundary guard: NaN/Inf screens on the
    columns this dispatch ships — a NaN in `used` silently wins every
    argmax (checkify analog, host-side so the device never pays)."""
    from ..analysis import sanitizer
    if not sanitizer.enabled():
        return
    sanitizer.check_finite(
        "select.request", capacity=req.capacity, used=req.used,
        ask=np.asarray(req.ask, np.float32),
        free_ports=req.free_ports, dev_slots=req.dev_slots)


def _sanitize_result(req: SelectRequest,
                     res: SelectResult) -> SelectResult:
    """NOMAD_TPU_SANITIZE=1 boundary guard on the unpacked result:
    chosen rows must be real table rows and scores finite."""
    from ..analysis import sanitizer
    if not sanitizer.enabled():
        return res
    n = len(req.feasible)
    idx = res.node_idx
    if idx.size:
        lo, hi = int(idx.min()), int(idx.max())
        if lo < -1 or hi >= n:
            raise sanitizer.SanitizerError(
                f"sanitizer[select.result]: node_idx range [{lo}, {hi}]"
                f" outside [-1, {n}) — the kernel chose a padding row")
    sanitizer.check_finite("select.result",
                           final_score=res.final_score)
    return res


def _stage_get(outs):
    """jax.device_get with bench attribution: result transfers are the
    `d2h` stage of the per-stage breakdown (the wall includes any
    remaining device compute — jax blocks the transfer on it — so d2h
    nests inside the kernel-stage window; see utils/stages)."""
    with stages.span("d2h"):
        return jax.device_get(outs)


def unpack_result(req: SelectRequest, outs) -> SelectResult:
    # ONE batched transfer: per-array np.asarray would serialize one
    # device round trip per output
    vals = _stage_get(outs)
    with stages.span("kernel_expand"):
        return _unpack_fetched(req, vals)


def _unpack_fetched(req: SelectRequest, vals) -> SelectResult:
    """Host half of unpack_result, on arrays already fetched."""
    (choices, finals, s_bin, s_anti, s_pen, s_aff, s_spread, s_dev, s_pre,
     top_idx, top_scores, exhausted, _ok_counts) = vals
    # meta rows (top-k, exhaustion) are materialized only on the first
    # and failing steps; forward-fill the sentinels in between
    sentinel = exhausted[:, 0] < 0
    if sentinel.any():
        top_idx = top_idx.copy()
        top_scores = top_scores.copy()
        exhausted = exhausted.copy()
        last = 0
        for s in range(len(exhausted)):
            if sentinel[s]:
                top_idx[s] = top_idx[last]
                top_scores[s] = top_scores[last]
                exhausted[s] = exhausted[last]
            else:
                last = s
    n = len(req.feasible)
    kk = req.count
    choices = choices[:kk]
    from ..analysis import sanitizer as _san
    if _san.enabled() and choices.size and int(choices.max()) >= n:
        # must run BEFORE the defensive clamp below, or a kernel bug
        # that picks a padding row is laundered into a benign
        # "unplaced" -1 and the guard never fires
        raise _san.SanitizerError(
            f"sanitizer[select.result]: kernel chose padding row "
            f"{int(choices.max())} (table has {n} rows)")
    choices = np.where(choices >= n, -1, choices)  # padding lanes
    placed = int((choices >= 0).sum())
    top_idx = np.where(top_idx >= n, -1, top_idx)
    return _sanitize_result(req, SelectResult(
        node_idx=choices,
        final_score=finals[:kk],
        scores={"binpack": s_bin[:kk], "job-anti-affinity": s_anti[:kk],
                "node-reschedule-penalty": s_pen[:kk],
                "node-affinity": s_aff[:kk],
                "allocation-spread": s_spread[:kk],
                "devices": s_dev[:kk],
                "preemption": s_pre[:kk]},
        top_idx=top_idx[:kk], top_scores=top_scores[:kk],
        nodes_evaluated=(req.n_considered if req.n_considered is not None
                         else n),
        nodes_filtered=int((req.n_considered if req.n_considered is not None
                            else n) - np.count_nonzero(req.feasible)),
        exhausted_dim=exhausted[:kk],
        placed=placed,
    ))


_CHUNKED_ARGS = ("capacity", "used0", "feasible", "ask", "k_valid",
                 "tg_coll0", "penalty", "affinity_norm", "desired_count",
                 "port_need", "free_ports", "port_ok",
                 "dev_slots0", "dev_score", "dev_fires", "pre_score")


def _expand_kway(req: SelectRequest, rounds) -> SelectResult:
    """Host half of the K-way arm: the dispatch's sequence payloads
    (_kway_sequence; more than one only when a dispatch ran out of its
    phase budget and continued) joined in order and handed to the scan's
    unpack — a slice, a clamp and the result's guard."""
    pi, pf = rounds[-1]
    pi = pi[:-1]
    if len(rounds) > 1:
        # a round that continued placed `KWAY_PLACED` and no less
        cuts = [int(r[0][-1, KWAY_PLACED]) for r in rounds[:-1]]
        pi = np.concatenate(
            [r[0][:c] for r, c in zip(rounds, cuts)] + [pi])
        pf = np.concatenate(
            [r[1][:c] for r, c in zip(rounds, cuts)] + [pf])
    s = len(_KWAY_SCORE_COLS)
    fin, s_bin, s_anti, s_pen, s_aff, s_dev, s_pre = pf[:, :s].T
    return _unpack_fetched(req, (
        pi[:, 0], fin, s_bin, s_anti, s_pen, s_aff,
        np.zeros(len(pi), np.float32), s_dev, s_pre,
        pi[:, 1:1 + TOP_K], pf[:, s:], pi[:, 1 + TOP_K:], None))


def _kway_continues(packed_i) -> bool:
    """A dispatch that left instances unplaced and whose last phase
    still placed some ran out of phases, not of nodes."""
    head = packed_i[-1]
    return bool(head[KWAY_REMAINING] > 0 and head[KWAY_PHASES] > 0
                and head[KWAY_LAST_PLACED] > 0)


def _kway_counts(lanes) -> Dict[str, int]:
    """The kernel_expand span's attrs on the K-way arm, summed over
    the rounds of every lane: phases run, those of them the overshoot
    rule held to the best node alone, instances placed."""
    heads = np.stack([pi[-1] for rounds in lanes for pi, _pf in rounds])
    return {"phases": int(heads[:, KWAY_PHASES].sum()),
            "tail_phases": int(heads[:, KWAY_TAIL_PHASES].sum()),
            "placed": int(heads[:, KWAY_PLACED].sum())}


class DispatchCostModel:
    """Measured per-shape dispatch costs: what the gateways' solo-or-
    batched question rests on (host-or-accelerator is no longer asked:
    SelectKernel._pick_device).

    Every device phase (dispatch through result transfer) of the solo
    and batched kernel arms reports its wall clock here, keyed by
    (arm, n_pad) — batched arms report seconds PER LANE so solo and
    batched numbers compare directly. The batching decisions then
    rest on what THIS host+device pair actually
    measured at this table shape rather than on constants calibrated
    on different hardware (BENCH_r05: the static model demoted every
    broker lane on real TPU — service_broker_batches=0 — while the
    shapes it demoted measured 1.42-1.61x when they fired).

    Exploration: a batched arm that is never dispatched is never
    measured, so when solo numbers are warm and batched ones are cold
    the profitability question returns True once every PROBE_EVERY
    calls — and a batched arm that measured SLOWER keeps being probed
    at the same cadence, so a stale number (e.g. one taken while the
    device was busy) cannot demote lanes forever.

    Methodology (recorded for re-anchor audits, STATUS.md §2.6): EWMA
    with alpha=0.25 over per-lane seconds, minimum 3 samples before a
    measured number overrides a formula, count variation deliberately
    folded into the EWMA (per-shape means per (arm, table size) — the
    steady state re-dispatches the same shapes, which is exactly when
    the numbers matter). Compile walls are excluded at the source: a
    dispatch that mints a NEW trace signature (_note_trace) reports
    with compiled=True and never enters the EWMA — a seconds-long
    compile would otherwise dominate it for many rounds, and one
    (arm, n_pad) key folds many separately-compiling lane/step
    buckets. Timing windows include per-request host unpack/expand on
    both the solo and batched arms, so the comparison is end-to-end
    per lane, not device-dispatch-only."""

    ALPHA = 0.25
    MIN_SAMPLES = 3
    PROBE_EVERY = 16

    def __init__(self):
        self._l = make_lock()
        self._stats: Dict[Tuple[str, int], List[float]] = {}
        self._probe = 0

    def observe(self, arm: str, n_pad: int, seconds: float,
                lanes: int = 1, compiled: bool = False,
                on_cpu: bool = False) -> None:
        """`on_cpu` marks a BATCHED dispatch the router placed on the
        host backend (solo arms carry it in their name, `scan@cpu`).
        The batched EWMA key folds host- and accelerator-routed
        dispatches, as it always has; the device stats and the kernel
        span must not — no host dispatch is filed under a device arm's
        name."""
        shown = arm + "@cpu" if on_cpu else arm
        # device economics (ISSUE 11): per-arm dispatch seconds and
        # fresh-compile counts, exported via nomad.device.* gauges and
        # the bench artifact — always on, like the recompile counter
        _note_dispatch(shown, seconds, compiled)
        key = (arm, n_pad)
        if compiled:
            # this dispatch minted a new trace signature (_note_trace):
            # its wall includes XLA compile and must not enter the
            # steady-state EWMA at all — one (arm, n_pad) key folds
            # many lane/step buckets that each compile separately, so
            # no single-replacement scheme could absorb them. The skip
            # also satisfies a restored entry's seeded marker: the
            # compile this restore was bracing for just happened
            with self._l:
                ent = self._stats.get(key)
                if ent is not None and len(ent) > 2:
                    ent[2] = False
            return
        per_lane = seconds / max(lanes, 1)
        with self._l:
            ent = self._stats.get(key)
            if ent is None:
                # compile walls never reach this point, so the first
                # recorded sample is already a steady-state one
                self._stats[key] = [per_lane, 1]
            elif len(ent) > 2 and ent[2]:
                # entry restored from a persisted snapshot whose
                # this-process compile was NOT caught by the trace
                # rule (e.g. the shape was traced earlier in-process):
                # drop one sample defensively rather than blend a
                # possible compile wall into a good persisted EWMA
                ent[2] = False
            else:
                ent[0] += self.ALPHA * (per_lane - ent[0])
                ent[1] += 1

    def estimate(self, arm: str, n_pad: int) -> Optional[float]:
        ent = self._stats.get((arm, n_pad))
        if ent is None or ent[1] < self.MIN_SAMPLES:
            return None
        return ent[0]

    def best(self, arms, n_pad: int) -> Optional[float]:
        vals = [v for v in (self.estimate(a, n_pad) for a in arms)
                if v is not None]
        return min(vals) if vals else None

    def probe_due(self) -> bool:
        with self._l:
            self._probe += 1
            return self._probe % self.PROBE_EVERY == 0

    # -- seeding (ISSUE 7: kill the cold start) ------------------------
    def seed(self, arm: str, n_pad: int, seconds: float,
             lanes: int = 1) -> None:
        """Install a steady-state measurement at MIN_SAMPLES weight so
        the very first organic dispatch decision at this shape is
        measured, not cold. A seed never overrides an entry that is
        already warm from live traffic."""
        per_lane = seconds / max(lanes, 1)
        with self._l:
            ent = self._stats.get((arm, n_pad))
            if ent is None or ent[1] < self.MIN_SAMPLES:
                self._stats[(arm, n_pad)] = [per_lane, self.MIN_SAMPLES]

    def promote(self, n_pad: int) -> int:
        """Calibration epilogue: entries at this shape count as warm
        (samples -> MIN_SAMPLES) so routing engages off the
        calibration run instead of waiting for 3+ organic samples.
        Safe because compile walls never enter the stats at all
        (observe's `compiled` flag) — any recorded sample is a
        steady-state one."""
        bumped = 0
        with self._l:
            for (_arm, np_), ent in self._stats.items():
                if np_ == n_pad and 1 <= ent[1] < self.MIN_SAMPLES:
                    ent[1] = self.MIN_SAMPLES
                    bumped += 1
        return bumped

    def load_snapshot(self, snap: Dict[str, dict]) -> int:
        """Restore persisted measurements (the snapshot() format, JSON
        next to the WAL snapshot): each entry installs at MIN_SAMPLES
        weight with a seeded marker so the first live observation —
        which pays this process's XLA compile — is dropped instead of
        blended. Entries already warm from live traffic win over the
        file."""
        loaded = 0
        for key_s, ent_d in (snap or {}).items():
            try:
                arm, np_s = key_s.rsplit("@", 1)
                n_pad = int(np_s)
                ewma = float(ent_d["ewma_s"])
            except (ValueError, KeyError, TypeError, AttributeError):
                continue
            with self._l:
                ent = self._stats.get((arm, n_pad))
                if ent is None or ent[1] < self.MIN_SAMPLES:
                    self._stats[(arm, n_pad)] = [ewma, self.MIN_SAMPLES,
                                                 True]
                    loaded += 1
        return loaded

    def snapshot(self) -> Dict[str, dict]:
        with self._l:
            return {f"{arm}@{n_pad}": {"ewma_s": round(ent[0], 6),
                                       "samples": ent[1]}
                    for (arm, n_pad), ent in sorted(self._stats.items())}


SOLO_ARMS = ("chunked", "kway", "scan")
BATCHED_ARMS = ("chunked_batched", "kway_batched", "scan_batched")

# process-wide: every SelectKernel (workers, gateways, benches) feeds
# and reads the same measured numbers
cost_model = DispatchCostModel()


class kernel_span:
    """One dispatch's `kernel` window — dispatch through result
    availability and host unpack/expand; packing and argument
    placement stay outside, in `kernel_pack`. The one call an arm
    makes: the stage report and its span land on every trace of the
    thread context (the dispatching eval's, or each lane's of a
    batched gateway fire) with (arm, n_pad, lanes, fresh-compile), and
    the window's wall feeds the cost model and the device stats. That
    wall is read inside the span, before its report is made, so the
    router's input does not carry the recorder's cost. A dispatch that
    raises is reported but never measured."""

    __slots__ = ("arm", "n_pad", "lanes", "fresh", "on_cpu", "_span",
                 "_t0")

    def __init__(self, arm: str, n_pad: int, lanes: int = 1,
                 fresh: bool = False, on_cpu: bool = False):
        self.arm = arm
        self.n_pad = n_pad
        self.lanes = lanes
        self.fresh = fresh
        self.on_cpu = on_cpu

    def __enter__(self) -> "kernel_span":
        self._span = stages.span(
            "kernel", arm=self.arm + "@cpu" if self.on_cpu else self.arm,
            n_pad=int(self.n_pad), lanes=int(self.lanes),
            fresh=bool(self.fresh)) if stages.enabled else stages.NULL_SPAN
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        seconds = time.perf_counter() - self._t0
        self._span.__exit__(et, ev, tb)
        if et is None:
            cost_model.observe(self.arm, self.n_pad, seconds,
                               lanes=self.lanes, compiled=self.fresh,
                               on_cpu=self.on_cpu)
        return False


# -- device-economics accounting (ISSUE 11) ----------------------------
# The north star's device economics — pad waste, per-arm dispatch time,
# fresh compiles — were trapped inside pack_request/_note_trace/
# DispatchCostModel and never exported. These counters are ALWAYS on
# (the cost is two dict adds under a lock per pack/dispatch, next to
# milliseconds of numpy work); the telemetry collector
# (nomad_tpu/telemetry/) publishes them as `nomad.device.*` gauges and
# the bench artifact records the per-round snapshot.

_DEVICE_L = make_lock()
DEVICE_STATS: Dict[str, float] = {
    # Σ live rows vs Σ padded rows shipped: 1 - n/n_pad is the fraction
    # of every dispatch's node axis spent scoring padding
    "pad_n_sum": 0.0,
    "pad_npad_sum": 0.0,
    "packs": 0.0,
}
# per-arm accumulators: {arm: [dispatch_seconds_sum, dispatches,
# fresh_compiles]} — compile walls are INCLUDED in seconds (they are
# real wall clock the eval paid; the compile count alongside is what
# attributes them)
DEVICE_ARM_STATS: Dict[str, List[float]] = {}


def _note_pack(n: int, n_pad: int) -> None:
    with _DEVICE_L:
        DEVICE_STATS["pad_n_sum"] += n
        DEVICE_STATS["pad_npad_sum"] += n_pad
        DEVICE_STATS["packs"] += 1


def _note_dispatch(arm: str, seconds: float, compiled: bool) -> None:
    with _DEVICE_L:
        ent = DEVICE_ARM_STATS.get(arm)
        if ent is None:
            ent = DEVICE_ARM_STATS[arm] = [0.0, 0.0, 0.0]
        ent[0] += seconds
        ent[1] += 1
        if compiled:
            ent[2] += 1


def device_stats_snapshot() -> Dict[str, object]:
    """One read for the bench artifact and the telemetry collector:
    pad-waste ratio, per-arm dispatch seconds / dispatch counts /
    fresh-compile counts (arms suffixed @cpu ran on the host backend,
    @mesh on the sharded mesh), the measured round trip to the
    accelerator, and the device
    ops that failed into a host path (device_table.DEVICE_OP_FAILURES;
    a healthy run holds none)."""
    from .device_table import DEVICE_OP_FAILURES
    with _DEVICE_L:
        n_sum = DEVICE_STATS["pad_n_sum"]
        np_sum = DEVICE_STATS["pad_npad_sum"]
        packs = DEVICE_STATS["packs"]
        arms = {a: list(v) for a, v in DEVICE_ARM_STATS.items()}
        routing = {"accel_rtt_s": (_accel_rtt_cache[0]
                                   if _accel_rtt_cache else None)}
    return {
        "routing": routing,
        "device_op_failures": dict(DEVICE_OP_FAILURES),
        "pad_waste_ratio": round(1.0 - (n_sum / np_sum), 4)
        if np_sum > 0 else 0.0,
        "pad_rows_live": n_sum,
        "pad_rows_shipped": np_sum,
        "packs": packs,
        "dispatch_s": {a: round(v[0], 4) for a, v in sorted(
            arms.items())},
        "dispatches": {a: int(v[1]) for a, v in sorted(arms.items())},
        "compiles": {a: int(v[2]) for a, v in sorted(arms.items())},
    }


def device_hbm_bytes() -> float:
    """Device HBM in use where the backend exposes it (jax
    memory_stats; the TPU runtime reports bytes_in_use, the CPU backend
    returns None): 0.0 when unavailable. Host-side runtime
    introspection — no device sync involved."""
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return 0.0
    return float(stats["bytes_in_use"])


def calibrate_cost_model(n: int, count: int = 16, lanes: int = 2,
                         kernel: Optional["SelectKernel"] = None
                         ) -> Dict[str, dict]:
    """Startup calibration probe (ISSUE 7): measure the solo and the
    batched dispatch arms at the live table shape with synthetic
    requests and seed the process-wide cost model, so batched lanes are
    cost-favored (or correctly demoted) from the FIRST organic dispatch
    instead of after 3+ organic samples — the 1-in-16 exploration probe
    never fires inside short scenarios (BENCH_r05:
    service_broker_batches=0 for the whole service run).

    Two dispatches per arm: the first pays XLA compile (the cost
    model's replace-first-sample rule discards it), the second is the
    steady-state number; promote() then lifts both arms to engagement
    weight. All timing flows through select()/select_many(), which
    block on the result transfer via the `_stage_get` fence — no raw
    host syncs here (lint: host-sync stays clean). Returns the cost
    model snapshot at this shape for logging/benches."""
    k = kernel or SelectKernel()
    n_pad = _pad_n(n)
    cap = np.tile(np.array([[4000.0, 8192.0, 102400.0, 1000.0]],
                           np.float32), (n, 1))
    ask = np.array([100.0, 100.0, 10.0, 0.0], np.float32)

    def req():
        return SelectRequest(
            ask=ask, count=count, feasible=np.ones(n, bool),
            capacity=cap, used=np.zeros_like(cap),
            desired_count=float(count),
            tg_collisions=np.zeros(n, np.int32),
            job_count=np.zeros(n, np.int32))

    lanes = max(2, min(int(lanes), GATEWAY_MAX_LANES))
    for _ in range(3):          # compile round, then steady state
        k.select(req())
        k.select_many([req() for _ in range(lanes)])
    cost_model.promote(n_pad)
    snap = cost_model.snapshot()
    return {key: v for key, v in snap.items()
            if key.rsplit("@", 1)[-1] == str(n_pad)}


_accel_rtt_cache: List[float] = []


def _accel_roundtrip_s() -> float:
    """Measured host<->accelerator round-trip latency (put + get of a
    tiny buffer), taken once per process. The router below and the
    gateways' coalescing windows (server/worker.py) read it."""
    if _accel_rtt_cache:
        return _accel_rtt_cache[0]
    dev = jax.devices()[0]
    small = np.zeros(8, np.float32)
    # nomad-lint: allow[host-sync] intentional probe: the sync IS the RTT measurement
    jax.device_get(jax.device_put(small, dev))  # warm the path
    t0 = __import__("time").perf_counter()
    for _ in range(2):
        # nomad-lint: allow[host-sync] intentional probe: the sync IS the RTT measurement
        jax.device_get(jax.device_put(small, dev))
    rtt = max((__import__("time").perf_counter() - t0) / 2, 1e-5)
    _accel_rtt_cache.append(rtt)
    return rtt


def _cpu_device():
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


def decorrelation_slice(req, lane: int, total: int, cache,
                        by_room: bool = True):
    """The one shared decorrelation rule (used by both the worker's
    solo-select slicing and the BatchGateway's lane partition): a
    Knuth-mix hash assigns each node to one of `total` lanes; the
    request keeps its lane's slice only when the slice's aggregate
    capacity headroom covers ~2x the ask (so slicing is a throughput
    heuristic, never a feasibility change — callers retry on the full
    set). Returns (slice_mask or None, new_cache); `cache` is the
    caller's (key, lane_ids) memo."""
    if total <= 1:
        return None, cache
    feas = req.feasible
    n = len(feas)
    cache_key, lane_ids = cache
    if cache_key != (n, total):
        mix = (np.arange(n, dtype=np.uint64)
               * np.uint64(2654435761)) & np.uint64(0xffffffff)
        lane_ids = ((mix >> np.uint64(7)) % np.uint64(total)) \
            .astype(np.int32)
        cache = ((n, total), lane_ids)
    slice_mask = feas & (lane_ids == (lane % total))
    if int(slice_mask.sum()) < 8:
        return None, cache
    if not by_room:
        # an evicting ask: room is what the select is about to make,
        # so the slice is held to a count of nodes instead
        return (slice_mask if int(slice_mask.sum()) >= 2 * req.count
                else None), cache
    free = req.capacity - req.used
    with np.errstate(divide="ignore", invalid="ignore"):
        per = np.where(req.ask[None, :] > 0,
                       free / np.maximum(req.ask[None, :], 1e-9),
                       np.inf).min(axis=1)
    headroom = float(np.floor(per[slice_mask]).clip(min=0).sum())
    if headroom < 2.0 * req.count:
        return None, cache
    return slice_mask, cache


def couples_nodes(req) -> bool:
    """The ask's score (or its feasibility) at one node depends on
    what the same dispatch placed on others — spread, distinct_*,
    reserved-port exclusivity: the scan arm's asks. The rest are
    node-local and take the chunked or the K-way arm."""
    return bool(req.spreads or req.distinct_props or req.distinct_hosts
                or req.scan_exclusive)


def partition_lanes(reqs, lane_base: int, total: int, cache):
    """Decorrelate the lanes of ONE batched dispatch: identical argmax
    sequences would make every lane place on the same winners and
    collide in the plan applier (optimistic concurrency). Applies
    decorrelation_slice per lane — hash partition + capacity-aware
    headroom — mutating each request's feasible mask in place. Returns
    (originals, cache): the original masks (None where untouched) so a
    lane that can't fill its slice retries on the FULL set —
    partitioning is a throughput heuristic and must never change
    failure semantics. Shared by the per-batch rendezvous gateway and
    the micro-batch gateway (server/worker.py)."""
    lanes = len(reqs)
    total = max(total, lanes)
    originals = [None] * lanes
    if not reqs:
        return originals, cache
    n = len(reqs[0].feasible)
    for i, req in enumerate(reqs):
        if len(req.feasible) != n:
            continue
        slice_mask, cache = decorrelation_slice(
            req, lane_base + i, total, cache)
        if slice_mask is None:
            continue
        originals[i] = req.feasible
        req.feasible = slice_mask
        # the sliced mask no longer matches the device-resident copy
        req.feas_token = None
        req.feas_residue = None
    return originals, cache


class SelectKernel:
    """Host wrapper: pads request arrays, routes the dispatch to the
    best backend, and unpacks results.

    Routing: every dispatch runs on the default backend — the
    accelerator where there is one. NOMAD_TPU_SELECT_BACKEND=cpu forces
    the host CPU backend (arms then read `<arm>@cpu`); `accel` and
    `auto` both mean the default (_pick_device has the history).

    Two device kernels:
      - _select_chunked: node-local scoring (no spread/distinct/
        reserved-port exclusivity) places whole chunks per step —
        O(nodes-touched) instead of O(count) sequential steps.
      - _select_scan: the general one-instance-per-step scan.
    """

    def __init__(self, backend: Optional[str] = None):
        import os
        self.backend = backend or os.environ.get(
            "NOMAD_TPU_SELECT_BACKEND", "auto")
        self._mesh_tried = False
        self._sharded = None
        # cross-worker decorrelation (lane, lanes): concurrent workers
        # running exact-greedy argmax over the SAME table pick the SAME
        # winners and collide in the plan applier. When set (by the
        # scheduling worker), large batch selects restrict themselves
        # to a hash-partitioned slice of the feasible set — the
        # columnar analog of the reference's per-eval node shuffle
        # (stack.go:70-90) — retrying on the full set if the slice
        # can't hold the ask.
        self.decorrelate = None
        self._decor_cache = (None, None)

    def _mesh_sharded(self):
        """The production multi-chip path (SURVEY §2.6: shard the node
        axis instead of sampling it): when more than one device is
        visible on an accelerator backend — or NOMAD_TPU_MESH=1 forces
        it (tests/dryrun on the virtual CPU mesh) — dispatches route
        through a jax.sharding.Mesh over all devices (the process-wide
        instance; see get_shared_sharded)."""
        if self._mesh_tried:
            return self._sharded
        self._mesh_tried = True
        self._sharded = get_shared_sharded()
        return self._sharded

    def single_device(self) -> bool:
        """Every dispatch of this kernel runs on the default device: no
        mesh, no forced host backend. What a request that carries
        device-resident columns (SelectRequest.victims) needs."""
        return self._mesh_sharded() is None and self._pick_device() is None

    # -- routing -------------------------------------------------------
    def _pick_device(self):
        """The CPU device when NOMAD_TPU_SELECT_BACKEND=cpu forces the
        host backend, else None: the default placement, which on a
        machine with an accelerator is the accelerator, for every arm
        and count. There is no cost model between the two any more
        (PR 27): it compared a static prior fitted to a transport with
        ~700x the local chip's latency and, once warm, per-arm EWMAs
        that folded every count into one number — so the first three
        one-instance scans went to the host by the prior, the EWMA of
        those read faster than the chip's 50-instance scans, and whole
        windows of the service cell ran `scan@cpu` with the chip 99.97%
        idle, run by run differently."""
        if self.backend != "cpu" or jax.default_backend() == "cpu":
            return None
        return _cpu_device()

    @staticmethod
    def _place_args(args: Dict, dev) -> Dict:
        if dev is None:
            return args
        return {k: (jax.device_put(v, dev) if isinstance(v, np.ndarray)
                    and v.ndim > 0 else v)
                for k, v in args.items()}

    def _resident_args(self, req: SelectRequest, n_pad: int,
                       dev) -> Optional[Dict]:
        """Device-resident replacements for the table-shaped inputs
        (capacity, used0, free_ports) when the request's NodeTable
        carries a live mirror token (ops/device_table.py): capacity
        and free_ports come straight off the resident device arrays,
        and used0 is computed ON DEVICE as resident-used + the sparse
        per-eval plan overlay — no dense table column crosses the bus.
        Returns None (dense fallback) for stale tables, host-forced
        dispatches, or overlays too wide to scatter. Assembly shared
        with the mesh path (device_table.resident_request_args)."""
        if dev is not None:
            return None                 # mirror lives on the default device
        mirror = getattr(req.table, "device_mirror", None) \
            if req.table is not None else None
        if mirror is None:
            return None
        from .device_table import resident_request_args
        return resident_request_args(mirror, req, n_pad,
                                     "nomad.select.resident")

    # -- entry ---------------------------------------------------------
    def select(self, req: SelectRequest) -> SelectResult:
        original = self._decorrelate_mask(req)
        res = self._select(req)
        if original is not None and res.placed < req.count:
            # the slice couldn't hold the ask: decorrelation is a
            # throughput heuristic and must never change failure
            # semantics — retry on the full node set
            req.feasible = original
            res = self._select(req)
        return res

    def _decorrelate_mask(self, req: SelectRequest):
        """Restrict a large batch select to this worker's hash slice of
        the feasible set when the slice's aggregate headroom still
        covers ~2x the ask. Returns the original feasible mask (caller
        restores it on shortfall) or None when untouched."""
        dec = self.decorrelate
        if dec is None:
            return None
        lane, lanes = dec
        # an ask that carries the victims' columns takes its lane at
        # ANY count: every worker reads the same columns and the same
        # ties (thousands of full nodes score alike), so two of them
        # chase one frontier node, and a one-instance eval was seen to
        # lose that race 37 times in a row and fail (PERF.md section 6,
        # PR 34). Which of tied nodes evicts, nothing ranks
        evicting = req.victims is not None
        if req.count < 256 and not evicting:
            return None
        slice_mask, cache = decorrelation_slice(
            req, lane, lanes, self._decor_cache, by_room=not evicting)
        self._decor_cache = cache
        if slice_mask is None:
            return None
        feas = req.feasible
        req.feasible = slice_mask
        req.feas_token = None
        req.feas_residue = None
        return feas

    def _select(self, req: SelectRequest) -> SelectResult:
        _sanitize_request(req)
        sharded = self._mesh_sharded()
        if sharded is not None:
            chunk_ok = not couples_nodes(req)
            n_pad_sh = sharded.pad_to_shards(len(req.feasible))
            if chunk_ok and req.count > 512 and n_pad_sh > KWAY_W:
                # the @mesh windows include packing and sharded
                # placement (the mesh path's h2d; no kernel_pack
                # beside them); they only feed the per-arm device
                # stats, never the single-device routing estimates
                spread_alg = req.algorithm == "spread"
                w = _kway_w(n_pad_sh)
                k_out = _bucket_k(req.count)
                fresh = _note_trace("kway@mesh", n_pad_sh,
                                    max_steps=_kway_steps(w),
                                    spread_alg=spread_alg, w=w,
                                    k_out=k_out)
                with kernel_span("kway@mesh", n_pad_sh, fresh=fresh):
                    # big batches keep the K-way kernel on the mesh:
                    # the same SPMD program, node axis sharded,
                    # top-k/gather collectives inserted by XLA;
                    # table-shaped columns come off the mesh-resident
                    # table when the request carries a live mirror token
                    args, _statics = pack_request(req, n_pad_sh)
                    cargs = sharded.place_chunked_args(
                        {k: args[k] for k in _CHUNKED_ARGS},
                        capacity_src=req.capacity, req=req)
                    with sharded.mesh:
                        pending = _select_kway(**cargs,
                                               max_steps=_kway_steps(w),
                                               spread_alg=spread_alg, w=w,
                                               k_out=k_out)
                    return self._finish_kway(req, cargs, spread_alg,
                                             pending, w=w)
            return sharded.select(req)      # observes scan@mesh itself
        n = len(req.feasible)
        n_pad = _pad_n(n)
        if not couples_nodes(req) and req.victims is None:
            dev = self._pick_device()
            if req.count > 512 and n_pad > KWAY_W:
                # big batches: K-way phases place on the top-32 nodes at
                # once — an order of magnitude fewer sequential steps
                return self._run_kway(req, n_pad, dev)
            return self._run_chunked(req, n_pad, dev)
        dev = self._pick_device()
        k = _bucket_k(max(req.count, 1))
        if req.victims is not None:
            # one scan length for every small preempting ask: a padded
            # step is a masked pass over the node axis (microseconds),
            # a bucket of its own another compile in every warm-up
            k = max(k, VICTIMS_SCAN_STEPS)
        with stages.span("kernel_pack"):
            args, statics = pack_request(req, n_pad)
            args = self._place_args(args, dev)
            resident = self._resident_args(req, n_pad, dev)
            if resident:
                args.update(resident)
            if req.victims is not None:
                v = req.victims
                args.update(capacity=v.capacity, used0=v.used_after,
                            pre_score=v.pre_score)
        fresh = _note_trace("scan", n_pad, k_steps=k,
                            cpu=dev is not None, **statics)
        with kernel_span("scan" + ("@cpu" if dev is not None else ""),
                         n_pad, fresh=fresh):
            _carry, outs = _select_scan(**args, k_steps=k, **statics)
            return unpack_result(req, outs)

    # -- k-way chunked path --------------------------------------------
    def _pack_kway(self, req: SelectRequest, n_pad: int, dev):
        """Pack + place the K-way kernel args; returns
        (cargs, spread_alg, w). Split from the dispatch so the cost
        model's window starts at the dispatch, like the other arms."""
        with stages.span("kernel_pack"):
            args, _statics = pack_request(req, n_pad)
            cargs = {k: args[k] for k in _CHUNKED_ARGS}
            cargs = self._place_args(cargs, dev)
            resident = self._resident_args(req, n_pad, dev)
            if resident:
                cargs.update(resident)
        return cargs, req.algorithm == "spread", _kway_w(n_pad)

    def _finish_kway(self, req: SelectRequest, cargs, spread_alg,
                     pending, w: int) -> SelectResult:
        rounds = self._finish_kway_rounds(req, cargs, spread_alg,
                                          pending, w=w)
        with stages.span("kernel_expand", **_kway_counts([rounds])):
            return _expand_kway(req, rounds)

    def _run_kway(self, req: SelectRequest, n_pad: int,
                  dev) -> SelectResult:
        cargs, spread_alg, w = self._pack_kway(req, n_pad, dev)
        k_out = _bucket_k(req.count)
        fresh = _note_trace("kway", n_pad, max_steps=_kway_steps(w),
                            spread_alg=spread_alg, w=w, k_out=k_out,
                            cpu=dev is not None)
        # window matches every other arm: dispatch through
        # unpack/expand, packing/placement excluded
        with kernel_span("kway" + ("@cpu" if dev is not None else ""),
                         n_pad, fresh=fresh):
            pending = _select_kway(**cargs, max_steps=_kway_steps(w),
                                   spread_alg=spread_alg, w=w,
                                   k_out=k_out)
            return self._finish_kway(req, cargs, spread_alg, pending,
                                     w=w)

    def select_many(self, reqs: List[SelectRequest]) -> List[SelectResult]:
        """Place B independent requests over the SAME node table in one
        device dispatch (vmapped K-way kernel) — multi-eval batching per
        SURVEY §2.6; the production caller is the worker's batched eval
        drain (server/worker.py process_eval_batch). Under mesh routing
        the batched kernel runs SPMD with the node axis sharded and the
        batch axis replicated. Falls back to sequential select() for
        shapes the K-way kernel doesn't cover — mixed capacity tables
        (evals against different snapshots) are counted on the
        nomad.select.batch_fallback metric so a silent serialization
        regression stays visible. Results are bit-identical to
        per-request select()."""
        if not reqs:
            return []
        for r in reqs:
            _sanitize_request(r)
        from ..utils import metrics
        sharded = self._mesh_sharded()
        n = len(reqs[0].feasible)
        n_pad = sharded.pad_to_shards(n) if sharded is not None \
            else _pad_n(n)
        shared_table = all(len(r.feasible) == n
                           and r.capacity is reqs[0].capacity
                           and r.algorithm == reqs[0].algorithm
                           for r in reqs)
        def _chunk_ok(r):
            return not couples_nodes(r)

        # small/medium chunk-eligible batches take the vmapped CHUNKED
        # kernel: steps ~ slowest lane's nodes-touched, the same
        # algorithm the solo path uses — batched without paying the
        # K-way phase machinery
        if len(reqs) > 1 and shared_table and \
                all(_chunk_ok(r) and r.count <= 512 for r in reqs):
            metrics.incr_counter("nomad.select.batch_dispatch")
            return self._run_chunked_batched(reqs, n_pad, sharded)

        # small-count batches needing the full scoring surface
        # (spreads, distinct-property, reserved ports) take the vmapped
        # SCAN — count is the step bound, so this stays cheap only for
        # small counts
        if len(reqs) > 1 and shared_table and \
                all(r.count <= SCAN_BATCH_MAX for r in reqs):
            metrics.incr_counter("nomad.select.batch_dispatch")
            return self._run_scan_batched(reqs, n_pad, sharded)

        eligible = (len(reqs) > 1 and n_pad > KWAY_W and shared_table
                    and all(_chunk_ok(r) for r in reqs))
        if not eligible:
            if len(reqs) > 1:
                # ANY multi-request batch that serializes is the
                # regression this counter exists to expose — mixed
                # snapshots (not shared_table) and shapes no batched
                # arm covers both count
                metrics.incr_counter("nomad.select.batch_fallback")
            return [self.select(r) for r in reqs]
        metrics.incr_counter("nomad.select.batch_dispatch")

        with stages.span("kernel_pack"):
            packs = [pack_request(r, n_pad)[0] for r in reqs]
            cargs = self._pad_and_stack(packs, _CHUNKED_ARGS)
            spread_alg = reqs[0].algorithm == "spread"
            cargs, mesh_ctx, on_cpu = self._place_batched(
                cargs, sharded, reqs[0].capacity, table=reqs[0].table)
        w = _kway_w(n_pad)
        k_out = _bucket_k(max(r.count for r in reqs))
        fresh = _note_trace("kway_batched", n_pad,
                            max_steps=_kway_steps(w),
                            spread_alg=spread_alg, w=w, k_out=k_out,
                            lanes=len(cargs["k_valid"]), cpu=on_cpu)
        # window includes per-lane unpack/expand so the number compares
        # end-to-end against the solo arms (which include theirs)
        with kernel_span("kway_batched", n_pad, lanes=len(reqs),
                         fresh=fresh, on_cpu=on_cpu):
            return self._kway_batched_results(
                reqs, cargs, mesh_ctx, spread_alg, w, k_out)

    def _kway_batched_results(self, reqs, cargs, mesh_ctx, spread_alg,
                              w: int, k_out: int) -> List[SelectResult]:
        """The kway_batched arm's `kernel` window: one vmapped dispatch,
        per-lane overflow continued solo, then every lane expanded."""
        with mesh_ctx:
            carry, outs = _select_kway_batched(**cargs,
                                               max_steps=_kway_steps(w),
                                               spread_alg=spread_alg,
                                               w=w, k_out=k_out)
        packed_i, packed_f = _stage_get(outs)
        lane_rounds = []
        for i, req in enumerate(reqs):
            rounds = [(packed_i[i], packed_f[i])]
            if _kway_continues(packed_i[i]):
                # rare overflow of the phase budget: continue this lane
                # on the single-request kernel from its carry state
                # host copies: the continuation runs on the default
                # single-device path even when the batch ran sharded —
                # pulled through the d2h fence so the bench attributes
                # the transfer (lint: host-sync)
                lane = {k: (np.asarray(_stage_get(cargs[k]))
                            if k == "capacity"
                            else np.asarray(_stage_get(cargs[k][i])))
                        for k in _CHUNKED_ARGS}
                used0, tg0, fp0, ds0 = _stage_get(
                    (carry[0][i], carry[1][i], carry[2][i],
                     carry[3][i]))
                lane.update(
                    used0=np.asarray(used0),
                    tg_coll0=np.asarray(tg0),
                    free_ports=np.asarray(fp0),
                    dev_slots0=np.asarray(ds0),
                    k_valid=packed_i[i][-1, KWAY_REMAINING])
                pending = _select_kway(**lane,
                                       max_steps=_kway_steps(w),
                                       spread_alg=spread_alg, w=w,
                                       k_out=_bucket_k(req.count))
                cont = self._finish_kway_rounds(req, lane, spread_alg,
                                                pending, w=w)
                rounds.extend(cont)
            lane_rounds.append(rounds)
        with stages.span("kernel_expand", **_kway_counts(lane_rounds)):
            return [_expand_kway(req, rounds)
                    for req, rounds in zip(reqs, lane_rounds)]

    @staticmethod
    def _pad_and_stack(packs: List[Dict], arg_names) -> Dict:
        """Shared lane assembly for every batched arm: pad the lane
        axis to a power of two (each distinct B is its own XLA
        compile — widths must land on warmable buckets; padding lanes
        carry k_valid=0 and place nothing) and
        stack per-lane arrays. Capacity stays unstacked — all lanes
        share one table, which is the batching precondition."""
        bp = 1
        while bp < len(packs):
            bp *= 2
        if bp > len(packs):
            dummy = dict(packs[0])
            dummy["k_valid"] = np.int32(0)
            packs = packs + [dummy] * (bp - len(packs))
        cargs = {}
        for name in arg_names:
            if name == "capacity":
                cargs[name] = packs[0][name]
            else:
                cargs[name] = np.stack([p[name] for p in packs])
        return cargs

    def _place_batched(self, cargs: Dict, sharded, capacity_src,
                       table=None):
        """Device placement for a stacked batch: mesh shardings when
        sharded (node axis split, lane axis replicated, capacity on the
        mesh-resident table / identity cache), else the default
        backend unless the host is forced. Returns (placed_cargs,
        mesh_context, on_cpu)."""
        import contextlib
        if sharded is not None:
            placed = sharded.place_batched_chunked_args(
                cargs, capacity_src=capacity_src, table=table)
            return placed, sharded.mesh, False
        dev = self._pick_device()
        return (self._place_args(cargs, dev), contextlib.nullcontext(),
                dev is not None)

    def batch_dispatch_profitable(self, n: int, count_hint: int = 16,
                                  tolerance: float = 1.0) -> bool:
        """Should the worker coalesce evals into gateway lanes?

        Recalibrated (BENCH_r05: the static model demoted every broker
        lane on real TPU even where batching measured 1.42-1.61x):
        once the cost model holds MEASURED per-lane dispatch costs for
        both a batched arm and a solo arm at this table shape, the
        decision is measured-batched < measured-solo * tolerance.
        Until the batched side is warm, a periodic probe lets lanes
        fire so the measurement exists at all. The static fallback
        remains: batch only when the dispatch runs on an accelerator
        (on the host backend B solo chunked dispatches beat one
        vmapped dispatch and the GIL serializes lane host work).
        Overridable with NOMAD_TPU_EVAL_BATCH=force|off (tests force
        lanes on CPU hosts).

        `tolerance` > 1 is the continuous-batching caller's setting
        (server/worker.py MicroBatchGateway): the per-lane EWMA folds
        ALL batch widths together, so on shapes where width 2 measures
        ~parity and width 8 wins, a strict < would flap coalescing off
        exactly when occupancy could grow — coalesce unless the
        batched arm measures DECISIVELY slower."""
        import os
        mode = os.environ.get("NOMAD_TPU_EVAL_BATCH", "auto")
        if mode == "force":
            return True
        if mode == "off":
            return False
        if self._mesh_sharded() is not None:
            return True
        n_pad = _pad_n(n)
        solo = cost_model.best(SOLO_ARMS, n_pad)
        batched = cost_model.best(BATCHED_ARMS, n_pad)
        if solo is not None and batched is not None:
            if batched < solo * tolerance:
                return True
            # measured demote — but keep the batched EWMA fresh: a
            # stale number (device contention, early-sample noise)
            # must not demote lanes forever, so probe at the same
            # exploration cadence
            return cost_model.probe_due()
        if jax.default_backend() == "cpu":
            return False
        if solo is not None and batched is None and \
                cost_model.probe_due():
            return True                 # exploration: measure a batch
        return self._pick_device() is None

    def _run_chunked_batched(self, reqs: List[SelectRequest], n_pad: int,
                             sharded) -> List[SelectResult]:
        """B chunk-eligible lanes through the vmapped chunked kernel in
        one dispatch; per-lane overflow continues on the solo kernel.
        Bit-identical to per-request select()."""
        spread_alg = reqs[0].algorithm == "spread"
        maxc = max(r.count for r in reqs)
        max_steps = 64 if maxc <= 64 else 512
        with stages.span("kernel_pack"):
            packs = [pack_request(r, n_pad)[0] for r in reqs]
            cargs = self._pad_and_stack(packs, _CHUNKED_ARGS)
            cargs, mesh_ctx, on_cpu = self._place_batched(
                cargs, sharded, reqs[0].capacity, table=reqs[0].table)
        fn = _chunked_batched_jit(max_steps, spread_alg)
        fresh = _note_trace("chunked_batched", n_pad,
                            max_steps=max_steps, spread_alg=spread_alg,
                            lanes=len(cargs["k_valid"]), cpu=on_cpu)
        # window includes per-lane unpack/expand so the number compares
        # end-to-end against the solo arms (which include theirs)
        with kernel_span("chunked_batched", n_pad, lanes=len(reqs),
                         fresh=fresh, on_cpu=on_cpu):
            return self._chunked_batched_results(
                reqs, fn, cargs, mesh_ctx, spread_alg)

    def _chunked_batched_results(self, reqs, fn, cargs, mesh_ctx,
                                 spread_alg) -> List[SelectResult]:
        """The chunked_batched arm's `kernel` window."""
        with mesh_ctx:
            carry, outs = fn(*[cargs[nm] for nm in _CHUNKED_ARGS])
        outs_np = _stage_get(outs)
        lane_rounds = []
        for i, req in enumerate(reqs):
            (choice, chunk, ti, ts, exh, feas, rem, steps) = \
                (a[i] for a in outs_np)
            steps = int(steps)
            rem = int(rem)
            rounds = [(choice[:steps], chunk[:steps], ti[:steps],
                       ts[:steps], exh[:steps], feas[:steps])]
            if rem > 0 and steps > 0 and chunk[steps - 1] != 0:
                # step-budget overflow: continue this lane solo from
                # its carry (host copies; the default device path) —
                # pulled through the d2h fence (lint: host-sync)
                lane = {nm: (np.asarray(_stage_get(cargs[nm]))
                             if nm == "capacity"
                             else np.asarray(_stage_get(cargs[nm][i])))
                        for nm in _CHUNKED_ARGS}
                used0, tg0, fp0, ds0 = _stage_get(
                    (carry[0][i], carry[1][i], carry[2][i],
                     carry[3][i]))
                lane.update(
                    used0=np.asarray(used0),
                    tg_coll0=np.asarray(tg0),
                    free_ports=np.asarray(fp0),
                    dev_slots0=np.asarray(ds0),
                    k_valid=np.int32(rem))
                rounds.extend(self._chunked_rounds(lane, spread_alg))
            lane_rounds.append(rounds)
        with stages.span("kernel_expand"):
            return [_expand_chunks(req, rounds)
                    for req, rounds in zip(reqs, lane_rounds)]

    @staticmethod
    def _chunked_rounds(cargs: Dict, spread_alg: bool,
                        max_steps: int = 4096) -> List:
        """Continuation rounds on the solo chunked kernel until the
        remaining count drains (shared by the batched arm's overflow
        path)."""
        rounds = []
        while True:
            (used, coll, freep, devs), outs = _select_chunked(
                **cargs, max_steps=max_steps, spread_alg=spread_alg)
            (choice, chunk, ti, ts, exh, feas,
             rem, steps) = _stage_get(outs)
            steps = int(steps)
            rem = int(rem)
            rounds.append((choice[:steps], chunk[:steps], ti[:steps],
                           ts[:steps], exh[:steps], feas[:steps]))
            if rem <= 0 or steps == 0 or chunk[steps - 1] == 0:
                break
            cargs.update(used0=used, tg_coll0=coll, free_ports=freep,
                         dev_slots0=devs, k_valid=np.int32(rem))
        return rounds

    def _run_scan_batched(self, reqs: List[SelectRequest], n_pad: int,
                          sharded) -> List[SelectResult]:
        """B lanes through the vmapped scan kernel in one dispatch;
        results are bit-identical to per-request select() (the chunked
        and K-way solo paths are proven scan-equivalent)."""
        spread_alg = reqs[0].algorithm == "spread"
        k = _bucket_k(max(max(r.count, 1) for r in reqs))
        with stages.span("kernel_pack"):
            packs = []
            s_live = p_live = 0
            for r in reqs:
                args, st = pack_request(r, n_pad)
                packs.append(args)
                s_live = max(s_live, st["s_live"])
                p_live = max(p_live, st["p_live"])
            cargs = self._pad_and_stack(packs, _SCAN_ARGS)
            cargs, mesh_ctx, on_cpu = self._place_batched(
                cargs, sharded, reqs[0].capacity, table=reqs[0].table)
        fn = _scan_batched_jit(k, spread_alg, s_live, p_live)
        fresh = _note_trace("scan_batched", n_pad, k_steps=k,
                            s_live=s_live, p_live=p_live,
                            lanes=len(cargs["k_valid"]), cpu=on_cpu)
        # window includes per-lane unpack so the number compares
        # end-to-end against the solo arms (which include theirs)
        with kernel_span("scan_batched", n_pad, lanes=len(reqs),
                         fresh=fresh, on_cpu=on_cpu):
            with mesh_ctx:
                _carry, outs = fn(*[cargs[nm] for nm in _SCAN_ARGS])
            outs_np = _stage_get(outs)
            with stages.span("kernel_expand"):
                return [_unpack_fetched(r, tuple(a[i] for a in outs_np))
                        for i, r in enumerate(reqs)]

    def _finish_kway_rounds(self, req, cargs, spread_alg, pending,
                            w: int):
        """The dispatch's payloads fetched, and those of its
        continuations while one ran out of its phase budget (no
        expansion) — shared by the batched path's per-lane overflow
        handling."""
        rounds = []
        while True:
            (used, coll, freep, devs), outs = pending
            packed_i, packed_f = _stage_get(outs)
            rounds.append((packed_i, packed_f))
            if not _kway_continues(packed_i):
                return rounds
            cargs.update(used0=used, tg_coll0=coll, free_ports=freep,
                         dev_slots0=devs,
                         k_valid=packed_i[-1, KWAY_REMAINING])
            pending = _select_kway(**cargs, max_steps=_kway_steps(w),
                                   spread_alg=spread_alg, w=w,
                                   k_out=_bucket_k(req.count))

    # -- chunked path --------------------------------------------------
    def _run_chunked(self, req: SelectRequest, n_pad: int,
                     dev) -> SelectResult:
        with stages.span("kernel_pack"):
            args, _statics = pack_request(req, n_pad)
            cargs = {k: args[k] for k in _CHUNKED_ARGS}
            cargs = self._place_args(cargs, dev)
            resident = self._resident_args(req, n_pad, dev)
            if resident:
                cargs.update(resident)
        spread_alg = req.algorithm == "spread"
        # near-equal node scores make chunks short (each placement is
        # overtaken after 1-2 instances), so a big count can need
        # thousands of steps — every continuation round is a full
        # host<->device round trip, so size the on-device step budget
        # to finish big batches in ONE dispatch
        if req.count <= 64:
            max_steps = 64
        elif req.count <= 512:
            max_steps = 512
        elif req.count <= 4096:
            max_steps = 4096
        else:
            max_steps = 16384       # covers count<=16384 in one dispatch
                                    # (a step always places >=1 or stops)
        fresh = _note_trace("chunked", n_pad, max_steps=max_steps,
                            spread_alg=spread_alg, cpu=dev is not None)
        with kernel_span("chunked" + ("@cpu" if dev is not None else ""),
                         n_pad, fresh=fresh):
            # an infeasible ask stops after one round, an exhausted
            # step budget continues from the device-resident carry
            rounds = self._chunked_rounds(cargs, spread_alg, max_steps)
            with stages.span("kernel_expand"):
                return _expand_chunks(req, rounds)


def _expand_chunks(req: SelectRequest, rounds) -> SelectResult:
    """Host-side expansion of per-step (node, chunk) results into the
    per-instance SelectResult the callers expect. Per-instance scores
    are recomputed with the same float32 node-local formula the kernel
    uses (each instance in a chunk sees the usage its predecessors left
    behind, exactly like the scan)."""
    n = len(req.feasible)
    k_total = req.count
    d = req.capacity.shape[1]
    ask = np.asarray(req.ask, np.float32)
    spread_alg = req.algorithm == "spread"
    desired = np.float32(max(req.desired_count, 1.0))

    node_idx = np.full(k_total, -1, np.int32)
    final = np.zeros(k_total, np.float32)
    s_bin = np.zeros(k_total, np.float32)
    s_anti = np.zeros(k_total, np.float32)
    s_pen = np.zeros(k_total, np.float32)
    s_aff = np.zeros(k_total, np.float32)
    s_dev = np.zeros(k_total, np.float32)
    s_pre = np.zeros(k_total, np.float32)
    top_i = np.full((k_total, TOP_K), -1, np.int32)
    top_s = np.full((k_total, TOP_K), NEG_INF, np.float32)
    exh_out = np.zeros((k_total, d), np.int32)

    aff_col = None
    if req.affinity is not None and req.affinity_sum_weights > 0:
        aff_col = (req.affinity / req.affinity_sum_weights).astype(np.float32)
    pen_col = req.penalty
    dev_col = req.dev_score if req.dev_fires else None
    pre_col = req.pre_score

    pos = 0
    extra = {}                               # node -> already placed here
    fail = None
    # the kernel materializes top-k/exhaustion meta only on the first
    # and failing steps; ordinary steps carry sentinels and reuse the
    # dispatch-level snapshot
    last_meta = None
    for (choice, chunk, ti, ts, exh, _feas) in rounds:
        for s in range(len(choice)):
            c = int(choice[s])
            m = int(chunk[s])
            if exh[s][0] >= 0:
                last_meta = (ti[s], ts[s], exh[s])
            if m <= 0 or c < 0:
                fail = last_meta
                continue
            m = min(m, k_total - pos)
            prior = extra.get(c, 0)
            a = np.arange(m, dtype=np.float32)
            after = (req.used[c].astype(np.float32)[None, :]
                     + (prior + a[:, None] + 1.0) * ask)
            cap_cpu = np.float32(max(req.capacity[c, 0], 1e-9))
            cap_mem = np.float32(max(req.capacity[c, 1], 1e-9))
            free_cpu = np.float32(1.0) - after[:, 0] / cap_cpu
            free_mem = np.float32(1.0) - after[:, 1] / cap_mem
            total = (np.power(np.float32(10.0), free_cpu)
                     + np.power(np.float32(10.0), free_mem))
            if spread_alg:
                fit_score = np.clip(total - 2.0, 0.0, 18.0)
            else:
                fit_score = np.clip(20.0 - total, 0.0, 18.0)
            binp = (fit_score / np.float32(18.0)).astype(np.float32)
            coll = np.float32(req.tg_collisions[c]) + np.float32(prior) + a
            anti_fires = coll > 0
            anti = np.where(anti_fires, -(coll + 1.0) / desired,
                            0.0).astype(np.float32)
            pen_f = bool(pen_col[c]) if pen_col is not None else False
            pen = np.float32(-1.0 if pen_f else 0.0)
            aff = np.float32(aff_col[c]) if aff_col is not None else \
                np.float32(0.0)
            dev = np.float32(dev_col[c]) if dev_col is not None else \
                np.float32(0.0)
            pre = np.float32(pre_col[c]) if pre_col is not None else \
                np.float32(0.0)
            fired = (1.0 + anti_fires.astype(np.float32)
                     + np.float32(1.0 if pen_f else 0.0)
                     + np.float32(1.0 if aff != 0.0 else 0.0)
                     + np.float32(1.0 if dev_col is not None else 0.0)
                     + np.float32(1.0 if pre != 0.0 else 0.0))
            fin = ((binp + anti + pen + aff + dev + pre)
                   / fired).astype(np.float32)

            sl = slice(pos, pos + m)
            node_idx[sl] = c
            final[sl] = fin
            s_bin[sl] = binp
            s_anti[sl] = anti
            s_pen[sl] = pen
            s_aff[sl] = aff
            s_dev[sl] = dev
            s_pre[sl] = pre
            m_ti, m_ts, m_exh = last_meta if last_meta is not None \
                else (ti[s], ts[s], np.zeros_like(exh[s]))
            top_i[sl] = np.where(m_ti >= n, -1, m_ti)
            top_s[sl] = m_ts
            exh_out[sl] = np.maximum(m_exh, 0)
            extra[c] = prior + m
            pos += m
    if fail is not None and pos < k_total:
        ti_f, ts_f, exh_f = fail
        top_i[pos:] = np.where(ti_f >= n, -1, ti_f)
        top_s[pos:] = ts_f
        exh_out[pos:] = exh_f

    considered = req.n_considered if req.n_considered is not None else n
    return _sanitize_result(req, SelectResult(
        node_idx=node_idx,
        final_score=final,
        scores={"binpack": s_bin, "job-anti-affinity": s_anti,
                "node-reschedule-penalty": s_pen,
                "node-affinity": s_aff,
                "allocation-spread": np.zeros(k_total, np.float32),
                "devices": s_dev, "preemption": s_pre},
        top_idx=top_i, top_scores=top_s,
        nodes_evaluated=considered,
        nodes_filtered=int(considered - np.count_nonzero(req.feasible)),
        exhausted_dim=exh_out,
        placed=pos,
    ))


# -- kernel-cache governance (governor/registry.py) --------------------

def kernel_cache_stats() -> Dict[str, int]:
    """Entry counts for the shape-keyed JIT caches this module owns.
    The batched-lane caches are true LRUs (KERNEL_CACHE_MAX); the
    plain jitted kernels report jax's internal per-function cache
    size."""
    out = {"scan_batched": _scan_batched_jit.cache_info().currsize,
           "chunked_batched": _chunked_batched_jit.cache_info().currsize}
    # _cache_size is private jax API (present in the installed 0.9.0):
    # unguarded, so a jax that drops it fails here instead of reporting
    # an empty cache forever
    for name, fn in (("scan", _select_scan),
                     ("chunked", _select_chunked),
                     ("kway", _select_kway)):
        out[name] = int(fn._cache_size())
    return out


def kernel_cache_entries() -> int:
    return sum(kernel_cache_stats().values())


def clear_kernel_caches() -> dict:
    """Governor reclaim: drop every cached compiled kernel. Rarely the
    right call on a healthy server (the LRU bound handles churn);
    exists for the watermark breach where compiled-shape cardinality
    itself is the leak. Next dispatches recompile warm shapes."""
    # the recompile gauge must see those recompiles: forget seen trace
    # signatures so re-traced warm shapes count as fresh compiles
    from ..analysis.sanitizer import traces
    traces.invalidate()
    before = kernel_cache_entries()
    _scan_batched_jit.cache_clear()
    _chunked_batched_jit.cache_clear()
    for fn in (_select_scan, _select_chunked, _select_kway):
        fn.clear_cache()
    return {"evicted": before}
