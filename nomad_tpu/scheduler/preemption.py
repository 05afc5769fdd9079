"""Preemption: choosing victim allocations on a node so a higher
priority placement fits.

Reference semantics: scheduler/preemption.go — candidates grouped by
priority ascending with a >=10 priority delta (filterAndGroupPreemptibleAllocs:663),
greedy closest-resource-distance selection (basicResourceDistance:608,
scoreForTaskGroup:640 with the maxParallel penalty:13), then a
superset-filter pass dropping redundant victims (filterSuperset:702).
Node choice across candidates uses the logistic preemption score
(rank.go preemptionScore:773: 1/(1+e^(0.0048*(netPriority-2048)))).
"""

from __future__ import annotations

import math
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models import Allocation, ComparableResources
# the two constants of upstream's rule, shared with the program
from ..ops.victims import MAX_PARALLEL_PENALTY, PRIORITY_DELTA
from ..utils import stages


# -- batched columnar victim selection (ISSUE 10) ----------------------
#
# ServerConfig.preempt_* knobs land here via configure() (the
# store.alloc_index.enabled idiom — the scheduler has no ServerConfig
# in scope). NOMAD_TPU_COLUMNAR_PREEMPT=0 is the runtime kill switch:
# it forces the per-node reference Preemptor for every round, exactly
# like NOMAD_TPU_COLUMNAR_RECONCILE=0 reverts the reconcile engine.

_COLUMNAR = True
# per-node cap on the victims' columns' width (ops/victims.py holds its
# own, lower, ceiling): a node with more job-carrying residents than
# the columns are wide takes the per-node reference path
ROWS_MAX = 4096
# victim-set memo bound (table.preempt_cache); crossing it clears the
# memo — the governor's preemption.victim_cache_entries watermark
# (governor_preempt_cache_high) reclaims earlier
CACHE_MAX = 200_000

# unlocked counters (the BUILD_STATS idiom: racy increments are
# tolerated — these feed gauges and the bench artifact, not billing)
PREEMPT_STATS: Dict[str, float] = {
    "nodes_scanned": 0, "candidate_rows": 0,
    "cache_hits": 0, "cache_misses": 0,
    "invalidations": 0, "cache_clears": 0,
    "columnar_nodes": 0, "fallback_nodes": 0,
    "select_s": 0.0,
}


def configure(columnar: Optional[bool] = None,
              rows_max: Optional[int] = None,
              cache_max: Optional[int] = None) -> None:
    """Install ServerConfig.preempt_* knobs (Server.__init__)."""
    global _COLUMNAR, ROWS_MAX, CACHE_MAX
    if columnar is not None:
        _COLUMNAR = bool(columnar)
    if rows_max is not None:
        ROWS_MAX = int(rows_max)
    if cache_max is not None:
        CACHE_MAX = int(cache_max)


def columnar_enabled() -> bool:
    # same env grammar as reconcile_columnar.columnar_enabled — an
    # operator flipping both kill switches must not need two spellings
    return _COLUMNAR and os.environ.get(
        "NOMAD_TPU_COLUMNAR_PREEMPT", "1").lower() \
        not in ("0", "false", "no", "off")


def preempt_stats() -> Dict[str, float]:
    return dict(PREEMPT_STATS)


def basic_resource_distance(ask: ComparableResources,
                            used: ComparableResources) -> float:
    mem = cpu = disk = 0.0
    if ask.memory_mb > 0:
        mem = (ask.memory_mb - used.memory_mb) / ask.memory_mb
    if ask.cpu_shares > 0:
        cpu = (ask.cpu_shares - used.cpu_shares) / ask.cpu_shares
    if ask.disk_mb > 0:
        disk = (ask.disk_mb - used.disk_mb) / ask.disk_mb
    return math.sqrt(mem * mem + cpu * cpu + disk * disk)


def score_for_task_group(ask: ComparableResources, used: ComparableResources,
                         max_parallel: int, num_preempted: int) -> float:
    penalty = 0.0
    if max_parallel > 0 and num_preempted >= max_parallel:
        penalty = float(num_preempted + 1 - max_parallel) * MAX_PARALLEL_PENALTY
    return basic_resource_distance(ask, used) + penalty


def net_priority(allocs: List[Allocation]) -> float:
    """rank.go netPriority:749: max priority plus sum/max crowding factor."""
    total = 0
    mx = 0.0
    for a in allocs:
        prio = a.job.priority if a.job else 50
        mx = max(mx, float(prio))
        total += prio
    if mx == 0:
        return 0.0
    return mx + total / mx


def preemption_score(netprio: float) -> float:
    """rank.go preemptionScore:773 — logistic, inflection at 2048."""
    rate = 0.0048
    origin = 2048.0
    return 1.0 / (1.0 + math.exp(rate * (netprio - origin)))


class Preemptor:
    def __init__(self, job_priority: int, namespace: str, job_id: str):
        self.job_priority = job_priority
        self.namespace = namespace
        self.job_id = job_id
        self.current_preemptions: Dict[Tuple[str, str, str], int] = {}
        self.alloc_details: Dict[str, Tuple[int, ComparableResources]] = {}
        self.node_remaining: Optional[ComparableResources] = None
        self.current_allocs: List[Allocation] = []
        self.all_usage = ComparableResources()

    def set_node(self, node) -> None:
        remaining = node.comparable_resources()
        remaining.subtract(node.comparable_reserved_resources())
        self.node_remaining = remaining

    def set_candidates(self, allocs: List[Allocation]) -> None:
        """Candidates exclude the placing job's own allocs, but ALL
        proposed allocs count against the node's remaining capacity —
        otherwise same-job allocs on the node are invisible to the math
        and preemption can approve an oversubscribing placement."""
        self.current_allocs = []
        self.all_usage = ComparableResources()
        for alloc in allocs:
            res = alloc.comparable_resources() or ComparableResources()
            self.all_usage.add(res)
            if alloc.job_id == self.job_id and alloc.namespace == self.namespace:
                continue
            max_parallel = 0
            tg = alloc.job.lookup_task_group(alloc.task_group) if alloc.job else None
            if tg is not None and tg.migrate is not None:
                max_parallel = tg.migrate.max_parallel
            self.alloc_details[alloc.id] = (max_parallel, res)
            self.current_allocs.append(alloc)

    def set_preemptions(self, allocs: List[Allocation]) -> None:
        self.current_preemptions = {}
        for a in allocs:
            key = (a.namespace, a.job_id, a.task_group)
            self.current_preemptions[key] = self.current_preemptions.get(key, 0) + 1

    def _num_preemptions(self, alloc: Allocation) -> int:
        return self.current_preemptions.get(
            (alloc.namespace, alloc.job_id, alloc.task_group), 0)

    def preempt_for_task_group(self, ask: ComparableResources
                               ) -> Optional[List[Allocation]]:
        """Find victims so `ask` fits; None if impossible."""
        needed = ask.copy()
        remaining = self.node_remaining.copy()
        remaining.subtract(self.all_usage)

        groups = self._filter_and_group()
        best: List[Allocation] = []
        all_met = False
        available = remaining.copy()

        for _prio, allocs in groups:
            allocs = list(allocs)
            while allocs and not all_met:
                best_idx = -1
                best_dist = math.inf
                for i, alloc in enumerate(allocs):
                    max_parallel, res = self.alloc_details[alloc.id]
                    dist = score_for_task_group(
                        needed, res, max_parallel,
                        self._num_preemptions(alloc))
                    if dist < best_dist:
                        best_dist = dist
                        best_idx = i
                closest = allocs.pop(best_idx)
                closest_res = self.alloc_details[closest.id][1]
                available.add(closest_res)
                all_met, _dim = available.superset(ask)
                best.append(closest)
                needed.subtract(closest_res)
            if all_met:
                break
        if not all_met:
            return None
        return self._filter_superset(best, remaining, ask)

    def preempt_for_device(self, req, node) -> Optional[List[Allocation]]:
        """Victims freeing device instances so `req` (a RequestedDevice)
        fits — preemption.go PreemptForDevice:472. Candidates holding
        instances of a matching group are taken lowest-priority-first,
        closest-distance within a priority band, until enough instances
        are free."""
        from .devices import group_satisfies

        def held_in(alloc, gid) -> int:
            res = alloc.allocated_resources
            if res is None:
                return 0
            return sum(len(dev.device_ids)
                       for tr in res.tasks.values()
                       for dev in tr.devices if dev.id_tuple() == gid)

        best: Optional[List[Allocation]] = None
        for g in node.node_resources.devices:
            if not group_satisfies(g, req):
                continue
            gid = g.id_tuple()
            total = sum(1 for i in g.instances if i.healthy)
            held_all = 0
            holders: List[Tuple[int, Allocation, int]] = []
            for alloc in self.current_allocs:
                h = held_in(alloc, gid)
                if h == 0:
                    continue
                held_all += h
                if alloc.job is not None and \
                        self.job_priority - alloc.job.priority >= \
                        PRIORITY_DELTA:
                    holders.append((alloc.job.priority, alloc, h))
            free = total - held_all
            if free >= req.count:
                return []                   # nothing to evict
            holders.sort(key=lambda t: (t[0], t[1].id))
            victims: List[Allocation] = []
            for _prio, alloc, h in holders:
                victims.append(alloc)
                free += h
                if free >= req.count:
                    break
            if free >= req.count and \
                    (best is None or len(victims) < len(best)):
                best = victims
        return best

    def preempt_for_network(self, reserved_ports: List[int],
                            mbits_needed: float, node,
                            already_freed_mbits: float = 0.0,
                            skip_ids: Optional[set] = None
                            ) -> Optional[List[Allocation]]:
        """Victims freeing colliding reserved ports and/or bandwidth —
        preemption.go PreemptForNetwork:270. Port holders are mandatory
        victims; bandwidth shortfall fills lowest-priority-first."""
        want_ports = set(reserved_ports or [])
        victims: List[Allocation] = []
        victim_ids = set()
        eligible: List[Tuple[int, float, Allocation, float]] = []
        used_mbits = 0.0
        node_mbits = sum(nw.mbits for nw in
                         node.node_resources.networks) or 0.0
        for alloc in self.current_allocs:
            _mp, res = self.alloc_details[alloc.id]
            alloc_ports = set()
            alloc_mbits = 0.0
            for nw in res.networks:
                alloc_mbits += nw.mbits
                alloc_ports.update(p.value for p in nw.reserved_ports)
            used_mbits += alloc_mbits
            is_eligible = (alloc.job is not None and self.job_priority -
                           alloc.job.priority >= PRIORITY_DELTA)
            if want_ports & alloc_ports:
                if skip_ids and alloc.id in skip_ids:
                    continue                # already evicted upstream
                if not is_eligible:
                    return None             # holder can't be preempted
                victims.append(alloc)
                victim_ids.add(alloc.id)
            elif is_eligible and alloc_mbits > 0:
                eligible.append((alloc.job.priority,
                                 -alloc_mbits, alloc, alloc_mbits))
        freed = already_freed_mbits + sum(
            sum(nw.mbits for nw in self.alloc_details[v.id][1].networks)
            for v in victims)
        if node_mbits and mbits_needed > 0:
            shortfall = (used_mbits - freed + mbits_needed) - node_mbits
            if shortfall > 0:
                eligible.sort(key=lambda t: (t[0], t[1], t[2].id))
                for _prio, _neg, alloc, mb in eligible:
                    if alloc.id in victim_ids or \
                            (skip_ids and alloc.id in skip_ids):
                        continue
                    victims.append(alloc)
                    victim_ids.add(alloc.id)
                    shortfall -= mb
                    if shortfall <= 0:
                        break
                if shortfall > 0:
                    return None
        return victims

    def _filter_and_group(self) -> List[Tuple[int, List[Allocation]]]:
        by_prio: Dict[int, List[Allocation]] = {}
        for alloc in self.current_allocs:
            if alloc.job is None:
                continue
            if self.job_priority - alloc.job.priority < PRIORITY_DELTA:
                continue
            by_prio.setdefault(alloc.job.priority, []).append(alloc)
        return sorted(by_prio.items())

    def _filter_superset(self, best: List[Allocation],
                         remaining: ComparableResources,
                         ask: ComparableResources) -> List[Allocation]:
        # sort by distance descending (largest victims first)
        best = sorted(
            best,
            key=lambda a: basic_resource_distance(
                self.alloc_details[a.id][1], ask),
            reverse=True)
        available = remaining.copy()
        out: List[Allocation] = []
        for alloc in best:
            out.append(alloc)
            available.add(self.alloc_details[alloc.id][1])
            met, _ = available.superset(ask)
            if met:
                break
        return out


def link_preemptions(plan, alloc, victims: List[Allocation]) -> None:
    """Record victims on the preempting alloc and stamp the victim stubs
    with the preemptor's id (generic_sched.go handlePreemptions)."""
    alloc.preempted_allocations = [v.id for v in victims]
    victim_ids = set(alloc.preempted_allocations)
    for stubs in plan.node_preemptions.values():
        for stub in stubs:
            if stub.id in victim_ids and not stub.preempted_by_allocation:
                stub.preempted_by_allocation = alloc.id
                stub.desired_description = f"Preempted by alloc ID {alloc.id}"


def preemption_enabled(sched_config, scheduler_type: str) -> bool:
    """operator.go PreemptionConfig gates per scheduler type."""
    pc = sched_config.preemption_config
    if scheduler_type == "system":
        return pc.system_scheduler_enabled
    if scheduler_type == "batch":
        return pc.batch_scheduler_enabled
    if scheduler_type == "service":
        return pc.service_scheduler_enabled
    return False


class PreemptionRound:
    """Preemption across the whole fleet for one (eval, task group):
    which full nodes could take an instance of the ask by evicting, at
    what score, and, for the nodes that win, whom.

    What is RESIDENT, and outlives the round: the victims' columns kept
    with the node table (ops/victims.VictimColumns) — per node row the
    job-carrying residents' priority, cpu, memory, disk, group code and
    max_parallel, on the device, one array a table version, advanced by
    the rows a commit touched. A round walks no `Allocation` list of a
    node nothing touched.

    What a round RECOMPUTES, every time it is asked (device_columns()):
    the selection itself — `_select_victims_fn` over every candidate
    node at once, against this plan's usage, with the slots this plan
    stops or preempts and the placing job's own taken out. Its columns
    stay on the device for the select that follows; resolve() brings
    the winners' victims to the host, victims_for() hands them out.

    The per-node `Preemptor` is the reference the program is held to
    (tests/test_victims_program.py) and the path for what the columns
    do not carry: device, reserved-port and bandwidth asks, a row wider
    than the columns, NOMAD_TPU_COLUMNAR_PREEMPT=0. There each node's
    (victims, score) entry is computed once and re-derived only when
    the plan state touching the node changed (`_invalidate_dirty`: the
    plan's per-node entry counts, and the max_parallel counts of the
    groups present — scoreForTaskGroup's penalty is the only cross-node
    coupling), with a cross-eval memo keyed on the row's identity.
    columns() / find_placement() are that host API; with the program on
    they run one dispatch and fetch every row (tests, the mesh route).
    Semantics per node: PreemptionScoringIterator + BinPack fallback
    (rank.go:415-448, 732-745).
    """

    def __init__(self, snapshot, table, mask, ask_vec, job, plan,
                 tg=None):
        self.snapshot = snapshot
        self.table = table
        self.mask = mask
        self.ask_vec = ask_vec
        self.job = job
        self.plan = plan
        self.tg = tg          # enables device/network preemption variants
        self.ask = ComparableResources(cpu_shares=float(ask_vec[0]),
                                       memory_mb=float(ask_vec[1]),
                                       disk_mb=float(ask_vec[2]))
        n = len(table.nodes)
        # cross-eval cache key parts: the tg's port/device shape and the
        # ask vector (victims depend on both); the per-node row identity
        # completes the key at lookup time
        reserved: Tuple = ()
        devs: Tuple = ()
        if tg is not None:
            from .stack import PlacementEngine
            dyn, rs = PlacementEngine._port_asks(tg)
            reserved = (dyn, tuple(sorted(rs)))
            from .devices import combined_device_asks
            # constraints/affinities change the victim set
            # (group_satisfies evaluates them), so they are part of the
            # cache identity
            devs = tuple(
                (r.name, r.count,
                 tuple((c.ltarget, c.rtarget, c.operand)
                       for c in (r.constraints or [])),
                 tuple((a.ltarget, a.rtarget, a.operand, a.weight)
                       for a in (r.affinities or [])))
                for r in combined_device_asks(tg))
        self._cache_sig = (job.priority, tuple(float(x) for x in ask_vec),
                          reserved, devs)
        # batched victim selection handles the resource dimensions; a
        # device or network-port/bandwidth ask keeps the per-node
        # reference path — PreemptForDevice / PreemptForNetwork walk
        # instance tables and port bitsets per alloc, exactly the rows
        # reconcile_columnar.py also drops to Python for
        mbits_need = float(ask_vec[3]) if len(ask_vec) > 3 else 0.0
        self._columnar = (columnar_enabled() and not devs
                          and not (reserved and reserved[1])
                          and not mbits_need > 0)
        # computed state: known[i] -> score[i] (-1 = infeasible) and
        # victim lists; invalidation is *dirty-tracked* from the plan's
        # per-node entry counts instead of re-hashed per call
        self._known = np.zeros(n, bool)
        self._scores = np.full(n, -1.0, np.float64)
        self._logistic = np.zeros(n, np.float64)
        self._freed = np.zeros((n, 4), np.float64)
        self._victims: Dict[int, List[Allocation]] = {}
        # idx -> group keys on the node that carry max_parallel > 0
        self._mp_groups: Dict[int, frozenset] = {}
        self._last_counts: Dict[str, Tuple[int, int, int]] = {}
        self._last_mp_counts: Dict[Tuple, int] = {}
        # the victims' columns this round read (ops/victims.py), its
        # last dispatch, and the rows whose fit the columns last
        # handed out came from an eviction
        self._vc = None
        self._host: frozenset = frozenset()     # rows the host evaluated
        self._selection = None
        self._evicting = np.zeros(n, bool)
        self._rows_refreshed = 0
        self._scanned = self._n_victims = 0

    # -- plan-state dirty tracking ------------------------------------
    def _preempted_now(self) -> List[Allocation]:
        out: List[Allocation] = []
        for allocs in self.plan.node_preemptions.values():
            out.extend(allocs)
        return out

    def _invalidate_dirty(self, current: List[Allocation]) -> None:
        """Drop cached entries for nodes whose plan state changed since
        the last call. Only nodes that appear in the plan's dicts can
        have changed — O(touched nodes), not O(all nodes)."""
        p = self.plan
        id_to_idx = self.table.id_to_idx
        touched: Dict[str, Tuple[int, int, int]] = {}
        for nid in (p.node_allocation.keys() | p.node_update.keys()
                    | p.node_preemptions.keys()):
            touched[nid] = (len(p.node_allocation.get(nid, ())),
                            len(p.node_update.get(nid, ())),
                            len(p.node_preemptions.get(nid, ())))
        for nid, counts in touched.items():
            if self._last_counts.get(nid) != counts:
                self._last_counts[nid] = counts
                idx = id_to_idx.get(nid)
                if idx is not None:
                    if self._known[idx]:
                        PREEMPT_STATS["invalidations"] += 1
                    self._known[idx] = False
        # global coupling: max_parallel penalties depend on the total
        # preempted count per group; invalidate nodes holding candidates
        # of groups whose count changed
        mp_counts: Dict[Tuple, int] = {}
        for a in current:
            key = (a.namespace, a.job_id, a.task_group)
            mp_counts[key] = mp_counts.get(key, 0) + 1
        if mp_counts != self._last_mp_counts:
            changed = {k for k in (mp_counts.keys()
                                   | self._last_mp_counts.keys())
                       if mp_counts.get(k) != self._last_mp_counts.get(k)}
            self._last_mp_counts = mp_counts
            for idx, groups in self._mp_groups.items():
                if groups & changed:
                    if self._known[idx]:
                        PREEMPT_STATS["invalidations"] += 1
                    self._known[idx] = False

    # -- per-node evaluation (exact one-shot semantics) ----------------
    def _cacheable(self, i: int) -> bool:
        """A node's victim entry can cross evals when nothing specific
        to THIS eval touches it: no plan entries on the node, and no
        allocs of the placing job among its candidates (the own-job
        exclusion makes victims job-relative)."""
        node_id = self.table.ids[i]
        p = self.plan
        if node_id in p.node_allocation or node_id in p.node_update \
                or node_id in p.node_preemptions:
            return False
        ns, jid = self.job.namespace, self.job.id
        for a in self.table.live_allocs[i]:
            if a.job_id == jid and a.namespace == ns:
                return False
        return True

    def _evaluate_node(self, i: int, used_row,
                       current: List[Allocation],
                       stopped_ids: set) -> Tuple[Optional[List[Allocation]],
                                                  float]:
        from ..models.funcs import ScoreFitBinPack

        # cross-eval fast path: an unchanged live-alloc row (identity —
        # rows are replaced copy-on-write) under the same priority/ask/
        # port/device signature yields the same victims; entries with
        # max_parallel-bearing candidates are never cached because their
        # penalty couples to the eval's running preemption counts
        cacheable = self._cacheable(i)
        row = self.table.live_allocs[i]
        key = (id(row), self._cache_sig)
        if cacheable:
            hit = self.table.preempt_cache.get(key)
            if hit is not None and hit[0] is row:
                PREEMPT_STATS["cache_hits"] += 1
                _row, victims, score, logistic, freed = hit
                self._logistic[i] = logistic
                self._freed[i] = freed
                self._mp_groups[i] = frozenset()
                return (list(victims) if victims is not None else None,
                        score)

        node = self.table.nodes[i]
        proposed = [a for a in self.snapshot.allocs_by_node(node.id)
                    if not a.terminal_status() and a.id not in stopped_ids]
        proposed.extend(self.plan.node_allocation.get(node.id, []))
        p = Preemptor(self.job.priority, self.job.namespace, self.job.id)
        p.set_node(node)
        p.set_candidates(proposed)
        p.set_preemptions(current)
        # remember the max_parallel-bearing groups for invalidation
        mp = set()
        for a in p.current_allocs:
            if p.alloc_details[a.id][0] > 0:
                mp.add((a.namespace, a.job_id, a.task_group))
        self._mp_groups[i] = frozenset(mp)

        def memo(victims_out, score, logistic=0.0, freed=None):
            """Record the result in the cross-eval cache when safe: the
            node wasn't eval-specific (_cacheable) and no candidate
            carries max_parallel (whose penalty couples to the running
            preemption counts of this eval)."""
            if cacheable and not mp:
                if len(self.table.preempt_cache) > CACHE_MAX:
                    self.table.preempt_cache.clear()
                    PREEMPT_STATS["cache_clears"] += 1
                self.table.preempt_cache[key] = (
                    row,
                    list(victims_out) if victims_out is not None else None,
                    score, logistic,
                    freed if freed is not None else np.zeros(4, np.float64))
            return victims_out, score

        # resource-dimension victims (skipped when the node already
        # fits on cpu/mem/disk and is a candidate only for device/port
        # reasons)
        res_fits = bool(np.all(
            used_row[:3] + np.asarray(self.ask_vec[:3])
            <= self.table.capacity[i, :3] + 1e-6))
        if res_fits:
            victims: List[Allocation] = []
        else:
            victims = p.preempt_for_task_group(self.ask)
            if not victims:
                return memo(None, 0.0)
            victims = list(victims)
        victim_ids = {v.id for v in victims}

        # device variant (preemption.go PreemptForDevice:472)
        if self.tg is not None:
            from .devices import combined_device_asks
            for reqd in combined_device_asks(self.tg):
                dvict = p.preempt_for_device(reqd, node)
                if dvict is None:
                    return memo(None, 0.0)
                for v in dvict:
                    if v.id not in victim_ids:
                        victims.append(v)
                        victim_ids.add(v.id)

        # network variant (preemption.go PreemptForNetwork:270):
        # reserved-port collisions and the bandwidth dimension
        reserved_ports: List[int] = []
        if self.tg is not None:
            from .stack import PlacementEngine
            _dyn, reserved_ports = PlacementEngine._port_asks(self.tg)
        mbits_needed = float(self.ask_vec[3]) \
            if len(self.ask_vec) > 3 else 0.0
        if reserved_ports or mbits_needed > 0:
            freed_mbits = 0.0
            for v in victims:
                cr = v.comparable_resources()
                if cr is not None:
                    freed_mbits += sum(nw.mbits for nw in cr.networks)
            nvict = p.preempt_for_network(reserved_ports, mbits_needed,
                                          node,
                                          already_freed_mbits=freed_mbits,
                                          skip_ids=victim_ids)
            if nvict is None:
                return memo(None, 0.0)
            for v in nvict:
                if v.id not in victim_ids:
                    victims.append(v)
                    victim_ids.add(v.id)
        if not victims:
            return memo(None, 0.0)
        # score: binpack fit after eviction + logistic preemption score
        util = ComparableResources()
        victim_ids = {v.id for v in victims}
        for a in proposed:
            if a.id not in victim_ids:
                util.add(a.comparable_resources())
        util.add(self.ask)
        binpack = ScoreFitBinPack(node, util) / 18.0
        pscore = preemption_score(net_priority(victims))
        # resources the evictions free, in kernel dim order
        # (cpu, memory, disk, network mbits)
        freed = np.zeros(4, np.float64)
        for v in victims:
            cr = v.comparable_resources()
            if cr is None:
                continue
            freed[0] += cr.cpu_shares
            freed[1] += cr.memory_mb
            freed[2] += cr.disk_mb
            freed[3] += sum(nw.mbits for nw in cr.networks)
        self._logistic[i] = pscore
        self._freed[i] = freed
        return memo(victims, (binpack + pscore) / 2.0, pscore, freed)

    # -- the resident columns and the program over them ----------------
    def _record(self, i: int, victims: Optional[List[Allocation]],
                score: float) -> None:
        self._known[i] = True
        if victims:
            self._scores[i] = score
            self._victims[i] = victims
        else:
            self._scores[i] = -1.0
            self._logistic[i] = 0.0
            self._freed[i] = 0.0
            self._victims.pop(i, None)

    def _evaluate_pending(self, pending, used,
                          current: List[Allocation]) -> None:
        """Resolve every pending node's (victims, score) entry on the
        host: ONE dispatch of the victims' program with everything
        fetched (the host API: tests, the mesh route), or the per-node
        reference Preemptor with its cross-eval memo when the round
        carries device / port asks or the kill switch is set."""
        t0 = time.perf_counter()
        with stages.span("preempt") as sp:
            sel = self._dispatch(used=used, current=current) \
                if self._columnar else None
            if sel is not None:
                pre, score, freed, slots = sel.fetch_all()
                rows = self._vc.rows
                for i in pending.tolist():
                    if i in self._host:
                        continue        # _dispatch recorded it
                    if score[i] < 0:
                        self._record(i, None, 0.0)
                        continue
                    self._logistic[i] = pre[i]
                    self._freed[i] = freed[i]
                    self._record(i, [rows[i][s] for s in slots[i]],
                                 float(score[i]))
            else:
                stopped_ids = self._stopped_ids(current)
                PREEMPT_STATS["fallback_nodes"] += len(pending)
                for i in pending.tolist():
                    if self._cache_lookup(i):
                        continue
                    PREEMPT_STATS["cache_misses"] += 1
                    victims, score_i = self._evaluate_node(
                        i, used[i], current, stopped_ids)
                    self._record(i, victims, score_i)
                PREEMPT_STATS["nodes_scanned"] += len(pending)
            n_victims = sum(len(self._victims.get(i, ()))
                            for i in pending.tolist())
            sp.note(nodes_scanned=len(pending), victims=n_victims,
                    rows_refreshed=self._rows_refreshed)
        PREEMPT_STATS["select_s"] += time.perf_counter() - t0

    def _cache_lookup(self, i: int) -> bool:
        """The per-node path's cross-eval memo."""
        if not self._cacheable(i):
            return False
        row = self.table.live_allocs[i]
        hit = self.table.preempt_cache.get((id(row), self._cache_sig))
        if hit is None or hit[0] is not row:
            return False
        PREEMPT_STATS["cache_hits"] += 1
        _row, victims, score, logistic, freed = hit
        self._logistic[i] = logistic
        self._freed[i] = freed
        self._mp_groups[i] = frozenset()
        self._record(i, list(victims) if victims is not None else None,
                     score)
        return True

    def _stopped_ids(self, current: List[Allocation]) -> set:
        out = {a.id for allocs in self.plan.node_update.values()
               for a in allocs}
        out.update(a.id for a in current)
        return out

    def _dispatch(self, used=None, proposed=None,
                  current: Optional[List[Allocation]] = None):
        """Run the victims' program over the whole table (asynchronous;
        ops/victims.py) and return its selection, or None when the plan
        already preempts from more groups than the program counts. The
        host's share, the `preempt_gather` span: the columns brought to
        this table version (the rows commits touched since the nearest
        version that had them), the slots the plan or the placing job
        itself takes out, and the rows wider than the columns, which
        the per-node Preemptor evaluates here. `preempt_kernel`: the
        dispatch and the wait for its counters; nodes it reports
        unfinished (more victims needed than it picks) go to the
        Preemptor too, and the program runs once more over their
        entries."""
        from ..ops import victims as vops
        t = self.table
        if current is None:
            current = self._preempted_now()
        with stages.span("preempt_gather") as sp:
            fresh = t.victims is None
            vc = self._vc = t.victim_columns(self.snapshot, ROWS_MAX)
            self._rows_refreshed = vc.refreshed if fresh else 0
            counts: Dict[int, int] = {}
            for code in vops.group_codes(
                    (a.namespace, a.job_id, a.task_group)
                    for a in current):
                counts[code] = counts.get(code, 0) + 1
            if len(counts) > vops.GROUP_COUNTS_MAX:
                sp.cancel()
                return None
            id_to_idx = t.id_to_idx
            dead = []
            own = [a for a in self.snapshot.allocs_by_job(
                self.job.namespace, self.job.id)
                if not a.terminal_status()]
            for allocs in (own, *self.plan.node_update.values(),
                           *self.plan.node_preemptions.values()):
                for a in allocs:
                    row = id_to_idx.get(a.node_id)
                    if row is not None:
                        slot = vc.slot_of(row, a)
                        if slot >= 0:
                            dead.append((row, slot))
            overrides: Dict[int, tuple] = {}
            if vc.over:
                self._host_rows(sorted(vc.over), used, proposed, current,
                                overrides)
            for i, groups in vc.mp_groups.items():
                self._mp_groups[i] = groups
        import jax

        def run():
            """One dispatch, and the wait for its counters."""
            with stages.span("preempt_kernel"):
                sel = vops.select_victims(
                    vc, t, self.mask, self.ask_vec, self.job.priority,
                    dead, counts, overrides, used=used, proposed=proposed)
                # nomad-lint: allow[host-sync] the program's counters: the wait IS the preempt_kernel span
                got = jax.device_get(sel.counters)
            return (sel, *map(int, got))

        sel, scanned, n_victims, eligible, unfinished = run()
        if unfinished:
            # nodes that need more victims than the program picks
            # (ops/victims.PICKS_MAX): the Preemptor's, then once more
            with stages.span("preempt_gather"):
                # nomad-lint: allow[host-sync] rare: nodes past PICKS_MAX, fetched inside preempt_gather
                rows = np.nonzero(jax.device_get(sel.unfinished))[0]
                self._host_rows(rows.tolist(), used, proposed, current,
                                overrides)
            sel, scanned, n_victims, eligible, _ = run()
        self._host = frozenset(vc.over) | frozenset(overrides)
        n_victims += sum(len(self._victims[i]) for i in overrides)
        PREEMPT_STATS["nodes_scanned"] += scanned
        PREEMPT_STATS["candidate_rows"] += eligible
        PREEMPT_STATS["columnar_nodes"] += scanned - len(overrides)
        self._scanned, self._n_victims = scanned, n_victims
        return sel

    def _host_rows(self, rows, used, proposed, current, overrides) -> None:
        """The per-node Preemptor over `rows` (wider than the columns,
        or in need of more victims than the program picks); those that
        fit by eviction go into `overrides`, which the program lays
        over its own result."""
        used_h = used if used is not None else proposed.used()
        stopped_ids = self._stopped_ids(current)
        t = self.table
        for i in rows:
            if not self.mask[i] or np.all(
                    used_h[i] + np.asarray(self.ask_vec)
                    <= t.capacity[i] + 1e-6):
                continue
            victims, score = self._evaluate_node(
                i, used_h[i], current, stopped_ids)
            self._record(i, victims, score)
            PREEMPT_STATS["fallback_nodes"] += 1
            if victims:
                overrides[i] = (self._logistic[i], score, self._freed[i])

    # -- entry ---------------------------------------------------------
    def _candidates(self, used, extra_candidates=None):
        """(candidate rows bool[N], after the pending ones among them
        are resolved on the host)."""
        current = self._preempted_now()
        self._invalidate_dirty(current)
        fits = np.all(used + np.asarray(self.ask_vec)[None, :]
                      <= self.table.capacity + 1e-6, axis=1)
        candidates = self.mask & ~fits
        if extra_candidates is not None:
            # nodes failing only on devices/reserved ports (the
            # PreemptForDevice / PreemptForNetwork variants)
            candidates |= self.mask & extra_candidates
        pending = np.nonzero(candidates & ~self._known)[0]
        if len(pending):
            self._evaluate_pending(pending, used, current)
        return candidates

    def find_placement(self, used) -> Optional[Tuple[int, List[Allocation],
                                                     float]]:
        """Best (node_idx, victims, score) for one failed instance, or
        None. `used` is the current proposed usage [N, D]."""
        candidates = self._candidates(used)
        masked = np.where(candidates & self._known, self._scores, -1.0)
        best_i = int(np.argmax(masked))
        if masked[best_i] < 0:
            return None
        return best_i, self._victims[best_i], float(masked[best_i])

    def columns(self, used, extra_candidates=None
                ) -> Tuple["np.ndarray", "np.ndarray"]:
        """Kernel competition columns on the HOST (rank.go:415-448):
        for every masked node that doesn't fit but CAN fit after
        evictions, (logistic preemption score, freed resources). `used`
        rows for those nodes should be reduced by `freed` before the
        kernel so fit and binpack reflect the post-eviction node. What
        a select takes when device_columns() cannot serve it."""
        candidates = self._candidates(used, extra_candidates)
        ok = candidates & self._known & (self._scores >= 0)
        self._evicting = ok
        d = used.shape[1]
        pre_score = np.where(ok, self._logistic, 0.0).astype(np.float32)
        freed = np.where(ok[:, None], self._freed[:, :d],
                         0.0).astype(np.float32)
        return pre_score, freed

    def device_columns(self, proposed):
        """The same columns left ON THE DEVICE for the select that
        follows (ops/victims.VictimSelection: `used_after`, `pre_score`,
        `capacity`), or None when this round cannot run the program
        (device / port / bandwidth asks, the kill switch, a plan that
        already preempts from too many groups): the caller then takes
        columns(). The winners' victims reach the host by resolve()."""
        if not self._columnar:
            return None
        t0 = time.perf_counter()
        with stages.span("preempt") as sp:
            sel = self._dispatch(proposed=proposed)
            if sel is None:
                sp.cancel()
                return None
            sp.note(nodes_scanned=self._scanned, victims=self._n_victims,
                    rows_refreshed=self._rows_refreshed)
        PREEMPT_STATS["select_s"] += time.perf_counter() - t0
        self._selection = sel
        self._evicting = np.zeros(len(self.table.nodes), bool)
        return sel

    def resolve(self, rows) -> None:
        """After the select that device_columns() fed: fetch the slots
        of the rows that won and keep their victims for victims_for()."""
        rows = sorted({int(r) for r in rows if r >= 0})
        sel = self._selection
        if sel is None or not rows:
            return
        pre, picked = sel.winners(rows)
        slots = self._vc.rows
        for k, i in enumerate(rows):
            if pre[k] <= 0:
                continue            # the node had room as it was
            self._evicting[i] = True
            if i not in self._host:         # else: recorded by _dispatch
                self._victims[i] = [slots[i][s] for s in picked[k]]

    def victims_for(self, idx: int):
        """The victims whose eviction makes node `idx` fit, or None when
        the columns last handed out gave it none."""
        if not self._evicting[idx]:
            return None
        return self._victims.get(idx)
