"""The plain reference: a scheduler's answers are placements, and what
makes one right is stated by the configuration's guarantees and by the
reference scheduler's ranking. This module holds the guarantees as
per-node Python over plain dicts (copied from chip_smoke.py, PR 21, and
grown), the reference ranking (PlainScorer one node at a time,
binpack_scores all at once) with the comparison of the program's
placements against it (check_rank), and a plain scheduler that can stand
in the program's place — whole, or with one guarantee or the ranking
broken, which is the control that has to come out as not correct.

It imports nothing from nomad_tpu and takes nothing the program made
but its answers: alloc stubs, full allocs and evals as HTTP returns
them.
"""

from __future__ import annotations

import collections
import heapq
import math
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from .fleet import DIMS

# plain-reference scores closer than this are one f32 tie: the kernels
# score in float32 and the chip's pow differs from the host's by tens
# of ulps (measured, PR 21), i.e. ~1e-5 on a score of order one
TIE_EPS = 1e-5

RANK_GAP_LIMIT = 0.05

# The guarantees are exact counts: their limit is 0. `rank_gap` is a
# score difference (scores lie in [-1, 1]); its limit stands between
# what sound runs of the program read and what the controls read
# (PERF.md, "How correct is decided", has the readings).
LIMITS = {
    "never_completed": 0, "unplaced_evals": 0, "lost_or_duplicated": 0,
    "unread": 0, "over_capacity": 0, "infeasible": 0, "port_conflicts": 0,
    "spread_over_target": 0, "stacked": 0, "rank_gap": RANK_GAP_LIMIT,
}


# A configuration with `resident_tiers` is held to four more numbers,
# exact counts too (check_evictions; PERF.md has the controls' readings)
TIER_LIMITS = {
    "evicted_wrongly": 0, "evicted_needlessly": 0, "evicted_with_room": 0,
    "residents_stopped": 0,
}

# upstream's filterAndGroupPreemptibleAllocs: an allocation may be
# preempted by a job whose priority is at least this much higher
PRIORITY_DELTA = 10


def preemption_enabled(scheduler_configuration: Optional[dict],
                       job_type: str) -> bool:
    """Whether the configuration's `scheduler_configuration` (the wire
    form of the operator API) lets a job of `job_type` preempt; absent,
    the defaults hold: system jobs alone."""
    pc = (scheduler_configuration or {}).get("preemption_config", {})
    return bool(pc.get(f"{job_type}_scheduler_enabled",
                       job_type == "system"))


def _resolve(node: dict, target: str) -> Tuple[Optional[str], bool]:
    if not target.startswith("${"):
        return target, True
    key = target[2:-1]
    if key == "node.datacenter":
        return node["datacenter"], True
    if key.startswith("attr."):
        val = node["attributes"].get(key[5:])
    elif key.startswith("meta."):
        val = node["meta"].get(key[5:])
    else:
        raise ValueError(f"target {target!r} is outside the reference's "
                         f"vocabulary")
    return val, val is not None


def constraint_ok(node: dict, constraint) -> bool:
    """One constraint, operand by operand (feasible.go checkConstraint)."""
    ltarget, operand, rtarget = constraint
    lval, lfound = _resolve(node, ltarget)
    rval, rfound = _resolve(node, rtarget)
    if not (lfound and rfound):
        return False
    if operand in ("=", "==", "is"):
        return lval == rval
    if operand == "regexp":
        return re.search(rval, lval) is not None
    raise ValueError(f"operand {operand!r} is outside the reference's "
                     f"vocabulary")


def node_feasible(node: dict, job: dict) -> List[str]:
    """Why `node` may NOT run `job` ([] when it may)."""
    why = []
    if node["datacenter"] not in job["datacenters"]:
        why.append(f"datacenter {node['datacenter']}")
    if job["driver"] not in node["drivers"]:
        why.append(f"driver {job['driver']}")
    for c in job["constraints"]:
        if not constraint_ok(node, c):
            why.append(f"constraint {c}")
    return why


# ---------------------------------------------------------------------
# The guarantees, each a list of what breaks it
# ---------------------------------------------------------------------

def check_evals(jobs: List[dict], evals: Dict[str, dict]
                ) -> Tuple[List[str], List[str]]:
    """(never completed, completed without placing everything)."""
    never, unplaced = [], []
    for job in jobs:
        ev = evals.get(job["id"])
        if ev is None or ev.get("status") not in ("complete", "failed",
                                                  "canceled"):
            never.append(f"{job['id']}: eval "
                         f"{(ev or {}).get('status', 'missing')}")
        elif ev["status"] != "complete" or ev.get("failed_tg_allocs") \
                or ev.get("blocked_eval"):
            unplaced.append(f"{job['id']}: {ev['status']} failed_tg_allocs="
                            f"{ev.get('failed_tg_allocs')} blocked="
                            f"{ev.get('blocked_eval')}")
    return never, unplaced


def check_committed(jobs: List[dict],
                    allocs: Dict[str, List[dict]]) -> List[str]:
    """Every asked allocation committed exactly once and readable."""
    bad = []
    seen_ids = set()
    for job in jobs:
        got = allocs.get(job["id"], [])
        want = {f"{job['id']}.{job['group']}[{i}]"
                for i in range(job["count"])}
        names = collections.Counter(a["name"] for a in got)
        dup = [n for n, c in names.items() if c > 1]
        if set(names) != want or dup:
            bad.append(f"{job['id']}: {len(got)} allocs for "
                       f"{job['count']} asked ({len(want - set(names))} "
                       f"missing, {len(set(names) - want)} unexpected, "
                       f"{len(dup)} duplicated names)")
        for a in got:
            if a["id"] in seen_ids:
                bad.append(f"{job['id']}: alloc id {a['id']} twice")
            seen_ids.add(a["id"])
            if a["desired_status"] != "run" or a["job_id"] != job["id"]:
                bad.append(f"{job['id']}: alloc {a['id']} is "
                           f"{a['desired_status']}/{a['job_id']}")
    return bad


def node_usage(backlog: Dict[str, Dict[str, float]], jobs: List[dict],
               allocs: Dict[str, List[dict]]
               ) -> Dict[str, Dict[str, float]]:
    """Per-node committed usage: the resident backlog (for a tiered
    configuration the resident allocations that still `run`:
    ResidentState.usage) plus every alloc of `jobs`, from the jobs'
    asks."""
    used = {nid: dict(row) for nid, row in backlog.items()}
    for job in jobs:
        for a in allocs.get(job["id"], []):
            row = used.get(a["node_id"])
            if row is None:
                continue        # check_feasible reports unknown nodes
            for d in DIMS:
                row[d] += job["ask"][d]
    return used


def check_capacity(fleet: List[dict],
                   used: Dict[str, Dict[str, float]]) -> List[str]:
    """Per node, committed cpu/memory/disk/mbits <= capacity."""
    bad = []
    for n in fleet:
        for d in DIMS:
            if used[n["id"]][d] > n["capacity"][d]:
                bad.append(f"{n['name']}: {d} {used[n['id']][d]} > "
                           f"{n['capacity'][d]}")
    return bad


def check_feasible(fleet: List[dict], jobs: List[dict],
                   allocs: Dict[str, List[dict]]) -> List[str]:
    """Every placement sits on a node whose attributes satisfy the job."""
    by_id = {n["id"]: n for n in fleet}
    bad = []
    verdicts: Dict[tuple, List[str]] = {}
    for job in jobs:
        shape = (job["driver"], tuple(job["datacenters"]),
                 tuple(job["constraints"]))
        for a in allocs.get(job["id"], []):
            node = by_id.get(a["node_id"])
            if node is None:
                bad.append(f"{a['name']}: unknown node {a['node_id']}")
                continue
            key = (node["id"], shape)
            why = verdicts.get(key)
            if why is None:
                why = verdicts[key] = node_feasible(node, job)
            if why:
                bad.append(f"{a['name']} on {node['name']}: {why}")
    return bad


def check_ports(full_allocs: List[dict], port_range) -> List[str]:
    """Dynamic ports: inside the dynamic range, unique per node."""
    bad = []
    taken: Dict[str, set] = collections.defaultdict(set)
    lo, hi = port_range
    for a in full_allocs:
        res = a.get("allocated_resources") or {}
        for task in (res.get("tasks") or {}).values():
            for nw in task.get("networks") or []:
                for p in nw.get("dynamic_ports") or []:
                    v = p["value"]
                    if not lo <= v <= hi:
                        bad.append(f"{a['name']}: port {v} outside "
                                   f"[{lo}, {hi}]")
                    if v in taken[a["node_id"]]:
                        bad.append(f"{a['name']}: port {v} taken twice "
                                   f"on node {a['node_id'][:8]}")
                    taken[a["node_id"]].add(v)
    return bad


def check_spread(fleet: List[dict], jobs: List[dict],
                 allocs: Dict[str, List[dict]]) -> List[str]:
    """Targeted spreads: the reference's boost (spread.go) turns
    negative once a value holds its desired count, so on a fleet with
    room in every value no explicit target ends above
    ceil(percent x count)."""
    by_id = {n["id"]: n for n in fleet}
    bad = []
    for job in jobs:
        for attribute, _weight, targets in job["spreads"]:
            hist = collections.Counter(
                _resolve(by_id[a["node_id"]], attribute)[0]
                for a in allocs.get(job["id"], [])
                if a["node_id"] in by_id)
            for value, percent in targets:
                bound = math.ceil(percent / 100.0 * job["count"])
                if hist.get(value, 0) > bound:
                    bad.append(f"{job['id']}: {hist[value]} on {value}, "
                               f"target {percent}% of {job['count']}")
    return bad


def binpack_scores(capacity: np.ndarray, used: np.ndarray,
                   ask: np.ndarray) -> np.ndarray:
    """rank.go BinPack for every node at once, in float64: the score of
    putting one more `ask` on a node that holds `used` (PlainScorer
    computes the same number one node at a time)."""
    free_cpu = 1.0 - (used[:, 0] + ask[0]) / capacity[:, 0]
    free_mem = 1.0 - (used[:, 1] + ask[1]) / capacity[:, 1]
    total = 10.0 ** free_cpu + 10.0 ** free_mem
    return np.clip(20.0 - total, 0.0, 18.0) / 18.0


def lane_ids(n_rows: int, lanes: int, rule: dict) -> np.ndarray:
    """The lane of every node table row under the deployment's stated
    decorrelation rule (the configuration file's server.decorrelation):
    rows in ascending node id, lane of row i =
    ((i x multiplier mod 2^modulus_bits) >> shift) mod lanes."""
    mix = (np.arange(n_rows, dtype=np.uint64) * np.uint64(rule["multiplier"])
           ) & np.uint64((1 << rule["modulus_bits"]) - 1)
    return ((mix >> np.uint64(rule["shift"])) % np.uint64(lanes)
            ).astype(np.int64)


def check_rank(fleet: List[dict], backlog: Dict[str, Dict[str, float]],
               jobs: List[dict], allocs: Dict[str, List[dict]],
               lanes: int, decorrelation: Optional[dict] = None,
               residents: Optional["ResidentState"] = None
               ) -> Tuple[List[str], float, List[str]]:
    """The ranking, judged plan by plan in the order the store
    committed them (the allocs' create_index), each against the fleet
    as it stood before that plan: the resident backlog plus every plan
    committed earlier. Jobs whose score is node-local (bin-pack and
    the job's own anti-affinity; no spread, no affinity) are ranked;
    the others only add their usage.

    stacked   plans that put an alloc on a node which then holds the
              job twice or more (by this plan or an earlier one of the
              job) although the reference's greedy, over the share that
              ranked the plan, would stack none: a node that already
              holds the job scores at most (1 - 2/count)/2, and at
              least as many other nodes with room OF THAT SHARE as the
              plan asked for score above that.
    rank_gap  the widest gap by which a plan's worst chosen node scores
              below the bound of the share that ranked it, k being the
              plan's distinct nodes.

    The share. `lanes` concurrent schedulers rank large asks over
    disjoint shares of the fleet (`decorrelation`, the configuration's
    stated rule: that is how they avoid each other's winners). A plan
    of an ask of `min_count` instances or more whose rows all lie in
    one lane was ranked over that lane: both numbers hold it to THAT
    LANE, whatever the other lane still holds (a share can run out of a
    machine class before the fleet does), and its rank bound is the
    k-th best node with room of the lane. Any other plan ranked the
    whole fleet, beside up to `lanes` - 1 others that did: both numbers
    hold it to the fleet, and its rank bound is the (lanes x k)-th best
    node with room of the fleet. Without a rule every plan is of the
    second kind. The rule's headroom test is not judged: the program
    takes it on a snapshot that may trail this replay by the other
    scheduler's plan in flight.

    With `residents` (a tiered configuration) `backlog` is the fleet as
    loaded, the resident allocations' evictions and replacements are
    applied at the index the store committed them under, and a plan
    that evicted is not ranked: which node among those that need an
    eviction is the preemption score's choice (PERF.md, Not compared).

    Returns (stacked, rank_gap, the widest gaps described)."""
    row = {n["id"]: i for i, n in enumerate(fleet)}
    capacity = np.array([[n["capacity"][d] for d in DIMS] for n in fleet],
                        dtype=np.float64)
    used = np.array([[backlog[n["id"]][d] for d in DIMS] for n in fleet],
                    dtype=np.float64)
    lane_of = lane_ids(len(fleet), lanes, decorrelation) \
        if decorrelation and lanes > 1 else None
    plans = []          # (create_index, job, rows of its placements)
    for job in jobs:
        by_index: Dict[int, List[int]] = collections.defaultdict(list)
        for a in allocs.get(job["id"], []):
            if a["node_id"] in row:
                by_index[int(a.get("create_index") or 0)].append(
                    row[a["node_id"]])
        plans.extend((index, job, rows) for index, rows in by_index.items())
    plans.sort(key=lambda p: p[0])

    feasible: Dict[tuple, np.ndarray] = {}
    held: Dict[str, np.ndarray] = {}    # job id -> its allocs per node
    stacked, widest = [], []
    rank_gap = 0.0
    due = sorted(residents.commits) if residents is not None else []
    for index, job, rows in plans:
        while due and due[0] < index:
            residents.apply(due.pop(0), row, used)
        ask = np.array([job["ask"][d] for d in DIMS], dtype=np.float64)
        chosen = np.bincount(rows, minlength=len(fleet))
        evicted = residents is not None and index in residents.evicting
        if not job["spreads"] and not job["affinities"] and not evicted:
            shape = (job["driver"], tuple(job["datacenters"]),
                     tuple(job["constraints"]))
            if shape not in feasible:
                feasible[shape] = np.array(
                    [not node_feasible(n, job) for n in fleet])
            room = feasible[shape] & np.all(used + ask <= capacity, axis=1)
            score = binpack_scores(capacity, used, ask)
            before = held.get(job["id"])
            asked = job["count"]        # what this plan's select asked for
            if before is not None:      # a retry: the job's own allocs
                score = np.where(before > 0, (score - (before + 1.0)
                                              / job["count"]) / 2.0, score)
                asked -= int(before.sum())
            k = int((chosen > 0).sum())
            lane = None         # the lane that ranked this plan, if one did
            if lane_of is not None and k \
                    and asked >= decorrelation["min_count"] \
                    and bool((lane_of[rows] == lane_of[rows[0]]).all()):
                lane = int(lane_of[rows[0]])
            if lane is None:
                pool, where = room, "fleet"
                nth, share = lanes * k, f"the {lanes}x{k}-th best"
            else:
                pool, where = room & (lane_of == lane), f"lane {lane}"
                nth, share = k, f"lane {lane}'s {k}-th best"
            if job["count"] > 1:
                ceiling = (1.0 - 2.0 / job["count"]) / 2.0
                now = chosen if before is None else chosen + before
                doubled = int(((chosen > 0) & (now > 1)).sum())
                above = int((pool & (score > ceiling)).sum())
                if doubled and above >= asked:
                    stacked.append(
                        f"{job['id']} plan {index}: {where}: {len(rows)} "
                        f"allocs on {k} nodes, {doubled} holding the job "
                        f"twice or more, {above:,} with room above the "
                        f"ceiling")
            best = np.sort(score[pool])[::-1]
            if k and len(best):
                ref = float(best[min(nth, len(best)) - 1])
                worst = float(score[chosen > 0].min())
                gap = max(0.0, ref - worst)
                widest.append((gap, (
                    f"{job['id']} plan {index}: worst of {k} chosen scores "
                    f"{worst:.6f}, {share} with room {ref:.6f}, the best "
                    f"{float(best[0]):.6f}")))
                rank_gap = max(rank_gap, gap)
        held[job["id"]] = held.get(job["id"], 0) + chosen
        used += chosen[:, None] * ask[None, :]
    widest.sort(key=lambda g: -g[0])
    return stacked, rank_gap, [w for g, w in widest[:5] if g > TIE_EPS]


class ResidentState:
    """The resident allocations of a tiered configuration as they stand
    after the drain. `plain` is fleet.residents (what the loader made),
    `now` what GET /v1/job/<id>/allocations answered for every tier
    job: the loader's allocations, and the replacements a follow-up
    eval of an evicted job placed. A resident runs on its node from the
    index it was created under (loaded: before any commit) until the
    index it was last modified under, if it no longer `run`s.

      commits   {commit index: [(kind, node id, tier, job id)]}: what
                changed since the load, the one replay that the
                capacity check (`usage`), the ranking (check_rank) and
                check_evictions read through `apply` — "placed" a
                replacement created under that index, "evict" a
                resident evicted under it, "stop" one that ended any
                other way
      stopped   what breaks `residents_stopped`: a loaded allocation
                that is gone, moved, or neither `run` nor `evict`; a
                tier job that holds more allocations the loader did
                not make than it had evicted
      evicting  the commit indices under which a resident was evicted"""

    def __init__(self, plain: dict, now: Dict[str, List[dict]]):
        self.tiers = plain["tiers"]
        self.plain = plain
        self.vecs = [np.array([t["alloc"][d] for d in DIMS],
                              dtype=np.float64) for t in self.tiers]
        self.commits: Dict[int, List[tuple]] = collections.defaultdict(list)
        self.stopped: List[str] = []
        self.evicting = set()
        seen = 0
        for job_id, pj in plain["jobs"].items():
            fresh = evicted = 0
            for stub in now.get(job_id, []):
                made = plain["allocs"].get(stub["id"])
                status, node_id = stub["desired_status"], stub["node_id"]
                if made is None:
                    fresh += 1
                    self.commits[int(stub.get("create_index") or 0)].append(
                        ("placed", node_id, pj["tier"], job_id))
                else:
                    seen += 1
                    if (made[1], made[2]) != (stub["job_id"], node_id):
                        self.stopped.append(
                            f"{stub['id']}: loaded on {made[2][:8]} for "
                            f"{made[1]}, read back on {node_id[:8]} of "
                            f"{stub['job_id']}")
                        node_id = made[2]
                if status == "run":
                    continue
                ended = int(stub.get("modify_index") or 0)
                if status == "evict":
                    evicted += 1
                    self.evicting.add(ended)
                else:
                    self.stopped.append(f"{stub['id']}: {status}")
                self.commits[ended].append(
                    ("evict" if status == "evict" else "stop", node_id,
                     pj["tier"], job_id))
            if fresh > evicted:
                self.stopped.append(
                    f"{job_id}: {fresh} allocations the loader did not "
                    f"make, {evicted} evicted")
        if seen != len(plain["allocs"]):
            read = {s["id"] for stubs in now.values() for s in stubs}
            self.stopped += [f"{i}: not read back"
                             for i in plain["allocs"] if i not in read]

    def apply(self, index: int, row_of: Dict[str, int], used: np.ndarray,
              count: Optional[np.ndarray] = None) -> None:
        """What commit `index` did to the residents, onto `used`
        [rows x DIMS] and, if given, `count` [rows x tiers]."""
        for kind, node_id, tier, _job_id in self.commits.get(index, ()):
            at = row_of.get(node_id)
            if at is None:
                continue
            sign = 1 if kind == "placed" else -1
            used[at] += sign * self.vecs[tier]
            if count is not None:
                count[at, tier] += sign

    def usage(self) -> Dict[str, Dict[str, float]]:
        """Per node, the resident allocations that still `run`: the
        fleet as loaded with every commit applied."""
        row_of = {nid: i for i, nid in enumerate(self.plain["usage"])}
        used = np.array([[row[d] for d in DIMS]
                         for row in self.plain["usage"].values()],
                        dtype=np.float64).reshape(len(row_of), len(DIMS))
        for index in self.commits:
            self.apply(index, row_of, used)
        return {nid: dict(zip(DIMS, used[i].tolist()))
                for nid, i in row_of.items()}


def _slots(free: np.ndarray, ask: np.ndarray) -> np.ndarray:
    """How many `ask`s fit into each row of `free`, every dimension."""
    dims = ask > 0
    if not dims.any():
        return np.full(len(free), np.iinfo(np.int64).max)
    return np.floor(np.clip(free[:, dims], 0, None) / ask[dims]
                    ).min(axis=1).astype(np.int64)


def check_evictions(fleet: List[dict], residents: ResidentState,
                    jobs: List[dict], allocs: Dict[str, List[dict]]
                    ) -> Dict[str, List[str]]:
    """The evictions, commit by commit in the store's order (the
    allocations' create_index; a victim's modify_index is its evictor's
    commit), each against the fleet as it stood before that commit.
    Placements are the window's jobs' and the replacements that the
    evicted jobs' follow-up evals made (priority and ask of their tier).

    evicted_wrongly     a victim on a node that took no placement under
                        that index, or whose tier's priority is within
                        PRIORITY_DELTA of (or above) the highest
                        priority placed there
    evicted_needlessly  per node, victims that can be restored, highest
                        tier first, with the node's placements still
                        fitting in every dimension (filterSuperset);
                        and an eligible victim of a tier while an
                        eligible allocation of a lower-priority tier
                        still runs there (tiers are taken lowest
                        first; sizes within a tier are equal, so both
                        are exact counts)
    evicted_with_room   placements that needed an eviction beyond those
                        that fit nowhere: a placement evicts only once
                        no feasible node has room for the ask as it is
    """
    tiers = residents.tiers
    prio = [t["priority"] for t in tiers]
    vecs = residents.vecs
    row_of = {n["id"]: i for i, n in enumerate(fleet)}
    capacity = np.array([[n["capacity"][d] for d in DIMS] for n in fleet],
                        dtype=np.float64)
    # the fleet as loaded
    used = np.array([[residents.plain["usage"][n["id"]][d] for d in DIMS]
                     for n in fleet], dtype=np.float64)
    loaded = [[0] * len(tiers) for _ in fleet]
    for tier, _job_id, node_id in residents.plain["allocs"].values():
        if node_id in row_of:
            loaded[row_of[node_id]][tier] += 1
    count = np.array(loaded, dtype=np.int64).reshape(len(fleet), len(tiers))

    # what was committed under each index: parts (who placed what where)
    # and victims (which residents were evicted there)
    parts: Dict[int, list] = collections.defaultdict(list)
    victims: Dict[int, Dict[int, List[int]]] = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for job in jobs:
        by_index: Dict[int, List[int]] = collections.defaultdict(list)
        for a in allocs.get(job["id"], []):
            if a["node_id"] in row_of:
                by_index[int(a.get("create_index") or 0)].append(
                    row_of[a["node_id"]])
        ask = np.array([job["ask"][d] for d in DIMS], dtype=np.float64)
        for index, rows in by_index.items():
            parts[index].append({"who": job["id"], "job": job, "ask": ask,
                                 "priority": job["priority"], "rows": rows})
    born: Dict[tuple, List[int]] = collections.defaultdict(list)
    for index, events in residents.commits.items():
        for kind, node_id, tier, job_id in events:
            if node_id not in row_of:
                continue
            if kind == "placed":
                born[(index, job_id, tier)].append(row_of[node_id])
            elif kind == "evict":
                victims[index][row_of[node_id]].append(tier)
    for (index, job_id, tier), rows in born.items():
        parts[index].append({"who": job_id, "job": None, "ask": vecs[tier],
                             "priority": prio[tier], "rows": rows})

    feasible: Dict[tuple, np.ndarray] = {}
    wrongly, needlessly, with_room = [], [], []
    for index in sorted(set(parts) | set(residents.commits)):
        here, gone = parts.get(index, []), victims.get(index, {})
        placed = collections.defaultdict(list)      # row -> its parts
        for part in here:
            for at in set(part["rows"]):
                placed[at].append(part)
        if gone:
            free = capacity - used
            for part in here:
                ask, job = part["ask"], part["job"]
                ok = np.ones(len(fleet), dtype=bool)
                if job is not None:
                    shape = (job["driver"], tuple(job["datacenters"]),
                             tuple(job["constraints"]))
                    if shape not in feasible:
                        feasible[shape] = np.array(
                            [not node_feasible(n, job) for n in fleet])
                    ok = feasible[shape]
                slots = _slots(free, ask)
                on = np.bincount(part["rows"], minlength=len(fleet))
                forced = sum(max(0, int(on[at] - slots[at]))
                             for at in gone if on[at])
                nowhere = max(0, len(part["rows"]) - int(slots[ok].sum()))
                if forced > nowhere:
                    with_room.extend(
                        [f"{part['who']} commit {index}: {forced} of "
                         f"{len(part['rows'])} placements evicted, "
                         f"{int(slots[ok].sum())} fitted as the fleet stood"]
                        * (forced - nowhere))
                # what this part takes is no longer free for the next
                free = free - on[:, None] * ask[None, :]
        for at, took in gone.items():
            name = fleet[at]["name"]
            if at not in placed:
                wrongly.extend([f"{name} commit {index}: {len(took)} evicted, "
                                f"nothing placed"] * len(took))
                continue
            top = max(part["priority"] for part in placed[at])
            peers = [t for t in took if top - prio[t] < PRIORITY_DELTA]
            wrongly.extend(
                [f"{name} commit {index}: {len(peers)} evicted of priority "
                 f"{sorted({prio[t] for t in peers})} for priority {top}"]
                * len(peers))
            after = used[at].copy()
            for t in took:
                after -= vecs[t]
            for part in placed[at]:
                after += part["rows"].count(at) * part["ask"]
            spare = 0
            for t in sorted(took, key=lambda t: -prio[t]):
                if np.all(after + vecs[t] <= capacity[at]):
                    after = after + vecs[t]
                    spare += 1
            left = count[at].copy()
            for t in took:
                left[t] -= 1
            jumped = sum(
                1 for t in took if t not in peers and any(
                    left[u] > 0 and prio[u] < prio[t]
                    and top - prio[u] >= PRIORITY_DELTA
                    for u in range(len(tiers))))
            if spare or jumped:
                needlessly.extend(
                    [f"{name} commit {index}: {len(took)} evicted, {spare} "
                     f"can be restored and the placements still fit, "
                     f"{jumped} taken while a lower tier still runs there"]
                    * (spare + jumped))
        residents.apply(index, row_of, used, count)
        for part in here:
            if part["job"] is not None:         # the window's own
                for at in part["rows"]:
                    used[at] += part["ask"]
    return {"evicted_wrongly": wrongly, "evicted_needlessly": needlessly,
            "evicted_with_room": with_room}


def port_sample(fleet: List[dict], jobs: List[dict],
                allocs: Dict[str, List[dict]], budget: int,
                rng) -> List[str]:
    """Which allocs to read in full for the port check: ports can only
    clash on one node, so whole nodes — the fullest first (where a clash
    is likeliest), then nodes drawn from the seed — until `budget`
    alloc reads are spent."""
    by_node: Dict[str, List[str]] = collections.defaultdict(list)
    for job in jobs:
        if job["dynamic_ports"]:
            for a in allocs.get(job["id"], []):
                by_node[a["node_id"]].append(a["id"])
    shared = [n for n, ids in by_node.items() if len(ids) > 1]
    shared.sort(key=lambda n: (-len(by_node[n]), n))
    head = shared[:max(1, len(shared) // 8)]
    tail = shared[len(head):]
    rng.shuffle(tail)
    out: List[str] = []
    for nid in head + tail:
        if len(out) + len(by_node[nid]) > budget:
            continue
        out.extend(by_node[nid])
    return out


def judge(fleet: List[dict], backlog: Dict[str, Dict[str, float]],
          jobs: List[dict], evals: Dict[str, dict],
          allocs: Dict[str, List[dict]], full_allocs: List[dict],
          unread: List[str], port_range, lanes: int,
          decorrelation: Optional[dict] = None,
          residents: Optional["ResidentState"] = None
          ) -> Tuple[dict, Dict[str, List[str]]]:
    """Every number compared, beside its limit, and what broke each.
    `lanes` is the configuration's number of concurrent schedulers,
    `decorrelation` its stated rule for their shares of the fleet.
    `residents` (a tiered configuration alone): the resident
    allocations as read back after the drain; `backlog` is then the
    fleet as loaded, the capacity check takes the residents that still
    run, and TIER_LIMITS' four numbers are compared too."""
    never, unplaced = check_evals(jobs, evals)
    placed_jobs = [j for j in jobs if j["id"] in allocs]
    stacked, rank_gap, widest = check_rank(fleet, backlog, placed_jobs,
                                           allocs, lanes, decorrelation,
                                           residents)
    if residents is not None:
        backlog = residents.usage()
    found = {
        "never_completed": never,
        "unplaced_evals": unplaced,
        "lost_or_duplicated": check_committed(
            [j for j in placed_jobs
             if (evals.get(j["id"]) or {}).get("status") == "complete"
             and not evals[j["id"]].get("failed_tg_allocs")], allocs),
        "unread": list(unread),
        "over_capacity": check_capacity(
            fleet, node_usage(backlog, placed_jobs, allocs)),
        "infeasible": check_feasible(fleet, placed_jobs, allocs),
        "port_conflicts": check_ports(full_allocs, port_range),
        "spread_over_target": check_spread(fleet, placed_jobs, allocs),
        "stacked": stacked,
        "rank_gap": widest,
    }
    limits = dict(LIMITS)
    if residents is not None:
        found.update(check_evictions(fleet, residents, placed_jobs, allocs))
        found["residents_stopped"] = residents.stopped
        limits.update(TIER_LIMITS)
    compared = {name: {"value": len(found[name]), "limit": limits[name]}
                for name in limits}
    compared["rank_gap"]["value"] = rank_gap
    return compared, found


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


# ---------------------------------------------------------------------
# The reference ranking and a plain scheduler to stand in the
# program's place
# ---------------------------------------------------------------------

class PlainScorer:
    """The reference scheduler's ranking in float64, one node at a time
    (rank.go BinPack / JobAntiAffinity / NodeAffinity, spread.go,
    ScoreNormalization: mean over the scorers that fired). Ties go to
    the lowest node id — the table's row order."""

    def __init__(self, fleet: List[dict], job: dict,
                 used: Dict[str, Dict[str, float]],
                 ignore_constraints: bool = False):
        self.job = job
        self.ask = job["ask"]
        self.nodes = [n for n in fleet
                      if ignore_constraints or not node_feasible(n, job)]
        self.used = {n["id"]: dict(used[n["id"]]) for n in self.nodes}
        self.coll: Dict[str, int] = collections.Counter()
        aff = job["affinities"]
        sum_w = sum(abs(w) for *_c, w in aff)
        self.affinity = {
            n["id"]: (sum(w for l, op, r, w in aff
                          if constraint_ok(n, (l, op, r))) / sum_w
                      if sum_w else 0.0) for n in self.nodes}
        self.spreads = []
        sum_sw = float(sum(w for _a, w, _t in job["spreads"]))
        for attribute, weight, targets in job["spreads"]:
            desired = {v: pct / 100.0 * job["count"] for v, pct in targets}
            rest = job["count"] - sum(desired.values())
            self.spreads.append({
                "value": {n["id"]: _resolve(n, attribute)[0]
                          for n in self.nodes},
                "desired": desired, "implicit": rest if rest > 0 else None,
                "weight": weight / sum_sw,
                "counts": collections.Counter()})

    def fits(self, node: dict) -> bool:
        u = self.used[node["id"]]
        return all(u[d] + self.ask[d] <= node["capacity"][d] for d in DIMS)

    def score(self, node: dict) -> float:
        u = self.used[node["id"]]
        cap = node["capacity"]
        free_cpu = 1.0 - (u["cpu"] + self.ask["cpu"]) / cap["cpu"]
        free_mem = 1.0 - (u["memory_mb"] + self.ask["memory_mb"]) \
            / cap["memory_mb"]
        total = 10.0 ** free_cpu + 10.0 ** free_mem
        parts = [min(max(20.0 - total, 0.0), 18.0) / 18.0]
        coll = self.coll[node["id"]]
        if coll > 0:
            parts.append(-(coll + 1.0) / max(self.job["count"], 1.0))
        if self.affinity[node["id"]] != 0.0:
            parts.append(self.affinity[node["id"]])
        spread = 0.0
        for sp in self.spreads:
            value = sp["value"][node["id"]]
            want = sp["desired"].get(value, sp["implicit"])
            if value is None or want is None:
                spread -= 1.0
            else:
                spread += (want - (sp["counts"][value] + 1.0)) / want \
                    * sp["weight"]
        if spread != 0.0:
            parts.append(spread)
        return sum(parts) / len(parts)

    def place(self, node: dict) -> None:
        u = self.used[node["id"]]
        for d in DIMS:
            u[d] += self.ask[d]
        self.coll[node["id"]] += 1
        for sp in self.spreads:
            sp["counts"][sp["value"][node["id"]]] += 1

    def in_row_order(self, count: int, apart: bool
                     ) -> List[Optional[dict]]:
        """No ranking at all: the first nodes with room in the table's
        row order — one alloc each when `apart`, else each node filled
        before the next."""
        out: List[Optional[dict]] = []
        for n in self.nodes:
            while len(out) < count and self.fits(n):
                self.place(n)
                out.append(n)
                if apart:
                    break
            if len(out) == count:
                break
        return out + [None] * (count - len(out))

    def greedy(self, count: int, check_fit: bool = True
               ) -> List[Optional[dict]]:
        """The node of each of `count` greedy placements."""
        out: List[Optional[dict]] = []

        def ok(n):
            return self.fits(n) if check_fit else True

        if self.spreads:
            # spread couples the nodes: rescore all of them per step
            for _ in range(count):
                best, best_s = None, -math.inf
                for n in self.nodes:       # id order: first max wins
                    if ok(n):
                        s = self.score(n)
                        if s > best_s:
                            best, best_s = n, s
                if best is not None:
                    self.place(best)
                out.append(best)
            return out
        # node-local scoring: a heap of (-score, row) is exact greedy
        heap = [(-self.score(n), i) for i, n in enumerate(self.nodes)
                if ok(n)]
        heapq.heapify(heap)
        for _ in range(count):
            if not heap:
                out.append(None)
                continue
            _s, i = heapq.heappop(heap)
            n = self.nodes[i]
            self.place(n)
            out.append(n)
            if ok(n):
                heapq.heappush(heap, (-self.score(n), i))
        return out


CONTROLS = ("capacity", "constraints", "lose", "ports", "firstfit",
            "norank", "noevict", "evictpeer", "evictall", "evictearly")


class PlainScheduler:
    """Answers the jobs the way the served path would, one eval after
    another, in the shapes HTTP returns. `broken` names the one
    guarantee the control drops:

      capacity     places without asking whether the node has room
      constraints  places without asking whether the node is feasible
      lose         acknowledges every job and loses the last alloc of
                   every third
      ports        hands a node's first dynamic port out twice
      firstfit     ranks nothing: every alloc goes to the first node, in
                   the table's row order, that is feasible and has room
      norank       ranks nothing but keeps a job's allocs apart: the
                   first `count` such nodes, one alloc each

    With `residents` (a tiered configuration: fleet.residents) and a
    `scheduler_configuration` that lets the job's type preempt, an
    instance for which no feasible node has room evicts: the node
    needing the fewest victims, lowest tier first, exactly as many as
    the deficit needs. Four more controls break that:

      noevict      places there and evicts nothing
      evictpeer    takes its victims from the tiers whose priority is
                   within PRIORITY_DELTA of the job's
      evictall     takes every eligible allocation of the node
      evictearly   never looks for room: every instance evicts, on the
                   first feasible node in row order that is full
    """

    def __init__(self, fleet: List[dict],
                 backlog: Dict[str, Dict[str, float]], port_range,
                 broken: Optional[str] = None,
                 residents: Optional[dict] = None,
                 scheduler_configuration: Optional[dict] = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.residents = residents
        self.scheduler_configuration = scheduler_configuration
        # node id -> per tier, the ids of its residents that still run
        self.running: Dict[str, List[List[str]]] = {}
        self.resident_stubs: Dict[str, dict] = {}
        if residents is not None:
            n_tiers = len(residents["tiers"])
            self.running = {n["id"]: [[] for _ in range(n_tiers)]
                            for n in fleet}
            for alloc_id, (tier, job_id, node_id) in \
                    residents["allocs"].items():
                self.running[node_id][tier].append(alloc_id)
                self.resident_stubs[alloc_id] = {
                    "id": alloc_id, "node_id": node_id, "job_id": job_id,
                    "desired_status": "run", "create_index": 0,
                    "modify_index": 0}
        self.fleet = fleet
        self.used = {nid: dict(row) for nid, row in backlog.items()}
        self.broken = broken
        self.port_lo = port_range[0]
        self.next_port: Dict[str, int] = collections.Counter()
        self.allocs: Dict[str, List[dict]] = {}
        self.full: Dict[str, dict] = {}
        self.evals: Dict[str, dict] = {}
        self._n = 0

    def resident_allocs(self) -> Dict[str, List[dict]]:
        """The residents as GET /v1/job/<id>/allocations would list
        them, by tier job."""
        out: Dict[str, List[dict]] = collections.defaultdict(list)
        for stub in self.resident_stubs.values():
            out[stub["job_id"]].append(stub)
        return out

    def _evict_for(self, job: dict, nodes: List[dict],
                   index: int) -> Optional[dict]:
        """Make room for one more instance of `job` on one of `nodes`
        by evicting residents there; the node, or None if none can."""
        tiers = self.residents["tiers"]
        pool = [t for t, tier in enumerate(tiers)
                if (job["priority"] - tier["priority"] >= PRIORITY_DELTA)
                != (self.broken == "evictpeer")]
        best = None
        for node in nodes:
            short = {d: self.used[node["id"]][d] + job["ask"][d]
                     - node["capacity"][d] for d in DIMS}
            if self.broken == "evictearly" and max(short.values()) <= 0:
                continue
            take = []
            for t in pool:
                size = tiers[t]["alloc"]
                need = max([math.ceil(short[d] / size[d]) for d in DIMS
                            if short[d] > 0 and size[d] > 0], default=0)
                k = min(len(self.running[node["id"]][t]), need)
                if k:
                    take.append((t, k))
                    for d in DIMS:
                        short[d] -= k * size[d]
            if max(short.values()) > 0:
                continue
            n_victims = sum(k for _t, k in take)
            if best is None or n_victims < best[0]:
                best = (n_victims, node, take)
            if self.broken == "evictearly":
                break
        if best is None:
            return None
        _n, node, take = best
        if self.broken == "noevict":
            take = []
        elif self.broken == "evictall":
            take = [(t, len(self.running[node["id"]][t])) for t in pool]
        for t, k in take:
            for _ in range(k):
                stub = self.resident_stubs[
                    self.running[node["id"]][t].pop(0)]
                stub["desired_status"] = "evict"
                stub["modify_index"] = index
            for d in DIMS:
                self.used[node["id"]][d] -= k * tiers[t]["alloc"][d]
        return node

    def submit(self, job: dict) -> None:
        scorer = PlainScorer(self.fleet, job, self.used,
                             ignore_constraints=self.broken == "constraints")
        preempts = self.residents is not None and preemption_enabled(
            self.scheduler_configuration, job["type"])
        if self.broken in ("firstfit", "norank"):
            nodes = scorer.in_row_order(job["count"],
                                        apart=self.broken == "norank")
        elif self.broken == "evictearly" and preempts:
            nodes = [None] * job["count"]
        else:
            nodes = scorer.greedy(job["count"],
                                  check_fit=self.broken != "capacity")
        stubs, placed = [], 0
        for i, node in enumerate(nodes):
            if node is None and preempts:
                node = self._evict_for(job, scorer.nodes,
                                       len(self.evals) + 1)
            if node is None:
                continue
            placed += 1
            for d in DIMS:
                self.used[node["id"]][d] += job["ask"][d]
            self._n += 1
            alloc_id = f"plain-{self._n:08d}"
            stub = {"id": alloc_id,
                    "name": f"{job['id']}.{job['group']}[{i}]",
                    "node_id": node["id"], "job_id": job["id"],
                    "desired_status": "run",
                    "create_index": len(self.evals) + 1}
            stubs.append(stub)
            ports = []
            for p in range(job["dynamic_ports"]):
                k = self.next_port[node["id"]]
                self.next_port[node["id"]] += 1
                if self.broken == "ports" and k == 1:
                    k = 0       # the node's first port, handed out twice
                ports.append({"label": f"p{p}", "value": self.port_lo + k})
            self.full[alloc_id] = dict(
                stub, allocated_resources={"tasks": {job["task"]: {
                    "networks": [{"dynamic_ports": ports}]}}})
        if self.broken == "lose" and len(self.evals) % 3 == 0 and stubs:
            self.full.pop(stubs.pop()["id"])
        self.allocs[job["id"]] = stubs
        placed_all = placed == job["count"]
        self.evals[job["id"]] = {
            "status": "complete", "job_id": job["id"],
            "failed_tg_allocs": None if placed_all else {job["group"]: {}},
            "blocked_eval": ""}
