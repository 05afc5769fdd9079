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
    """Per-node committed usage: the resident backlog plus every alloc
    of `jobs`, from the jobs' asks."""
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
               lanes: int, decorrelation: Optional[dict] = None
               ) -> Tuple[List[str], float, List[str]]:
    """The ranking, judged plan by plan in the order the store
    committed them (the allocs' create_index), each against the fleet
    as it stood before that plan: the resident backlog plus every plan
    committed earlier. Jobs whose score is node-local (bin-pack and
    the job's own anti-affinity; no spread, no affinity) are ranked;
    the others only add their usage.

    stacked   allocs of one job that share a node although the
              reference's greedy would stack none: a node that already
              holds the job scores at most (1 - 2/count)/2, and at
              least `count` other nodes with room score above that.
    rank_gap  the widest gap by which a plan's worst chosen node scores
              below the bound of the share that ranked it, k being the
              plan's distinct nodes. `lanes` concurrent schedulers rank
              large asks over disjoint shares of the fleet
              (`decorrelation`, the configuration's stated rule: that
              is how they avoid each other's winners). A plan of an ask
              of `min_count` instances or more whose rows all lie in
              one lane was ranked over that lane: its bound is the k-th
              best node with room OF THAT LANE, whatever the other
              lane still holds (a share can run out of a machine class
              before the fleet does). Any other plan ranked the whole
              fleet, beside up to `lanes` - 1 others that did: its
              bound is the (lanes x k)-th best node with room of the
              fleet. Without a rule every plan is of the second kind.

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
    for index, job, rows in plans:
        ask = np.array([job["ask"][d] for d in DIMS], dtype=np.float64)
        chosen = np.bincount(rows, minlength=len(fleet))
        if not job["spreads"] and not job["affinities"]:
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
            if before is None and job["count"] > 1:
                ceiling = (1.0 - 2.0 / job["count"]) / 2.0
                mine = [row[a["node_id"]] for a in allocs[job["id"]]
                        if a["node_id"] in row]
                if int((room & (score > ceiling)).sum()) >= job["count"] \
                        and len(mine) > len(set(mine)):
                    stacked.append(f"{job['id']}: {len(mine)} allocs on "
                                   f"{len(set(mine))} nodes")
            k = int((chosen > 0).sum())
            pool, nth, share = room, lanes * k, f"the {lanes}x{k}-th best"
            if lane_of is not None and k \
                    and asked >= decorrelation["min_count"]:
                lane = lane_of[rows[0]]
                if bool((lane_of[rows] == lane).all()):
                    pool, nth = room & (lane_of == lane), k
                    share = f"lane {int(lane)}'s {k}-th best"
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
          decorrelation: Optional[dict] = None
          ) -> Tuple[dict, Dict[str, List[str]]]:
    """Every number compared, beside its limit, and what broke each.
    `lanes` is the configuration's number of concurrent schedulers,
    `decorrelation` its stated rule for their shares of the fleet."""
    never, unplaced = check_evals(jobs, evals)
    placed_jobs = [j for j in jobs if j["id"] in allocs]
    stacked, rank_gap, widest = check_rank(fleet, backlog, placed_jobs,
                                           allocs, lanes, decorrelation)
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
    compared = {name: {"value": len(found[name]), "limit": LIMITS[name]}
                for name in LIMITS}
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
            "norank")


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
    """

    def __init__(self, fleet: List[dict],
                 backlog: Dict[str, Dict[str, float]], port_range,
                 broken: Optional[str] = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.fleet = fleet
        self.used = {nid: dict(row) for nid, row in backlog.items()}
        self.broken = broken
        self.port_lo = port_range[0]
        self.next_port: Dict[str, int] = collections.Counter()
        self.allocs: Dict[str, List[dict]] = {}
        self.full: Dict[str, dict] = {}
        self.evals: Dict[str, dict] = {}
        self._n = 0

    def submit(self, job: dict) -> None:
        scorer = PlainScorer(self.fleet, job, self.used,
                             ignore_constraints=self.broken == "constraints")
        if self.broken in ("firstfit", "norank"):
            nodes = scorer.in_row_order(job["count"],
                                        apart=self.broken == "norank")
        else:
            nodes = scorer.greedy(job["count"],
                                  check_fit=self.broken != "capacity")
        stubs = []
        for i, node in enumerate(nodes):
            if node is None:
                continue
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
        placed_all = all(n is not None for n in nodes)
        self.evals[job["id"]] = {
            "status": "complete", "job_id": job["id"],
            "failed_tg_allocs": None if placed_all else {job["group"]: {}},
            "blocked_eval": ""}
