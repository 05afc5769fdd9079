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
import json
import math
import re
from fractions import Fraction
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

# A configuration whose machine classes carry device groups is held to
# one more, an exact count too (check_devices)
DEVICE_LIMITS = {"device_conflicts": 0}

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


# ---------------------------------------------------------------------
# Devices: upstream's device stanza (a name, a count, constraints and
# affinities on ${device.*}), DeviceChecker and AssignDevice. A node's
# group is the plain dict fleet.build_fleet makes: vendor, type, model,
# attributes (the fingerprint's values) and the ids of its instances,
# all healthy.
# ---------------------------------------------------------------------

# A unit: its dimension and how many of the dimension's base unit it is
# (the NVIDIA plugin fingerprints memory in MiB, clocks in MHz, power in
# W). Two values compare only within one dimension, or both bare.
UNITS = {
    "B": ("byte", 1), "KiB": ("byte", 2 ** 10), "MiB": ("byte", 2 ** 20),
    "GiB": ("byte", 2 ** 30), "TiB": ("byte", 2 ** 40),
    "kB": ("byte", 10 ** 3), "KB": ("byte", 10 ** 3), "MB": ("byte", 10 ** 6),
    "GB": ("byte", 10 ** 9), "TB": ("byte", 10 ** 12),
    "MHz": ("hertz", 10 ** 6), "GHz": ("hertz", 10 ** 9),
    "mW": ("watt", Fraction(1, 1000)), "W": ("watt", 1), "kW": ("watt", 10 ** 3),
}
_UNITS_LONGEST_FIRST = sorted(UNITS, key=len, reverse=True)

DEVICE_OPERANDS = ("=", "!=", "<", "<=", ">", ">=")


def device_value(value) -> tuple:
    """A fingerprint's value as ("number", amount in the base unit, its
    dimension or None) or ("string", text): a number, or a string that
    is a number and one of UNITS ("16 GiB", "1530 MHz", "300 W"), is a
    number; anything else is a string."""
    text = str(value).lower() if isinstance(value, bool) else str(value)
    unit = next((u for u in _UNITS_LONGEST_FIRST
                 if text[-1:].isalpha() and text.endswith(u)), None)
    digits = text[:-len(unit)].strip() if unit else text
    try:
        amount = Fraction(digits)
    except (ValueError, ZeroDivisionError):
        return ("string", text)
    if unit is None:
        return ("number", amount, None)
    dimension, scale = UNITS[unit]
    return ("number", amount * scale, dimension)


def compare_device_values(left, right) -> Optional[int]:
    """-1, 0 or 1, or None where the two cannot be compared: a number
    and a string, or numbers in two dimensions (a bare number against
    one with a unit among them)."""
    a, b = device_value(left), device_value(right)
    if a[0] != b[0] or a[2:] != b[2:]:
        return None
    return (a[1] > b[1]) - (a[1] < b[1])


def _resolve_device(group: dict, target: str) -> Tuple[Optional[object], bool]:
    """${device.vendor|type|model} and ${device.attr.<key>} of a group;
    a target that is no interpolation is a literal."""
    if not target.startswith("${"):
        return target, True
    key = target[2:-1]
    if key in ("device.vendor", "device.type", "device.model"):
        return group[key[7:]], True
    if key.startswith("device.attr."):
        val = group["attributes"].get(key[12:])
        return val, val is not None
    raise ValueError(f"device target {target!r} is outside the reference's "
                     f"vocabulary")


def device_constraint_ok(group: dict, constraint) -> bool:
    """One device constraint or affinity's test against one group
    (feasible.go checkAttributeConstraint): `!=` holds where one side is
    missing, every other operand needs both; values that cannot be
    compared satisfy nothing."""
    ltarget, operand, rtarget = constraint[:3]
    if operand not in DEVICE_OPERANDS:
        raise ValueError(f"device operand {operand!r} is outside the "
                         f"reference's vocabulary")
    lval, lfound = _resolve_device(group, ltarget)
    rval, rfound = _resolve_device(group, rtarget)
    if operand == "!=" and lfound != rfound:
        return True
    if not (lfound and rfound):
        return False
    c = compare_device_values(lval, rval)
    if c is None:
        return False
    return {"=": c == 0, "!=": c != 0, "<": c < 0, "<=": c <= 0,
            ">": c > 0, ">=": c >= 0}[operand]


def device_name_matches(group: dict, name: str) -> bool:
    """An ask's name in upstream's three forms: `type`,
    `vendor/type` or `vendor/type/model`."""
    parts = name.split("/")
    vendor, kind, model = (["", name, ""] if len(parts) == 1
                           else [parts[0], parts[1], "/".join(parts[2:])])
    return all(not want or want == group[key] for want, key in
               ((vendor, "vendor"), (kind, "type"), (model, "model")))


def group_satisfies(group: dict, ask: dict) -> bool:
    """The ask's name and every one of its constraints."""
    return device_name_matches(group, ask["name"]) and all(
        device_constraint_ok(group, c) for c in ask["constraints"])


def devices_ok(node: dict, job: dict) -> bool:
    """DeviceChecker: every ask of `job` has a group of `node` that
    satisfies it with at least `count` healthy instances."""
    return all(any(group_satisfies(g, ask) and len(g["ids"]) >= ask["count"]
                   for g in node.get("devices") or ())
               for ask in job.get("devices") or ())


def _shape(job: dict) -> tuple:
    """What node_feasible reads of a job, as a key."""
    key = (job["driver"], tuple(job["datacenters"]),
           tuple(job["constraints"]))
    if job.get("devices"):
        key += (json.dumps(job["devices"], sort_keys=True),)
    return key


def node_feasible(node: dict, job: dict, devices: bool = True) -> List[str]:
    """Why `node` may NOT run `job` ([] when it may); `devices`: whether
    its device asks count."""
    why = []
    if node["datacenter"] not in job["datacenters"]:
        why.append(f"datacenter {node['datacenter']}")
    if job["driver"] not in node["drivers"]:
        why.append(f"driver {job['driver']}")
    for c in job["constraints"]:
        if not constraint_ok(node, c):
            why.append(f"constraint {c}")
    if devices and not devices_ok(node, job):
        why.append(f"devices {[a['name'] for a in job['devices']]}")
    return why


class DeviceFleet:
    """The fleet's device groups as arrays (row = the fleet's order,
    column = a group's place on its node), and what one allocation of a
    job takes of them where a node says so alone."""

    def __init__(self, fleet: List[dict]):
        self.fleet = fleet
        groups = [n.get("devices") or [] for n in fleet]
        self.instances = np.zeros((len(fleet), max(map(len, groups),
                                                   default=0)), np.int64)
        for i, gs in enumerate(groups):
            for j, g in enumerate(gs):
                self.instances[i, j] = len(g["ids"])
        self._takes: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}

    def takes(self, job: dict) -> Tuple[np.ndarray, np.ndarray]:
        """(per row and group, the instances one allocation of `job`
        takes there; per row, whether that is known): known where every
        ask is satisfied by at most one group of the node (by none: a
        placement there is infeasible and takes nothing); where two or
        more satisfy one ask, which one the program took only the
        allocation's own grant says."""
        key = json.dumps(job.get("devices") or [], sort_keys=True)
        hit = self._takes.get(key)
        if hit is not None:
            return hit
        need = np.zeros_like(self.instances)
        known = np.ones(len(self.fleet), dtype=bool)
        for i, node in enumerate(self.fleet):
            groups = node.get("devices") or []
            for ask in job.get("devices") or ():
                fit = [j for j, g in enumerate(groups)
                       if group_satisfies(g, ask)]
                if len(fit) > 1:
                    known[i] = False
                elif fit:
                    need[i, fit[0]] += ask["count"]
        self._takes[key] = (need, known)
        return need, known

    def room(self, job: dict, granted: np.ndarray) -> np.ndarray:
        """Per row, how many more allocations of `job` its free matching
        instances hold (`granted`: instances taken, per row and group);
        unbounded for a job without asks."""
        if not job.get("devices"):
            return np.full(len(self.fleet), np.iinfo(np.int64).max)
        need, _known = self.takes(job)
        free = self.instances - granted
        per = np.where(need > 0, free // np.maximum(need, 1),
                       np.iinfo(np.int64).max)
        out = per.min(axis=1) if per.shape[1] else np.zeros(len(self.fleet),
                                                            np.int64)
        # a node none of whose groups an ask can use holds none
        return np.where(need.sum(axis=1) > 0, out, 0)

    def granted(self, jobs: List[dict], allocs: Dict[str, List[dict]]
                ) -> Tuple[np.ndarray, set]:
        """Instances granted to live allocations of `jobs`, per row and
        group, from the stubs; and the rows where that is not known (a
        node with two groups that satisfy one ask)."""
        row = {n["id"]: i for i, n in enumerate(self.fleet)}
        out = np.zeros_like(self.instances)
        unknown = set()
        for job in jobs:
            if not job.get("devices"):
                continue
            need, known = self.takes(job)
            for a in allocs.get(job["id"], []):
                at = row.get(a["node_id"])
                if at is None or a.get("desired_status", "run") != "run":
                    continue
                if known[at]:
                    out[at] += need[at]
                else:
                    unknown.add(at)
        return out, unknown


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


def _granted(alloc: dict) -> List[dict]:
    """The device grants of a full allocation, every task's, in order."""
    tasks = (alloc.get("allocated_resources") or {}).get("tasks") or {}
    return [d for task in tasks.values() for d in task.get("devices") or []]


def _group_of(node: dict, grant: dict) -> Optional[dict]:
    """The node's group a grant names (vendor, type, model)."""
    return next((g for g in node.get("devices") or ()
                 if (g["vendor"], g["type"], g["model"])
                 == (grant.get("vendor"), grant.get("type"),
                     grant.get("name"))), None)


def check_device_capacity(fleet: List[dict], devices: DeviceFleet,
                          jobs: List[dict], allocs: Dict[str, List[dict]],
                          device_full: List[dict]) -> List[str]:
    """The device dimension of over_capacity: per node and group, the
    instances granted to live allocations are at most the group's. From
    the stubs (asks x allocations) on every node that says alone which
    group an ask takes; on a node with two groups that satisfy one ask,
    from the allocations read there in full (`device_full`: the device
    check's sample)."""
    granted, unknown = devices.granted(jobs, allocs)
    bad = []

    def over(node, group, n):
        bad.append(f"{node['name']}: {n} instances of {group['vendor']}/"
                   f"{group['type']}/{group['model']} granted, it has "
                   f"{len(group['ids'])}")

    for at in np.flatnonzero((granted > devices.instances).any(axis=1)):
        for j, group in enumerate(fleet[at]["devices"]):
            if granted[at, j] > devices.instances[at, j]:
                over(fleet[at], group, int(granted[at, j]))
    row = {n["id"]: i for i, n in enumerate(fleet)}
    read: Dict[Tuple[int, int], int] = collections.Counter()
    for a in device_full:
        at = row.get(a["node_id"])
        if at not in unknown or a.get("desired_status") != "run":
            continue
        for grant in _granted(a):
            group = _group_of(fleet[at], grant)
            if group is not None:
                read[(at, id(group))] += len(grant.get("device_ids") or [])
    for at in sorted(unknown):
        for group in fleet[at]["devices"]:
            if read[(at, id(group))] > len(group["ids"]):
                over(fleet[at], group, read[(at, id(group))])
    return bad


def check_devices(fleet: List[dict], jobs: List[dict],
                  device_full: List[dict]) -> List[str]:
    """The device grants of the allocations read in full on the device
    check's sample of nodes (device_conflicts): each live allocation
    holds one grant an ask, in the asks' order, each of `count` distinct
    ids of one group of its node that satisfies the ask; and no id is
    granted twice, to two live allocations or in two grants of one."""
    by_id = {n["id"]: n for n in fleet}
    job_of = {j["id"]: j for j in jobs}
    holder: Dict[Tuple[str, str], str] = {}
    bad = []
    for a in device_full:
        job, node = job_of.get(a["job_id"]), by_id.get(a["node_id"])
        if job is None or node is None or a.get("desired_status") != "run":
            continue        # check_committed / check_feasible report these
        asks, grants = job.get("devices") or [], _granted(a)
        if len(grants) != len(asks):
            bad.append(f"{a['name']} on {node['name']}: {len(grants)} "
                       f"device grants for {len(asks)} asks")
        for ask, grant in zip(asks, grants):
            ids = grant.get("device_ids") or []
            group = _group_of(node, grant)
            if group is None or not group_satisfies(group, ask):
                bad.append(f"{a['name']} on {node['name']}: granted "
                           f"{grant.get('vendor')}/{grant.get('type')}/"
                           f"{grant.get('name')}, no group there that "
                           f"satisfies {ask['name']}")
            elif not set(ids) <= set(group["ids"]):
                bad.append(f"{a['name']} on {node['name']}: ids "
                           f"{sorted(set(ids) - set(group['ids']))[:2]} are "
                           f"no instances of its group")
            if len(set(ids)) != len(ids) or len(ids) != ask["count"]:
                bad.append(f"{a['name']} on {node['name']}: {len(ids)} ids "
                           f"({len(set(ids))} distinct) for a count of "
                           f"{ask['count']}")
        held = collections.Counter(dev for grant in grants
                                   for dev in set(grant.get("device_ids")
                                                  or []))
        for dev, n in held.items():
            if n > 1:
                bad.append(f"{a['name']} on {node['name']}: instance "
                           f"{dev[:8]} granted in {n} of its grants")
            other = holder.setdefault((node["id"], dev), a["id"])
            if other != a["id"]:
                bad.append(f"{a['name']} on {node['name']}: instance "
                           f"{dev[:8]} granted to {other} too")
    return bad


def check_feasible(fleet: List[dict], jobs: List[dict],
                   allocs: Dict[str, List[dict]]) -> List[str]:
    """Every placement sits on a node whose attributes satisfy the job."""
    by_id = {n["id"]: n for n in fleet}
    bad = []
    verdicts: Dict[tuple, List[str]] = {}
    for job in jobs:
        shape = _shape(job)
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


def _name_index(name: str) -> int:
    """The index of an allocation's name, `<job>.<group>[<index>]`; -1
    for a name of another form (check_committed reports it)."""
    m = re.search(r"\[(\d+)\]$", name)
    return int(m.group(1)) if m else -1


def check_rank(fleet: List[dict], backlog: Dict[str, Dict[str, float]],
               jobs: List[dict], allocs: Dict[str, List[dict]],
               lanes: int, decorrelation: Optional[dict] = None,
               residents: Optional["ResidentState"] = None
               ) -> Tuple[List[str], float, List[str]]:
    """The ranking, judged plan by plan in the order the store
    committed them (the allocs' create_index), each against the fleet
    as it stood before that plan: the resident backlog plus every plan
    committed earlier. Jobs whose score is node-local (bin-pack and
    the job's own anti-affinity; no spread, no affinity, no affinity of
    a device ask) are ranked; the others only add their usage. A node
    has room for a job that asks for devices where one more allocation
    fits by bin-pack AND its free matching instances hold one more
    grant of every ask (the instances granted replayed plan by plan from
    the stubs); where some node has two groups that satisfy one ask,
    which one an allocation took only its own grant says, and no device
    job is ranked.

    stacked   plans that put an alloc on a node which then holds the
              job twice or more (by this plan or an earlier one of the
              job) although the reference's greedy, over the share that
              ranked the plan, would stack none: a node that already
              holds the job scores at most (1 - 2/count)/2, and at
              least as many other nodes with room OF THAT SHARE as the
              plan asked for score above that.
    rank_gap  the widest gap by which a plan's worst chosen node scores
              below the bound of the share that ranked it, k being the
              plan's distinct nodes, and for a plan the applier committed
              in part k plus the placements it lost: the names below its
              last that neither it nor an earlier plan of the job holds
              (a later plan of the job places them; the scheduler names
              a plan's placements from the lowest free name on).

    Plans the store committed in one entry (one create_index) are
    replayed one at a time in the order of `jobs`; a node in which a
    plan of that entry replayed later leaves no room for this plan's
    job has no room for it either: the applier gave it to the other.

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
    asking = [j for j in jobs if j.get("devices")]
    devices = DeviceFleet(fleet) if asking else None
    granted = devices.instances * 0 if devices else None
    devices_ranked = devices is not None and all(
        devices.takes(j)[1].all() for j in asking)
    plans = []      # (create_index, job, rows and name indexes placed)
    for job in jobs:
        by_index: Dict[int, Tuple[List[int], List[int]]] = \
            collections.defaultdict(lambda: ([], []))
        for a in allocs.get(job["id"], []):
            if a["node_id"] in row:
                rows, names = by_index[int(a.get("create_index") or 0)]
                rows.append(row[a["node_id"]])
                names.append(_name_index(a["name"]))
        plans.extend((index, job, *placed)
                     for index, placed in by_index.items())
    plans.sort(key=lambda p: p[0])
    named: Dict[str, set] = collections.defaultdict(set)

    feasible: Dict[tuple, np.ndarray] = {}
    held: Dict[str, np.ndarray] = {}    # job id -> its allocs per node
    stacked, widest = [], []
    rank_gap = 0.0
    due = sorted(residents.commits) if residents is not None else []
    # what the plans of this plan's entry replayed after it take
    rest_used = np.zeros_like(used)
    rest_granted = granted * 0 if devices else None
    for at, (index, job, rows, names) in enumerate(plans):
        while due and due[0] < index:
            residents.apply(due.pop(0), row, used)
        ask = np.array([job["ask"][d] for d in DIMS], dtype=np.float64)
        chosen = np.bincount(rows, minlength=len(fleet))
        if at == 0 or plans[at - 1][0] != index:
            rest_used[:] = 0
            if devices:
                rest_granted[:] = 0
            nxt = at + 1
            while nxt < len(plans) and plans[nxt][0] == index:
                _i, other, other_rows, _n = plans[nxt]
                took = np.bincount(other_rows, minlength=len(fleet))
                rest_used += took[:, None] * np.array(
                    [other["ask"][d] for d in DIMS])[None, :]
                if other.get("devices"):
                    rest_granted += took[:, None] * devices.takes(other)[0]
                nxt += 1
        else:
            rest_used -= chosen[:, None] * ask[None, :]
            if job.get("devices"):
                rest_granted -= chosen[:, None] * devices.takes(job)[0]
        evicted = residents is not None and index in residents.evicting
        asks = job.get("devices") or []
        ranked = not asks or devices_ranked and not any(
            a["affinities"] for a in asks)
        if not job["spreads"] and not job["affinities"] and not evicted \
                and ranked:
            shape = _shape(job)
            if shape not in feasible:
                feasible[shape] = np.array(
                    [not node_feasible(n, job) for n in fleet])
            room = feasible[shape] & np.all(used + rest_used + ask
                                            <= capacity, axis=1)
            if asks:
                room &= devices.room(job, granted + rest_granted) >= 1
            score = binpack_scores(capacity, used, ask)
            before = held.get(job["id"])
            asked = job["count"]        # what this plan's select asked for
            if before is not None:      # a retry: the job's own allocs
                score = np.where(before > 0, (score - (before + 1.0)
                                              / job["count"]) / 2.0, score)
                asked -= int(before.sum())
            k = int((chosen > 0).sum())
            # a plan the applier committed in part ranked the placements
            # it lost too: the names below its last that neither it nor
            # an earlier plan of the job holds
            lost = len(set(range(max(names, default=0))) - set(names)
                       - named[job["id"]])
            depth = k + lost
            lane = None         # the lane that ranked this plan, if one did
            if lane_of is not None and k \
                    and asked >= decorrelation["min_count"] \
                    and bool((lane_of[rows] == lane_of[rows[0]]).all()):
                lane = int(lane_of[rows[0]])
            if lane is None:
                pool, where = room, "fleet"
                nth, share = lanes * depth, f"the {lanes}x{depth}-th best"
            else:
                pool, where = room & (lane_of == lane), f"lane {lane}"
                nth, share = depth, f"lane {lane}'s {depth}-th best"
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
        named[job["id"]].update(names)
        used += chosen[:, None] * ask[None, :]
        if asks:
            granted += chosen[:, None] * devices.takes(job)[0]
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
                    shape = _shape(job)
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


def device_sample(fleet: List[dict], jobs: List[dict],
                  allocs: Dict[str, List[dict]], n_nodes: int,
                  rng) -> List[str]:
    """Which allocs to read in full for the device check: every
    allocation of `jobs` on `n_nodes` nodes that hold device groups and
    allocations of the jobs, the fullest first (an eighth of them: where
    a conflict is likeliest), then nodes drawn from the seed."""
    if n_nodes <= 0:
        return []
    has = {n["id"] for n in fleet if n.get("devices")}
    by_node: Dict[str, List[str]] = collections.defaultdict(list)
    for job in jobs:
        for a in allocs.get(job["id"], []):
            if a["node_id"] in has:
                by_node[a["node_id"]].append(a["id"])
    nodes = sorted(by_node, key=lambda n: (-len(by_node[n]), n))
    head = nodes[:max(1, n_nodes // 8)]
    tail = nodes[len(head):]
    rng.shuffle(tail)
    return [a for nid in (head + tail)[:n_nodes] for a in by_node[nid]]


def judge(fleet: List[dict], backlog: Dict[str, Dict[str, float]],
          jobs: List[dict], evals: Dict[str, dict],
          allocs: Dict[str, List[dict]], full_allocs: List[dict],
          unread: List[str], port_range, lanes: int,
          decorrelation: Optional[dict] = None,
          residents: Optional["ResidentState"] = None,
          device_full: Optional[List[dict]] = None
          ) -> Tuple[dict, Dict[str, List[str]]]:
    """Every number compared, beside its limit, and what broke each.
    `lanes` is the configuration's number of concurrent schedulers,
    `decorrelation` its stated rule for their shares of the fleet.
    `residents` (a tiered configuration alone): the resident
    allocations as read back after the drain; `backlog` is then the
    fleet as loaded, the capacity check takes the residents that still
    run, and TIER_LIMITS' four numbers are compared too. A fleet with
    device groups: over_capacity takes their instances too, and
    `device_full`, the allocations read in full on the device check's
    sample of nodes (device_sample), is judged as DEVICE_LIMITS says."""
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
    if any(n.get("devices") for n in fleet):
        found["over_capacity"] += check_device_capacity(
            fleet, DeviceFleet(fleet), placed_jobs, allocs, device_full or [])
        found["device_conflicts"] = check_devices(fleet, placed_jobs,
                                                  device_full or [])
        limits.update(DEVICE_LIMITS)
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

def plain_grant(node: dict, job: dict, free: List[List[str]]
                ) -> Optional[List[Tuple[dict, List[str]]]]:
    """AssignDevice, plainly: per ask in order, the first group of the
    node that satisfies it with `count` free instances, and its first
    free ids, taken out of `free` (per group, the ids not granted, in
    the group's order); None, and `free` untouched, where an ask finds
    none."""
    trial = [list(ids) for ids in free]
    out = []
    for ask in job["devices"]:
        for j, group in enumerate(node.get("devices") or []):
            if len(trial[j]) >= ask["count"] and group_satisfies(group, ask):
                out.append((group, trial[j][:ask["count"]]))
                del trial[j][:ask["count"]]
                break
        else:
            return None
    free[:] = trial
    return out


def _first_groups(node: dict, asks: List[dict]) -> List[Optional[dict]]:
    """Per ask, the node's first group that satisfies it."""
    return [next((g for g in node.get("devices") or ()
                  if group_satisfies(g, ask)), None) for ask in asks]


class PlainScorer:
    """The reference scheduler's ranking in float64, one node at a time
    (rank.go BinPack / JobAntiAffinity / NodeAffinity, spread.go,
    ScoreNormalization: mean over the scorers that fired). Ties go to
    the lowest node id — the table's row order."""

    def __init__(self, fleet: List[dict], job: dict,
                 used: Dict[str, Dict[str, float]],
                 ignore_constraints: bool = False,
                 free: Optional[Dict[str, List[List[str]]]] = None,
                 ignore_devices: bool = False):
        self.job = job
        self.ask = job["ask"]
        self.nodes = [n for n in fleet if ignore_constraints
                      or not node_feasible(n, job, not ignore_devices)]
        # a device job: how many more of its allocations each node's free
        # instances grant (`free`: the scheduler's, per node and group),
        # and the `devices` scorer (rank.go: the matched weights of the
        # groups granted over every device affinity's weight)
        asks = job.get("devices") or []
        self.dev_room: Dict[str, float] = {}
        self.dev_score: Optional[Dict[str, float]] = None
        for n in self.nodes if asks else ():
            if ignore_devices and not devices_ok(n, job):
                self.dev_room[n["id"]] = math.inf
                continue
            trial = [list(ids) for ids in (free or {}).get(n["id"], [])]
            room = 0
            while plain_grant(n, job, trial) is not None:
                room += 1
            self.dev_room[n["id"]] = room
        sum_dw = sum(abs(w) for a in asks for *_c, w in a["affinities"])
        if sum_dw:
            self.dev_score = {n["id"]: sum(
                w for a, g in zip(asks, _first_groups(n, asks)) if g
                for *c, w in a["affinities"] if device_constraint_ok(g, c)
            ) / sum_dw for n in self.nodes}
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
        return all(u[d] + self.ask[d] <= node["capacity"][d] for d in DIMS) \
            and self.dev_room.get(node["id"], 1) >= 1

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
        if self.dev_score is not None:
            parts.append(self.dev_score[node["id"]])
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
        if node["id"] in self.dev_room:
            self.dev_room[node["id"]] -= 1
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
            "norank", "noevict", "evictpeer", "evictall", "evictearly",
            "devblind", "devtwice")


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

    A job that asks for devices is granted instance ids on its node:
    per ask, the first free ones of the first group that satisfies it
    (plain_grant), and a node has room for it only where they are
    there. Two more controls break that:

      devblind     picks nodes as if no group had to satisfy the asks (a
                   node whose groups can grant still has to have them
                   free); where nothing satisfies an ask it grants none
      devtwice     hands the first id a node granted to its next
                   allocation's first grant again
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
        # node id -> per device group, the ids not granted yet; and the
        # first id each node granted (devtwice's)
        self.free = {n["id"]: [list(g["ids"]) for g in n["devices"]]
                     for n in fleet if n.get("devices")}
        self.first_granted: Dict[str, str] = {}
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

    def _grant(self, node: dict, job: dict) -> List[dict]:
        """The device grants of one allocation on `node`, as a full
        allocation lists them; none where the node cannot grant."""
        grants = plain_grant(node, job, self.free.get(node["id"], [])) or []
        out = [{"vendor": g["vendor"], "type": g["type"], "name": g["model"],
                "device_ids": list(ids)} for g, ids in grants]
        if out:
            first = self.first_granted.setdefault(node["id"],
                                                  out[0]["device_ids"][0])
            if self.broken == "devtwice":
                out[0]["device_ids"][0] = first
        return out

    def submit(self, job: dict) -> None:
        scorer = PlainScorer(self.fleet, job, self.used,
                             ignore_constraints=self.broken == "constraints",
                             free=self.free,
                             ignore_devices=self.broken == "devblind")
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
            task = {"networks": [{"dynamic_ports": ports}]}
            if job.get("devices"):
                task["devices"] = self._grant(node, job)
            self.full[alloc_id] = dict(
                stub, allocated_resources={"tasks": {job["task"]: task}})
        if self.broken == "lose" and len(self.evals) % 3 == 0 and stubs:
            self.full.pop(stubs.pop()["id"])
        self.allocs[job["id"]] = stubs
        placed_all = placed == job["count"]
        self.evals[job["id"]] = {
            "status": "complete", "job_id": job["id"],
            "failed_tg_allocs": None if placed_all else {job["group"]: {}},
            "blocked_eval": ""}
