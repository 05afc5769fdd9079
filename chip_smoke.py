#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served scheduling path
still starts, places and commits on the accelerator.

One process, one command, from the root of a checkout:

    python3 chip_smoke.py [--seed N]

It boots Server + RpcServer + HTTPApiServer in-process exactly as
`nomad-tpu agent -server` does (default ServerConfig, gc_safepoints on,
a data_dir so raft/WAL and the ingest path are live), registers a
10,000-node fleet through Server.register_node, loads a resident backlog
of 400,000 running allocs with the replay loader the C2M bench uses,
then drives a few requests over HTTP from a client thread: one batch job
of 10,000 instances and 16 service jobs of 10 instances carrying a
spread, an affinity and `=`/`regexp` constraints — twice. Every alloc is
read back over HTTP and checked against a plain per-node reference that
imports nothing from nomad_tpu/ops. Between the waves one job of each
shape is processed alone on the quiesced server with every kernel arm
pinned to the chip and compared, by node name, with the scan arm pinned
to the host CPU backend; a disagreement is classified from the plain
reference's scores, never tolerated silently. The second wave is the
warm path: it may compile a plan retry's smaller count bucket, but must
meet no new shape family and no XLA compile that a new trace signature
does not explain.

Exit status 0 and a last stdout line
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
(exactly those keys, the device as JAX reports it) mean every phase
passed ON AN ACCELERATOR. The evidence — arms, waves, routing, counters,
problems — is the stdout line before it, {"report": {...}}, and
<--out>/chip_smoke.json. With no accelerator (JAX reports platform
"cpu") the script prints no result and exits 4; there is no fallback.
`--rehearse-cpu` is an explicit small CPU rehearsal for debugging the
script itself: its lines say platform "cpu", the report says
"rehearsal": true, and it asserts nothing about the device.

Wall-clock figures in the output are set-up information (they include
compilation and a cold host); the smoke claims no rate.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import heapq
import json
import logging
import math
import os
import random
import re
import shutil
import sys
import tempfile
import threading
import time
import traceback
import uuid
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------
# Scenario: plain data from a seed (no jax, no nomad_tpu at import)
# ---------------------------------------------------------------------

N_NODES = 10_000            # reference README "10K+ nodes in production"
ALLOCS_PER_NODE = 40        # the C2M density (BASELINE config #5)
BATCH_COUNT = 10_000        # BASELINE config #2 shape
N_SERVICE = 16              # BASELINE config #3 shape, count=10 each
SERVICE_COUNT = 10
N_DCS, N_RACKS = 4, 16
DCS = [f"dc{d + 1}" for d in range(N_DCS)]
DIMS = ("cpu", "memory_mb", "disk_mb", "mbits")
# mock.node(): node resources minus its reservation
NODE_CAPACITY = {"cpu": 4000 - 100, "memory_mb": 8192 - 256,
                 "disk_mb": 100 * 1024 - 4 * 1024, "mbits": 1000}
# the flyweight resources row bench.ladder.seed_c2m_allocs loads
BACKLOG_ALLOC = {"cpu": 50, "memory_mb": 64, "disk_mb": 10, "mbits": 0}
DYNAMIC_PORT_RANGE = (20000, 32000)
# plain-reference scores closer than this are one f32 tie: the kernels
# score in float32 and the chip's pow differs from the host's by tens
# of ulps (measured, PR 21), i.e. ~1e-5 on a score of order one
TIE_EPS = 1e-5
TIME_LIMIT_S = 1150         # the contract allows 1200


def build_fleet(seed: int, n_nodes: int = N_NODES) -> List[dict]:
    """The fleet as plain dicts: 4 datacenters x 16 racks over the
    attribute/meta vocabulary bench.ladder._seed_nodes uses, node ids
    pinned from the seed (row order and argmax tie-breaks follow ids).
    Returned sorted by id — the server's table row order."""
    rng = random.Random(seed)
    fleet = []
    for i in range(n_nodes):
        fleet.append({
            "id": str(uuid.UUID(int=rng.getrandbits(128), version=4)),
            "name": f"node-{i}",
            "datacenter": DCS[i % N_DCS],
            "attributes": {"kernel.name": "linux", "arch": "x86",
                           "nomad.version": "0.5.0", "driver.exec": "1",
                           "driver.mock_driver": "1"},
            "meta": {"pci-dss": "true", "database": "mysql",
                     "version": "5.6",
                     "rack": f"r{(i // N_DCS) % N_RACKS}"},
            "drivers": ["exec", "mock_driver"],
            "capacity": dict(NODE_CAPACITY),
        })
    fleet.sort(key=lambda n: n["id"])
    return fleet


def build_jobs(tag: str, batch_count: int = BATCH_COUNT,
               n_service: int = N_SERVICE) -> List[dict]:
    """One wave as plain job specs: a big batch job (node-local scoring
    -> K-way arm + native stream merge) and `n_service` small service
    jobs whose spread/affinity/constraints take the scan arm, compiled
    feasibility and the parked device mask."""
    jobs = [{
        "id": f"smoke-{tag}-batch", "type": "batch", "group": "worker",
        "task": "worker", "driver": "mock_driver", "count": batch_count,
        "ask": {"cpu": 100, "memory_mb": 100, "disk_mb": 150, "mbits": 50},
        "dynamic_ports": 0, "datacenters": list(DCS),
        "constraints": [], "affinities": [], "spreads": [],
    }]
    for i in range(n_service):
        jobs.append({
            "id": f"smoke-{tag}-svc-{i:02d}", "type": "service",
            "group": "web", "task": "web", "driver": "exec",
            "count": SERVICE_COUNT,
            "ask": {"cpu": 500, "memory_mb": 256, "disk_mb": 150,
                    "mbits": 50},
            "dynamic_ports": 2, "datacenters": list(DCS),
            "constraints": [("${attr.kernel.name}", "=", "linux"),
                            ("${meta.rack}", "regexp", "^r[0-9]$")],
            "affinities": [("${meta.rack}", "=", "r3", 50)],
            "spreads": [("${node.datacenter}", 50,
                         [("dc1", 40), ("dc2", 30)])],
        })
    return jobs


# ---------------------------------------------------------------------
# Plain reference: per-node Python, nothing from nomad_tpu/ops
# ---------------------------------------------------------------------

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
        raise ValueError(f"target {target!r} is outside the smoke's "
                         f"vocabulary")
    return val, val is not None


def constraint_ok(node: dict, constraint: Tuple[str, str, str]) -> bool:
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
    raise ValueError(f"operand {operand!r} is outside the smoke's "
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


def check_committed(jobs: List[dict], allocs: Dict[str, List[dict]],
                    evals: Dict[str, dict]) -> List[str]:
    """Every asked allocation committed exactly once, nothing failed."""
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
        ev = evals.get(job["id"])
        if ev is None or ev.get("status") != "complete":
            bad.append(f"{job['id']}: eval "
                       f"{(ev or {}).get('status', 'missing')}")
        elif ev.get("failed_tg_allocs") or ev.get("blocked_eval"):
            bad.append(f"{job['id']}: failed_tg_allocs="
                       f"{ev.get('failed_tg_allocs')} blocked="
                       f"{ev.get('blocked_eval')}")
    return bad


def node_usage(fleet: List[dict], backlog_per_node: int,
               jobs: List[dict], allocs: Dict[str, List[dict]]
               ) -> Dict[str, Dict[str, float]]:
    """Per-node committed usage: the resident backlog plus every alloc
    of `jobs`, from the jobs' asks."""
    used = {n["id"]: {d: backlog_per_node * BACKLOG_ALLOC[d]
                      for d in DIMS} for n in fleet}
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
    for job in jobs:
        for a in allocs.get(job["id"], []):
            node = by_id.get(a["node_id"])
            if node is None:
                bad.append(f"{a['name']}: unknown node {a['node_id']}")
                continue
            why = node_feasible(node, job)
            if why:
                bad.append(f"{a['name']} on {node['name']}: {why}")
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


def check_ports(full_allocs: List[dict]) -> List[str]:
    """Dynamic ports: inside the dynamic range, unique per node."""
    bad = []
    taken: Dict[str, set] = collections.defaultdict(set)
    lo, hi = DYNAMIC_PORT_RANGE
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


class PlainScorer:
    """The reference scheduler's ranking in float64, one node at a time
    (rank.go BinPack / JobAntiAffinity / NodeAffinity, spread.go,
    ScoreNormalization: mean over the scorers that fired). Ties go to
    the lowest node id — the table's row order."""

    def __init__(self, fleet: List[dict], job: dict,
                 used: Dict[str, Dict[str, float]]):
        self.job = job
        self.ask = job["ask"]
        self.nodes = [n for n in fleet if not node_feasible(n, job)]
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

    def greedy(self, count: int) -> List[Optional[str]]:
        """The node name of each of `count` greedy placements."""
        out: List[Optional[str]] = []
        if self.spreads:
            # spread couples the nodes: rescore all of them per step
            for _ in range(count):
                best, best_s = None, -math.inf
                for n in self.nodes:       # id order: first max wins
                    if self.fits(n):
                        s = self.score(n)
                        if s > best_s:
                            best, best_s = n, s
                if best is not None:
                    self.place(best)
                out.append(best["name"] if best else None)
            return out
        # node-local scoring: a heap of (−score, row) is exact greedy
        heap = [(-self.score(n), i) for i, n in enumerate(self.nodes)
                if self.fits(n)]
        heapq.heapify(heap)
        for _ in range(count):
            if not heap:
                out.append(None)
                continue
            _s, i = heapq.heappop(heap)
            n = self.nodes[i]
            self.place(n)
            out.append(n["name"])
            if self.fits(n):
                heapq.heappush(heap, (-self.score(n), i))
        return out


def classify_sequences(arm: str, got: List[Optional[str]],
                       want: List[Optional[str]],
                       scorer_factory) -> dict:
    """Compare one arm's node names with the oracle's, step by step.
    On a disagreement, replay the common prefix on the plain reference
    and score the two nodes at the first divergent step: closer than
    TIE_EPS is an f32 tie, anything else a real disagreement."""
    n = min(len(got), len(want))
    diff = [i for i in range(n) if got[i] != want[i]]
    out = {"arm": arm, "steps": n,
           "length_mismatch": len(got) != len(want),
           "step_mismatches": len(diff),
           "multiset_mismatches": sum(
               (collections.Counter(got) - collections.Counter(want))
               .values())}
    if diff:
        first = diff[0]
        scorer = scorer_factory()
        by_name = {nd["name"]: nd for nd in scorer.nodes}
        for name in want[:first]:
            if name is not None:
                scorer.place(by_name[name])
        a, b = by_name.get(got[first]), by_name.get(want[first])
        if a is None or b is None:
            out.update(first_divergence=first, tie=False,
                       why="a side chose no feasible node")
        else:
            delta = abs(scorer.score(a) - scorer.score(b))
            out.update(first_divergence=first, plain_score_delta=delta,
                       tie=bool(delta <= TIE_EPS and scorer.fits(a)
                                and scorer.fits(b)))
    return out


# ---------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------

class SmokeFailure(Exception):
    pass


def _log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _model_node(plain: dict):
    from nomad_tpu.mock import fixtures as mock
    node = mock.node()
    node.id = plain["id"]
    node.name = plain["name"]
    node.datacenter = plain["datacenter"]
    node.attributes = dict(plain["attributes"])
    node.meta = dict(plain["meta"])
    node.compute_class()
    return node


def _model_job(plain: dict):
    from nomad_tpu.mock import fixtures as mock
    from nomad_tpu.models import (Affinity, Constraint, Spread,
                                  SpreadTarget)
    job = mock.batch_job() if plain["type"] == "batch" else mock.job()
    job.id = plain["id"]
    job.datacenters = list(plain["datacenters"])
    job.constraints = []
    tg = job.task_groups[0]
    tg.count = plain["count"]
    tg.constraints = [Constraint(ltarget=l, rtarget=r, operand=op)
                      for l, op, r in plain["constraints"]]
    tg.affinities = [Affinity(ltarget=l, rtarget=r, operand=op, weight=w)
                     for l, op, r, w in plain["affinities"]]
    tg.spreads = [Spread(attribute=a, weight=w,
                         spread_target=[SpreadTarget(v, p) for v, p in t])
                  for a, w, t in plain["spreads"]]
    # the mock shapes ARE the asks (group "worker": 100/100/150 + 50
    # mbits; group "web": 500/256/150 + 50 mbits + 2 dynamic ports)
    task = tg.tasks[0]
    got = {"cpu": task.resources.cpu, "memory_mb": task.resources.memory_mb,
           "disk_mb": tg.ephemeral_disk.size_mb,
           "mbits": sum(nw.mbits for nw in task.resources.networks)}
    ports = sum(len(nw.dynamic_ports) for nw in task.resources.networks)
    if (got, ports, tg.name, task.driver) != (
            plain["ask"], plain["dynamic_ports"], plain["group"],
            plain["driver"]):
        raise SmokeFailure(f"mock job drifted from the scenario: {got}")
    return job


class _ReplayIndex:
    """What seed_c2m_allocs needs of a harness: the store, and raft
    indices drawn from the server's own counter (a replay, like a
    snapshot restore, writes the store under fresh indices)."""

    def __init__(self, srv):
        self.store = srv.store
        self._srv = srv

    def next_index(self) -> int:
        with self._srv._raft_l:
            self._srv._raft_index += 1
            return self._srv._raft_index


class CompileCounter:
    """XLA backend compiles as JAX itself reports them (jax.monitoring),
    independent of the repo's trace-signature bookkeeping."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def _shape_family(signature: tuple) -> tuple:
    """A kernel trace signature without its count bucket. The optimistic
    plan applier may commit a plan partially, and the retry asks for the
    remaining count: the same shape family in a smaller step bucket — a
    fresh compile the first attempt's shape never causes."""
    return tuple(x for x in signature
                 if not (isinstance(x, tuple) and len(x) == 2
                         and x[0] in ("k_steps", "max_steps", "k_out")))


def new_shape_families(before: Dict[str, set],
                       after: Dict[str, set]) -> List[str]:
    """Placement-kernel signatures in `after` whose shape family was not
    in `before`: a different pad, lane width, backend or scorer set —
    what a warm wave of the same jobs must never meet. (Table and mask
    scatters carry no statics and are bucketed by rows touched.)"""
    out = []
    for kernel, sigs in after.items():
        known = {_shape_family(s) for s in before.get(kernel, ())}
        for sig in sigs - before.get(kernel, set()):
            has_statics = any(isinstance(x, tuple) and x
                              and isinstance(x[0], str) for x in sig)
            if has_statics and _shape_family(sig) not in known:
                out.append(f"{kernel}{sig}")
    return sorted(out)


def _arm_delta(before: dict, after: dict) -> dict:
    out = {}
    for key in ("dispatches", "compiles", "dispatch_s"):
        d = {arm: after[key][arm] - before[key].get(arm, 0)
             for arm in after[key]}
        out[key] = {a: (round(v, 3) if key == "dispatch_s" else v)
                    for a, v in d.items() if v}
    return out


class Smoke:
    def __init__(self, args, device: dict):
        self.args = args
        self.device = device
        self.on_chip = device["platform"] != "cpu"
        self.report: dict = {"setup_seconds": {}, "phases": {}}
        self.problems: List[str] = []
        self.fleet = build_fleet(args.seed, args.nodes)
        self.by_name = {n["name"]: n for n in self.fleet}
        self.jobs_done: List[dict] = []
        self.allocs: Dict[str, List[dict]] = {}
        self.full_allocs: Dict[str, dict] = {}     # port-carrying allocs
        self.evals: Dict[str, dict] = {}
        self.data_dir = tempfile.mkdtemp(prefix="nomad-tpu-smoke-")
        self.srv = self.rpc = self.api = self.client = None

    # -- bookkeeping ---------------------------------------------------
    def record(self, phase: str, problems: List[str]) -> None:
        self.report["phases"][phase] = "ok" if not problems else "FAILED"
        for p in problems[:20]:
            _log(f"{phase}: {p}")
        if problems:
            self.problems.append(f"{phase}: {len(problems)} problem(s), "
                                 f"first: {problems[0]}")

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = round(time.perf_counter() - t0, 2)
            self.report["setup_seconds"][name] = dt
            _log(f"{name}: {dt}s (set-up information, not a rate)")

    # -- phases --------------------------------------------------------
    def boot(self) -> None:
        """Server + RpcServer + HTTPApiServer as cmd_agent wires them.
        One deployment setting differs from the agent's defaults: no
        client agents exist to heartbeat for the fleet, so the node TTL
        is long (the C2M bench does the same)."""
        from nomad_tpu.api import HTTPApiServer
        from nomad_tpu.api.client import ApiClient
        from nomad_tpu.rpc import RpcServer
        from nomad_tpu.server import Server, ServerConfig
        with self.timed("boot"):
            self.srv = Server(ServerConfig(
                num_schedulers=2, gc_safepoints=True,
                data_dir=self.data_dir, heartbeat_ttl_s=3600.0))
            self.rpc = RpcServer(self.srv, port=0)
            self.srv.rpc_server = self.rpc
            self.srv.start()
            self.rpc.start()
            self.api = HTTPApiServer(self.srv, port=0)
            self.api.start()
            self.client = ApiClient(f"http://127.0.0.1:{self.api.port}")
        leader = self.client._request("GET", "/v1/status/leader")
        _log(f"agent up: http :{self.api.port} rpc {self.rpc.addr} "
             f"leader {leader!r}")

    def seed(self) -> None:
        from nomad_tpu.bench.ladder import seed_c2m_allocs
        srv = self.srv
        with self.timed("seed_nodes"):
            nodes = [_model_node(p) for p in self.fleet]
            for node in nodes:
                srv.register_node(node)
        threads = threading.active_count()
        self.report["threads_after_node_register"] = threads
        n_allocs = self.args.nodes * self.args.allocs_per_node
        with self.timed("seed_backlog"):
            seed_c2m_allocs(_ReplayIndex(srv), nodes, n_allocs,
                            sched_allocs=0)
        with self.timed("table_build"):
            table = srv.store.snapshot().node_table()
        from nomad_tpu.ops.select import _pad_n
        self.report["fleet"] = {
            "nodes": srv.store.node_count(),
            "resident_allocs": sum(
                1 for _ in srv.store.allocs_by_job("default", "c2m-seed")),
            "n_pad": _pad_n(table.n),
            "heartbeat_timers_armed": srv._heartbeats.armed(),
        }
        # the backlog the capacity check assumes, read back over HTTP
        probe = self.fleet[len(self.fleet) // 2]
        on_node = self.client.node_allocations(probe["id"])
        one = self.client.get_allocation(on_node[0]["id"])
        task = next(iter(one["allocated_resources"]["tasks"].values()))
        row = {"cpu": task["cpu"]["cpu_shares"],
               "memory_mb": task["memory"]["memory_mb"],
               "disk_mb": one["allocated_resources"]["shared"]["disk_mb"],
               "mbits": 0}
        problems = []
        if table.ids != [n["id"] for n in self.fleet]:
            problems.append("table rows are not the fleet in id order")
        if len(on_node) != self.args.allocs_per_node or row != BACKLOG_ALLOC:
            problems.append(f"backlog on {probe['name']}: {len(on_node)} "
                            f"allocs of {row}")
        if self.report["fleet"]["nodes"] != self.args.nodes or \
                self.report["fleet"]["resident_allocs"] != n_allocs:
            problems.append(f"fleet is {self.report['fleet']}")
        self.record("seed", problems)

    def wave(self, tag: str) -> dict:
        """Register one wave over HTTP (bulk PUT /v1/jobs), wait from
        the client's side for every eval, read every alloc back."""
        from nomad_tpu.ops.select import device_stats_snapshot
        from nomad_tpu.utils.codec import to_wire
        from nomad_tpu.analysis.sanitizer import traces
        jobs = build_jobs(tag, self.args.batch_count, self.args.services)
        before = device_stats_snapshot()
        sigs0, xla0 = traces.signatures(), self.xla.count
        with self.timed(f"wave_{tag}"):
            results = self.client.register_jobs_bulk(
                [to_wire(_model_job(j)) for j in jobs])
            errors = [r for r in results if "Error" in r]
            if errors:
                raise SmokeFailure(f"bulk register: {errors[:3]}")
            self._await_and_read(
                jobs, [r["EvalID"] for r in results], timeout_s=600)
        delta = _arm_delta(before, device_stats_snapshot())
        # every jitted program (arms and table/mask scatters) reports a
        # trace signature the first time it meets a shape; XLA's own
        # compile count beside it shows a recompile of a KNOWN shape,
        # which the signatures cannot
        sigs1 = traces.signatures()
        delta["new_trace_signatures"] = {
            k: len(v - sigs0.get(k, set())) for k, v in sigs1.items()
            if v - sigs0.get(k, set())}
        delta["new_shape_families"] = new_shape_families(sigs0, sigs1)
        delta["xla_backend_compiles"] = self.xla.count - xla0
        self.jobs_done.extend(jobs)
        return delta

    def _await_and_read(self, jobs, eval_ids, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        pending = dict(zip((j["id"] for j in jobs), eval_ids))
        while pending:
            for job_id, eval_id in list(pending.items()):
                ev = self.client.get_evaluation(eval_id)
                if ev["status"] in ("complete", "failed", "canceled"):
                    self.evals[job_id] = ev
                    del pending[job_id]
            if pending and time.monotonic() > deadline:
                raise SmokeFailure(f"evals not complete after "
                                   f"{timeout_s}s: {sorted(pending)}")
            if pending:
                time.sleep(0.05)
        for job in jobs:
            self.allocs[job["id"]] = self.client.job_allocations(job["id"])

    def check_reference(self, phase: str) -> None:
        """The plain reference over everything committed so far."""
        used = node_usage(self.fleet, self.args.allocs_per_node,
                          self.jobs_done, self.allocs)
        for job in self.jobs_done:
            if job["dynamic_ports"]:
                for a in self.allocs.get(job["id"], []):
                    if a["id"] not in self.full_allocs:
                        self.full_allocs[a["id"]] = \
                            self.client.get_allocation(a["id"])
        self.record(phase,
                  check_committed(self.jobs_done, self.allocs, self.evals)
                  + check_capacity(self.fleet, used)
                  + check_feasible(self.fleet, self.jobs_done, self.allocs)
                  + check_spread(self.fleet, self.jobs_done, self.allocs)
                  + check_ports(list(self.full_allocs.values())))

    # -- chip against host ---------------------------------------------
    def pinned_arms(self) -> None:
        """One job of each shape, alone on the quiesced server, through
        the worker's own eval path with a dispatch pinned to the
        accelerator; then every other arm the captured request can take,
        pinned likewise, each compared by node name with the scan arm
        pinned to the host CPU backend."""
        import jax
        import nomad_tpu.ops.select as sel
        from nomad_tpu.utils.codec import to_wire
        srv = self.srv
        cpu_dev = jax.local_devices(backend="cpu")[0]
        accel = sel.SelectKernel(backend="accel")
        results: List[dict] = []

        def scan_pinned(req, dev):
            """The repo's parity oracle (tests/test_chunked_kernel.py):
            the scan kernel called directly, here with its inputs
            placed on `dev` (None = the default device, the chip)."""
            n_pad = sel._pad_n(len(req.feasible))
            k = sel._bucket_k(max(req.count, 1))
            args, statics = sel.pack_request(req, n_pad)
            args = sel.SelectKernel._place_args(args, dev)
            _carry, outs = sel._select_scan(**args, k_steps=k, **statics)
            return sel.unpack_result(req, outs)

        def names(res):
            # table rows are the fleet in id order (checked in seed())
            return [self.fleet[i]["name"] if i >= 0 else None
                    for i in res.node_idx.tolist()]

        def copy(req, **kw):
            return dataclasses.replace(req, **kw)

        def measure(label, run):
            """Run one call; report which arm(s) it dispatched."""
            before = sel.device_stats_snapshot()
            xla0 = self.xla.count
            t0 = time.perf_counter()
            outs = run()
            wall = time.perf_counter() - t0
            delta = _arm_delta(before, sel.device_stats_snapshot())
            return outs, {
                "call": label,
                "arm": "+".join(sorted(delta["dispatches"])) or label,
                "xla_compiles": self.xla.count - xla0,
                "wall_s": round(wall, 2)}

        def solo(req, c):
            return lambda: [accel.select(copy(req, count=c))]

        def pair(req, c):
            return lambda: accel.select_many(
                [copy(req, count=c), copy(req, count=c)])

        def compare(job, req, first, used_before):
            """Every other arm the captured request can take, and the
            oracles, against what the eval was served (`first`)."""
            def scorer():
                return PlainScorer(self.fleet, job, used_before)
            t0 = time.perf_counter()
            host = names(scan_pinned(copy(req), cpu_dev))
            host_s = time.perf_counter() - t0
            chunk_ok = not (req.spreads or req.distinct_props
                            or req.distinct_hosts or req.scan_exclusive)
            # greedy is prefix-consistent: the first c placements of a
            # request ARE the request with count=c, so a prefix has the
            # same oracle
            calls = [first + (req.count,),
                     measure("select_many", pair(req, req.count))
                     + (req.count,)]
            if chunk_ok and req.count > 512:
                # count <= 512 is the shape the chunked arms take; the
                # scan arm takes any request
                calls += [
                    measure("select[:512]", solo(req, 512)) + (512,),
                    measure("select_many[:512]", pair(req, 512)) + (512,),
                    measure("scan", lambda: [scan_pinned(copy(req), None)])
                    + (req.count,)]
            for outs, meta, count in calls:
                for lane, res in enumerate(outs):
                    row = classify_sequences(meta["arm"], names(res),
                                             host[:count], scorer)
                    row.update(meta, job=job["id"], lane=lane,
                               placed=int(res.placed))
                    results.append(row)
            # right vs wrong, apart from chip vs host: the oracle itself
            # against the plain float64 greedy
            row = classify_sequences("host-scan-vs-plain", host,
                                     scorer().greedy(req.count), scorer)
            row.update(job=job["id"], call="oracle", lane=0,
                       placed=sum(1 for x in host if x is not None),
                       xla_compiles=0, wall_s=round(host_s, 2))
            results.append(row)

        for w in srv.workers:
            w.set_pause(True)
        time.sleep(1.0)         # let a dequeue in flight time out
        try:
            wave = build_jobs("pin", self.args.batch_count, 1)
            for job in (wave[1], wave[0]):       # service, then batch
                used_before = node_usage(
                    self.fleet, self.args.allocs_per_node,
                    self.jobs_done, self.allocs)
                captured = []

                def dispatch(req):
                    # inside the eval only the arm the eval is served
                    # from: the broker redelivers an eval that is not
                    # acked within its 60 s unack timer
                    first = measure("select", solo(req, req.count))
                    captured.append((req, first))
                    return first[0][0]

                with self.timed(f"pinned_{job['type']}"):
                    resp = self.client.register_job(
                        to_wire(_model_job(job)))
                    ev, token = srv.eval_broker.dequeue(
                        list(srv.config.enabled_schedulers), 10.0)
                    if ev is None or ev.id != resp["EvalID"]:
                        raise SmokeFailure(
                            f"quiesced broker handed out {ev!r}")
                    srv.workers[0].process_eval(ev, token,
                                                dispatch=dispatch)
                    self._await_and_read([job], [ev.id], timeout_s=120)
                    for req, first in captured:
                        compare(job, req, first, used_before)
                self.jobs_done.append(job)
        finally:
            for w in srv.workers:
                w.set_pause(False)

        self.report["pinned"] = results
        problems = []
        arms_run = {a for r in results for a in r["arm"].split("+")}
        if self.on_chip:
            host_arms = sorted(a for a in arms_run if a.endswith("@cpu"))
            if host_arms:
                problems.append(f"pinned arms ran on the host: {host_arms}")
        for r in results:
            if r["length_mismatch"] or r["placed"] != r["steps"]:
                problems.append(f"{r['arm']} {r['job']}: placed "
                                f"{r['placed']} of {r['steps']}")
            if r["step_mismatches"] and not r.get("tie"):
                problems.append(
                    f"{r['arm']} {r['job']}: {r['step_mismatches']} steps "
                    f"differ from the oracle, first at "
                    f"{r.get('first_divergence')} with plain score delta "
                    f"{r.get('plain_score_delta', r.get('why'))}")
        self.report["pinned_arms_run"] = sorted(arms_run)
        self.report["pinned_disagreements"] = {
            "rows": sum(1 for r in results if r["step_mismatches"]),
            "steps": sum(r["step_mismatches"] for r in results),
            "multiset": sum(r["multiset_mismatches"] for r in results),
            "all_f32_ties": all(r.get("tie", True) for r in results),
        }
        self.record("pinned_arms", problems)

    # -- evidence ------------------------------------------------------
    def evidence(self, waves: Dict[str, dict]) -> None:
        from nomad_tpu import native
        from nomad_tpu.ops.select import (device_hbm_bytes,
                                          device_stats_snapshot,
                                          mesh_stats_snapshot)
        from nomad_tpu.scheduler import feasible_compiler
        from nomad_tpu.utils import metrics
        from nomad_tpu.utils.platform import compile_cache_entries
        srv = self.srv
        rep = self.report
        dev = device_stats_snapshot()
        rep["arms"] = {
            "accelerator": {a: {"dispatches": dev["dispatches"][a],
                                "seconds_setup_info": dev["dispatch_s"][a],
                                "fresh_compiles": dev["compiles"][a]}
                            for a in dev["dispatches"]
                            if not a.endswith("@cpu")},
            "host_cpu": {a: {"dispatches": dev["dispatches"][a],
                             "seconds_setup_info": dev["dispatch_s"][a],
                             "fresh_compiles": dev["compiles"][a]}
                         for a in dev["dispatches"] if a.endswith("@cpu")},
        }
        rep["waves"] = waves
        rep["routing"] = dev["routing"]
        rep["device_op_failures"] = dev["device_op_failures"]
        rep["device_hbm_bytes"] = device_hbm_bytes()
        rep["pad_waste_ratio"] = dev["pad_waste_ratio"]
        rep["resident_table"] = srv.store.table_cache.device.snapshot()
        rep["mask_store"] = srv.store.table_cache.device.feas.snapshot()
        fc = feasible_compiler.stats()
        rep["feasibility"] = {k: fc[k] for k in (
            "mask_hits", "mask_builds", "recompiles", "fallbacks",
            "token_survivals", "token_invalidations", "residue_rows")}
        rep["select_counters"] = {
            k: v for k, v in metrics.counter_totals().items()
            if k.startswith("nomad.select.")}
        rep["gateway"] = dict(srv.gateway.stats) if srv.gateway else None
        rep["ingest"] = (dict(srv.ingest.stats)
                         if getattr(srv, "ingest", None) else None)
        rep["mesh"] = mesh_stats_snapshot()
        rep["native"] = {"codec": native.load_codec() is not None}
        rep["xla_backend_compiles"] = {"count": self.xla.count,
                                       "seconds_setup_info":
                                       round(self.xla.seconds, 1)}
        rep["compile_cache"]["entries_after"] = compile_cache_entries()
        rep["worker_failed_evals"] = sum(
            w.stats["failed"] for w in srv.workers)

        problems = []
        w1, w2 = waves["w1"], waves["w2"]
        served = collections.Counter(w1["dispatches"])
        served.update(w2["dispatches"])
        on_accel = sum(v for a, v in served.items()
                       if not a.endswith("@cpu"))
        if self.on_chip and on_accel < 1:
            problems.append(
                f"no placement dispatch of the served waves ran on the "
                f"accelerator under the default router: {dict(served)}; "
                f"routing {dev['routing']}")
        # w2["compiles"] counts fresh compiles per arm; one there is a
        # plan retry's smaller count bucket unless it is ALSO listed as
        # a new shape family, which a warm wave must never meet
        if w2["new_shape_families"]:
            problems.append(f"second wave met new shape families: "
                            f"{w2['new_shape_families']}")
        if w2["xla_backend_compiles"] > \
                sum(w2["new_trace_signatures"].values()):
            problems.append(
                f"second wave recompiled a known shape: XLA compiled "
                f"{w2['xla_backend_compiles']} programs for new "
                f"signatures {w2['new_trace_signatures']}")
        if dev["device_op_failures"]:
            problems.append(f"device ops failed into a host path: "
                            f"{dev['device_op_failures']}")
        if rep["worker_failed_evals"]:
            problems.append(f"{rep['worker_failed_evals']} evals failed "
                            f"in a worker and were redelivered")
        if not all(rep["native"].values()):
            problems.append(f"native modules not loaded: {rep['native']}")
        if self.on_chip and rep["device_hbm_bytes"] <= 0:
            problems.append("device_hbm_bytes() reports nothing in use")
        mesh = rep["mesh"]
        n_dev = self.device["device_count"]
        if self.on_chip and bool(mesh) != (n_dev > 1):
            problems.append(f"{n_dev} accelerator device(s) but mesh "
                            f"routing is {'on' if mesh else 'off'}")
        if mesh:
            per_dev = self._resident_bytes_by_device()
            mesh["resident_bytes_by_device"] = per_dev
            rep["route"] = "mesh"
            if mesh.get("devices") != n_dev \
                    or mesh.get("reshard_uploads", 0) < 1 \
                    or mesh.get("resident_hits", 0) <= 0 \
                    or len(set(per_dev.values())) != 1 \
                    or len(per_dev) != n_dev:
                problems.append(f"mesh route evidence incomplete: {mesh}")
        else:
            rep["route"] = "single-device"
            hits = rep["select_counters"].get(
                "nomad.select.resident_dispatch", 0)
            if hits <= 0 or rep["resident_table"]["uploads"] < 1:
                problems.append(
                    f"resident table never served a dispatch: "
                    f"{rep['resident_table']} {rep['select_counters']}")
        self.record("evidence", problems)

    def _resident_bytes_by_device(self) -> Dict[str, int]:
        """Bytes of the mesh-resident columns on each device, from the
        shards themselves (the gauge divides a total by the count)."""
        from nomad_tpu.ops.select import get_shared_sharded
        st = get_shared_sharded().resident._state
        out: Dict[str, int] = collections.Counter()
        for arr in (st.capacity, st.used, st.free_ports):
            for shard in arr.addressable_shards:
                out[str(shard.device)] += int(shard.data.nbytes)
        return dict(out)

    # -- lifecycle -----------------------------------------------------
    def run(self) -> None:
        from nomad_tpu.utils.platform import compile_cache_entries
        import jax
        self.xla = CompileCounter()
        self.report["compile_cache"] = {
            "dir": jax.config.jax_compilation_cache_dir,
            "placed_by": ("JAX_COMPILATION_CACHE_DIR"
                          if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          else "utils.platform (fixed in-checkout path)"),
            "entries_before": compile_cache_entries()}
        self.boot()
        self.seed()
        waves = {"w1": self.wave("w1")}
        self.check_reference("reference_w1")
        self.pinned_arms()
        waves["w2"] = self.wave("w2")
        self.check_reference("reference_all")
        self.evidence(waves)

    def close(self) -> None:
        for part in (self.api, self.rpc, self.srv):
            if part is not None:
                try:
                    part.shutdown()
                except Exception:
                    traceback.print_exc()
        shutil.rmtree(self.data_dir, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--out", default="chiprun_out",
                    help="directory for the full report")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="explicit CPU rehearsal of the script itself")
    ap.add_argument("--nodes", type=int, default=N_NODES)
    ap.add_argument("--allocs-per-node", type=int, default=ALLOCS_PER_NODE)
    ap.add_argument("--batch-count", type=int, default=BATCH_COUNT)
    ap.add_argument("--services", type=int, default=N_SERVICE)
    args = ap.parse_args(argv)

    # a run that hangs would hold the chip: hard stop inside the limit
    # (exit codes 2 and 3 are left to the chip tool's own meanings)
    watchdog = threading.Timer(TIME_LIMIT_S, lambda: os._exit(5))
    watchdog.daemon = True
    watchdog.start()
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    t_start = time.perf_counter()

    from nomad_tpu.utils.platform import init_backend
    device = init_backend()
    _log(f"backend: {device}")
    if device["platform"] == "cpu" and not args.rehearse_cpu:
        print("chip_smoke: JAX found no accelerator (platform 'cpu'); "
              "this smoke has no CPU fallback", file=sys.stderr)
        return 4
    if device["platform"] != "cpu" and args.rehearse_cpu:
        print("chip_smoke: --rehearse-cpu on an accelerator",
              file=sys.stderr)
        return 4

    smoke = Smoke(args, device)
    try:
        smoke.run()
    except Exception as e:
        traceback.print_exc()
        smoke.problems.append(f"{type(e).__name__}: {e}")
    finally:
        smoke.close()
    ok = not smoke.problems
    # the result line: exactly these keys, the device as JAX reports it
    result = {
        "ok": ok,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["device_kind"]),
                   "count": int(device["device_count"])},
    }
    report = {
        **result,
        "rehearsal": bool(args.rehearse_cpu),
        "seed": args.seed,
        "problems": smoke.problems,
        "total_seconds_setup_info": round(time.perf_counter() - t_start, 1),
        **smoke.report,
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    # the evidence first, the result line last
    print(json.dumps({"report": report}, default=str))
    print(json.dumps(result))
    sys.stdout.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
