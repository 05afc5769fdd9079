#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the agent in-process as `nomad-tpu agent -server` wires it, on the
accelerator JAX finds (none, or fewer chips than the cell asks for:
exit 4, no result), loads the cell's configuration from --seed, warms
up, measures for --seconds, checks every answer against the plain
reference and prints the result as the last line of standard output.
Everything that belongs to one configuration, traffic mix, cell or
metric is a file the harness finds by the name in BENCHMARK.json:

    benchmark/configs/<config>.json    the deployment's sizes
    benchmark/traffic/<traffic>.json   the mix's parameters (found as
                                       traffic/<traffic>.json under the
                                       first of `paths` that has it)
    benchmark/cells/<cell>.json        (optional) a cell's own overrides
    benchmark/metrics/<metric>.json    the metric's reader and arguments
    benchmark/readers/<reader>.py      read(obs, **args) -> number or None

Three optional keys open a deployment that preempts (PR 32); every path
they switch on is chosen by the key, never by a name, and a
configuration or mix without it runs as before:

    a mix's job.priority                 the template's priority (else 50)
    a configuration's resident_tiers     the backlog, tier by tier:
        [{name, job_type, priority, jobs, allocs_per_node,
          alloc{cpu, memory_mb, disk_mb, mbits}}] (allocs_per_node is
        times the node's class scale); the harness's own
        loader builds it (every resident carries its job), the probe
        checks one node against it, every tier job's allocations are
        read back after the drain, and the judge compares four more
        numbers (evicted_wrongly, evicted_needlessly, evicted_with_room,
        residents_stopped). Without it: the program's one-job loader
        and `resident_allocs_per_node` / `resident_alloc`
    a configuration's scheduler_configuration   the wire form of
        PUT /v1/operator/scheduler/configuration: sent after boot and
        before the fleet loads, read back, and refused if it differs

A fourth opens a fleet with devices, chosen the same way:

    a machine class's devices            device groups {vendor, type,
        model, count, attributes} on every node of the class, each
        instance a UUID from a stream of its own (fleet.build_fleet);
        the probe reads one device node back and refuses the run if
        its groups differ; the judge compares one more number,
        device_conflicts, over the allocations read in full on the
        mix's `device_check_nodes` device nodes, and over_capacity
        counts instances. A mix's `device_deck`, lists of device asks
        dealt over its deck, is sent as the task's resources.devices

Flags beyond the contract's four are for the builder's own runs:
--rate (the sweep), --control (the reference in the program's place
with one guarantee or the ranking broken), --nodes with --rehearse-cpu
(a toy rehearsal on the CPU, which says platform "cpu" in its line),
--manifest (the tests' own, with a cell for a mix no cell runs yet:
`service-stream`, and the tests' tiered toy on its own mix,
tests/benchmark/traffic/toy-evict.json).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse             # noqa: E402
import importlib            # noqa: E402
import json                 # noqa: E402
import logging              # noqa: E402
import os                   # noqa: E402
import random               # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402
import traceback            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import fleet as fleetlib         # noqa: E402
from benchmark.lib import kernelcost                # noqa: E402
from benchmark.lib import reference                 # noqa: E402
from benchmark.lib import traffic                   # noqa: E402
from benchmark.lib import window                    # noqa: E402

TIME_LIMIT_S = 345.0        # the contract allows 360
WARMUP_ROUND_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def plan_cell(manifest: dict, name: str) -> dict:
    """What BENCHMARK.json says of one cell: its configuration's file,
    its traffic, and the metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    mine = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in mine)]
    return {"cell": cell, "config_file": config["file"],
            "paths": manifest["paths"],
            "end_to_end": e2e, "per_layer": per_layer}


def find_mix(paths: list, name: str) -> str:
    """A mix's file: `traffic/<name>.json` under the manifest's `paths`,
    the first in their order that has it (benchmark/ holds the mixes
    cells run; the tests' toys lie under tests/benchmark/)."""
    for directory in paths:
        path = os.path.join(ROOT, directory, "traffic", name + ".json")
        if os.path.exists(path):
            return path
    raise SystemExit(f"no traffic/{name}.json under {paths}")


def read_metrics(metrics: list, obs: dict) -> dict:
    """Each metric through the reader its own file names; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        spec = load_json("benchmark", "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Run:
    def __init__(self, args, plan: dict):
        self.args = args
        self.cell = plan["cell"]
        self.cfg = load_json(plan["config_file"])
        overrides = {}
        cell_file = os.path.join(ROOT, "benchmark", "cells",
                                 self.cell["name"] + ".json")
        if os.path.exists(cell_file):
            overrides = load_json("benchmark", "cells",
                                  self.cell["name"] + ".json")
        self.mix = traffic.load_mix(
            find_mix(plan["paths"], self.cell["traffic"]), overrides)
        if args.rate:
            self.mix["rate_per_s"] = args.rate
        self.seconds = float(args.seconds)
        self.seed = int(args.seed)
        self.trace = bool(args.trace)
        self.dcs = traffic.datacenters_of(self.cfg)
        self.fleet = fleetlib.build_fleet(self.cfg, self.seed, args.nodes)
        if args.rehearse_cpu:
            # the toy keeps the mix's proportions: no job larger than a
            # tenth of the fleet, as 1,000 instances are of 10,000 nodes
            self.mix["rehearse_s"] = min(1.0, self.mix.get("rehearse_s", 0.0))
            traffic.scale_counts(self.mix, min(
                1.0, 0.1 * len(self.fleet) / max(self.mix["deck"])))
        # a backlog the configuration describes tier by tier, as plain
        # data; without `resident_tiers` the program's one-job loader's
        self.residents = fleetlib.residents(self.cfg, self.fleet) \
            if "resident_tiers" in self.cfg else None
        self.backlog = self.residents["usage"] if self.residents \
            else fleetlib.backlog_usage(self.cfg, self.fleet)
        self.port_range = tuple(self.cfg["dynamic_port_range"])
        self.has_devices = any(n.get("devices") for n in self.fleet)
        self.problems: list = []
        self.obs: dict = {"seconds": self.seconds, "series": {}}
        self.trace_dir = None

    # -- the requests, the same for the program and for the control ----
    def requests(self):
        warm = traffic.warmup_requests(self.mix, self.seed, self.dcs)
        if self.args.rehearse_cpu:
            # a CPU compile of every bucket takes minutes and proves
            # nothing: the rehearsal keeps one solo and one burst round
            n_solo = len(self.mix.get("warmup", {}).get("solo", []))
            warm = warm[:1] + warm[n_solo:n_solo + 1]
        if self.mix["loop"] == "open":
            timed = traffic.open_loop(self.mix, self.seed, self.seconds,
                                      self.dcs, self.mix["rate_per_s"])
        else:
            timed = traffic.closed_loop(self.mix, self.seed, self.seconds,
                                        self.dcs)
        return warm, timed

    # -- the program ---------------------------------------------------
    def serve(self, device: dict) -> dict:
        from benchmark.lib import agent as agentlib
        from benchmark.lib import client
        mix, seconds = self.mix, self.seconds
        compiles = agentlib.CompileCounter()
        agent = self.agent = agentlib.Agent(self.cfg, log)
        addr = agent.boot()
        if "scheduler_configuration" in self.cfg:
            self._configure_scheduler(client.Http(addr))
        loaded = agent.load(self.fleet, self.residents)
        if loaded["nodes"] != len(self.fleet) or \
                not loaded["rows_in_id_order"]:
            raise RuntimeError(f"fleet did not load as made: {loaded}")
        http = client.Http(addr)
        self._probe_backlog(http)
        if self.has_devices:
            self._probe_devices(http)

        warm, timed = self.requests()
        loop = None
        if mix["loop"] == "closed":
            in_flight = mix["in_flight_per_scheduler"] \
                * self.cfg["server"]["num_schedulers"]
            loop = client.ClosedLoop(addr, timed, in_flight)
            mine = {j["id"] for s in loop.sent for j in s.req.jobs}
            watch = client.EvalWatch(
                addr, on_done=lambda jid, now: jid in mine
                and loop.job_done(jid, now))
        else:
            watch = client.EvalWatch(addr)
        watch.start()
        watch.ready.wait(30.0)
        if watch.error:
            raise RuntimeError(f"event stream: {watch.error}")

        # warm-up: every round through the served path, awaited
        t_w = time.perf_counter()
        warm_sent = []
        for rnd in warm:
            for req in rnd:
                s = client.Sent(req)
                client.put_jobs(http, s)
                warm_sent.append(s)
                if s.status != 200:
                    raise RuntimeError(f"warm-up register: {s.error}")
            ids = [j["id"] for req in rnd for j in req.jobs]
            missing = watch.wait_for(
                ids, time.perf_counter() + WARMUP_ROUND_TIMEOUT_S)
            if missing:
                raise RuntimeError(f"warm-up evals never completed: "
                                   f"{missing[:3]} ({watch.error})")
        log(f"warm-up: {len(warm)} rounds in "
            f"{time.perf_counter() - t_w:.2f}s, met {len(compiles.met)} "
            f"programs, {compiles.fetched} of them from the compile cache")

        taps = {}
        if self.trace:
            taps["stages"] = agentlib.StageTap()
            taps["gc"] = agentlib.GcWatch()

        # rehearsal, then the window: t0 is the window's start
        rehearse_s = float(mix.get("rehearse_s", 0.0))
        profile_lead_s = 1.5 if self.trace else 0.0
        t0 = time.perf_counter() + rehearse_s + profile_lead_s + 0.05
        if loop is None:
            gen = client.OpenLoop(addr, timed, mix["senders"])
            gen.start(t0)
        else:
            gen = loop
            gen.start()
        prof = None
        if self.trace:
            time.sleep(max(0.0, t0 - profile_lead_s - time.perf_counter()))
            prof = self._profile_start()
        time.sleep(max(0.0, t0 - time.perf_counter()))
        setup_s = t0 - T_START
        routing_before = agent.routing()
        sigs_before = agent.signatures()
        counters_before = agent.counters()
        log(f"window opens: setup_s {setup_s:.2f}")
        if prof is not None:
            profile_s = min(float(mix.get("profile_s", 15.0)), seconds)
            time.sleep(max(0.0, t0 + profile_s - time.perf_counter()))
            self._profile_stop(prof)
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        routing_after = agent.routing()
        counters_after = agent.counters()
        new_sigs = {k: sorted(map(str, v - sigs_before.get(k, set())))
                    for k, v in agent.signatures().items()
                    if v - sigs_before.get(k, set())}
        if loop is not None:
            loop.stop()
        log("window closed")
        peak = agentlib.memory_peak_bytes()

        # wait for every answer that is due: late is late, not wrong
        gen.join(10.0)
        sent_all = warm_sent + [s for s in gen.sent if s.sent_at is not None]
        acked = [s for s in sent_all if s.status == 200]
        ids = [j["id"] for s in acked for j in s.req.jobs]
        give_up = t_close + float(mix["drain_s"])
        missing = watch.wait_for(ids, give_up)
        if missing:
            log(f"{len(missing)} evals never completed: {missing[:3]}")
        if self.residents:
            # the evicted jobs' follow-up evals are the program's to
            # finish before the residents are read back
            calm, idle_s = agent.quiesce(
                max(1.0, give_up - time.perf_counter()))
            log(f"broker {'idle' if calm else 'STILL BUSY'} after "
                f"{idle_s:.2f}s")
            if not calm:
                # read back now, the residents are a store still moving:
                # a late follow-up eval would be judged half done
                self.problems.append(
                    f"the broker still held ready or outstanding evals "
                    f"{idle_s:.1f}s after the drain: the residents were "
                    f"read back while evals were in flight")
        if taps:
            agent.settle()
        for tap in taps.values():
            tap.close()

        # read the answers back, then free the program
        jobs = [j for s in acked for j in s.req.jobs]
        allocs, unread = {}, []
        t_r = time.perf_counter()
        for job in jobs:
            if job["id"] not in watch.done_at:
                continue
            status, body = http.request(
                "GET", f"/v1/job/{job['id']}/allocations")
            if status == 200 and isinstance(body, list):
                allocs[job["id"]] = body
            else:
                unread.append(f"{job['id']}: HTTP {status}")
        full = []
        rng = random.Random(self.seed)
        for alloc_id in reference.port_sample(
                self.fleet, jobs, allocs, int(mix["port_check_allocs"]), rng):
            status, body = http.request("GET", f"/v1/allocation/{alloc_id}")
            if status == 200:
                full.append(body)
            else:
                unread.append(f"alloc {alloc_id}: HTTP {status}")
        log(f"read back {sum(len(a) for a in allocs.values())} allocs of "
            f"{len(allocs)} jobs and {len(full)} in full in "
            f"{time.perf_counter() - t_r:.2f}s")
        device_full = self._read_device_sample(http, jobs, allocs, unread) \
            if self.has_devices else None
        residents_now = self._read_residents(http, unread) \
            if self.residents else None
        failures = agent.worker_failures()
        op_failures = routing_after["device_op_failures"]
        watch.stop()
        http.close()
        agent.close()
        if failures or op_failures:
            self.problems.append(f"{failures} evals failed in a worker, "
                                 f"device ops failed: {op_failures}")

        # the client's own series, relative to the window's start
        done = watch.done_at
        series = self.obs["series"]
        timed_sent = [s for s in gen.sent if s.sent_at is not None]
        if loop is None:
            in_win = [s for s in timed_sent
                      if 0.0 <= s.req.due_s < seconds]
            due = [t0 + s.req.due_s for s in in_win]
            ok_done = [done.get(s.req.jobs[0]["id"])
                       if s.status == 200 else None for s in in_win]
            lat, _n = window.latencies_ms(due, ok_done, give_up)
            series["eval_ms"] = [(s.req.due_s, v)
                                 for s, v in zip(in_win, lat)]
            half = [v for t, v in series["eval_ms"] if t < seconds / 2], \
                [v for t, v in series["eval_ms"] if t >= seconds / 2]
            log(f"open loop at {mix['rate_per_s']}/s: {len(in_win)} due, "
                f"p50 by half {window.quantile(half[0], 0.5)} / "
                f"{window.quantile(half[1], 0.5)} ms, p90 by half "
                f"{window.quantile(half[0], 0.9)} / "
                f"{window.quantile(half[1], 0.9)} ms, p50/p90/p99 "
                f"{window.quantile(lat, 0.5):.1f}/"
                f"{window.quantile(lat, 0.9):.1f}/"
                f"{window.quantile(lat, 0.99):.1f} ms, not done at the close "
                f"{sum(1 for d in ok_done if d is None or d > t_close)}")
            by_count = {}
            for s_, v in zip(in_win, lat):
                by_count.setdefault(s_.req.jobs[0]["count"], []).append(v)
            log("p50 by job size: " + str({
                c: round(window.quantile(v, 0.5), 1)
                for c, v in sorted(by_count.items())}))
            series["generator_late_ms"] = [
                (s.req.due_s, (s.sent_at - t0 - s.req.due_s) * 1000.0)
                for s in in_win]
        else:
            in_win = [s for s in timed_sent
                      if 0.0 <= s.sent_at - t0 < seconds]
        series["register_ms"] = [
            (s.sent_at - t0, (s.acked_at - s.sent_at) * 1000.0)
            for s in timed_sent if s.status == 200]
        good = {j["id"]: j for j in jobs
                if (watch.evals.get(j["id"]) or {}).get("status")
                == "complete"}
        series["placements"] = [
            (done[jid] - t0, len(allocs.get(jid, [])))
            for jid in good if jid in done]
        attempted = sum(len(s.req.jobs) for s in in_win)
        failed = sum(
            1 for s in in_win for j in s.req.jobs
            if s.status != 200 or j["id"] not in good
            or watch.evals[j["id"]].get("failed_tg_allocs"))
        done_rel = sorted(done[j] - t0 for j in done)
        self.obs.update(
            setup_s=setup_s, device=device,
            evals_done=sum(1 for t in done_rel if 0.0 <= t < seconds),
            routing={"before": routing_before["dispatches"],
                     "after": routing_after["dispatches"]},
            counters={"before": counters_before, "after": counters_after},
            programs_met=[(t - t0, kind, s) for t, kind, s in compiles.met])
        met_in = [m for m in self.obs["programs_met"]
                  if 0.0 <= m[0] < seconds]
        log(f"programs first met in the window: {len(met_in)} {met_in[:4]}; "
            f"new trace signatures: {new_sigs}")
        log(f"program counters at the open {counters_before} and at the "
            f"close {counters_after}")
        before = routing_before["dispatches"]
        self.arms = {arm: n - before.get(arm, 0)
                     for arm, n in routing_after["dispatches"].items()
                     if n - before.get(arm, 0)}
        log(f"dispatches in the window by arm: {self.arms}")
        if self.trace:
            self.obs["stages"] = [(name, end - t0, s) for name, end, s
                                  in taps["stages"].samples]
            self.obs["gc_pauses"] = [(t - t0, s, gen_) for t, s, gen_
                                     in taps["gc"].pauses]
            sums: dict = {}
            for name, end, s in self.obs["stages"]:
                if 0.0 <= end < seconds:
                    n, total = sums.get(name, (0, 0.0))
                    sums[name] = (n + 1, total + s)
            log("stage reports that ended in the window (count, seconds): "
                + str({k: (n, round(t, 3))
                       for k, (n, t) in sorted(sums.items())}))
            self._reduce_trace(t0, jobs, done, allocs, residents_now)
        return {"jobs": jobs, "evals": watch.evals, "allocs": allocs,
                "full": full, "unread": unread, "attempted": attempted,
                "failed": failed, "peak": peak,
                "residents_now": residents_now, "device_full": device_full}

    def _configure_scheduler(self, http) -> None:
        """The configuration's `scheduler_configuration`, PUT to the
        operator API as an operator would and read back: a deployment
        that does not hold what its file states is not run."""
        want = self.cfg["scheduler_configuration"]
        path = "/v1/operator/scheduler/configuration"
        status, body = http.request(
            "PUT", path, json.dumps({"SchedulerConfig": want}).encode())
        if status != 200:
            raise RuntimeError(f"scheduler configuration: HTTP {status} "
                               f"{body}")
        status, body = http.request("GET", path)
        got = (body or {}).get("SchedulerConfig") or {}

        def held(want, got):
            if isinstance(want, dict):
                return isinstance(got, dict) and all(
                    held(v, got.get(k)) for k, v in want.items())
            return want == got
        http.close()
        if status != 200 or not held(want, got):
            raise RuntimeError(f"scheduler configuration read back as "
                               f"{got}, the file states {want}")
        log(f"scheduler configuration PUT and read back: {got}")

    def _probe_tiers(self, http, probe: dict, on_node: list) -> None:
        """One node's allocations against the tiers: how many of each
        tier's jobs, their resources, and the jobs' priority."""
        tiers = self.residents["tiers"]
        want = [0] * len(tiers)
        for _alloc, (tier, _job, node_id) in \
                self.residents["allocs"].items():
            want[tier] += node_id == probe["id"]
        got = [0] * len(tiers)
        first = {}                      # tier -> one of its stubs here
        for stub in on_node:
            plain = self.residents["jobs"].get(stub["job_id"])
            if plain is None:
                raise RuntimeError(f"backlog on {probe['name']}: "
                                   f"{stub['job_id']} is no tier's job")
            got[plain["tier"]] += 1
            first.setdefault(plain["tier"], stub)
        if got != want:
            raise RuntimeError(f"backlog on {probe['name']}: {got} allocs "
                               f"by tier, the tiers make {want}")
        for tier, stub in first.items():
            _s, one = http.request("GET", f"/v1/allocation/{stub['id']}")
            _s, job = http.request("GET", f"/v1/job/{stub['job_id']}")
            res = one["allocated_resources"]
            task = next(iter(res["tasks"].values()))
            row = {"cpu": task["cpu"]["cpu_shares"],
                   "memory_mb": task["memory"]["memory_mb"],
                   "disk_mb": res["shared"]["disk_mb"], "mbits": 0}
            if row != tiers[tier]["alloc"] \
                    or job["priority"] != tiers[tier]["priority"] \
                    or job["type"] != tiers[tier]["job_type"]:
                raise RuntimeError(
                    f"backlog on {probe['name']}: {stub['id']} holds {row} "
                    f"for a {job['type']} job of priority "
                    f"{job['priority']}, tier {tiers[tier]['name']} states "
                    f"otherwise")
        log(f"backlog on {probe['name']}: {got} allocs by tier, as made")

    def _read_residents(self, http, unread: list) -> dict:
        """Every tier job's allocations as they now stand, over the
        served path: what was evicted, and what a follow-up eval of an
        evicted job placed since."""
        t_r = time.perf_counter()
        now = {}
        for job_id in self.residents["jobs"]:
            status, body = http.request(
                "GET", f"/v1/job/{job_id}/allocations")
            if status == 200 and isinstance(body, list):
                now[job_id] = body
            else:
                unread.append(f"{job_id}: HTTP {status}")
        stubs = [a for body in now.values() for a in body]
        log(f"read back {len(stubs)} resident allocs of {len(now)} tier "
            f"jobs in {time.perf_counter() - t_r:.2f}s: "
            f"{sum(a['desired_status'] == 'evict' for a in stubs)} evicted, "
            f"{sum(a['id'] not in self.residents['allocs'] for a in stubs)} "
            f"placed since the load")
        return now

    def device_sample(self, jobs: list, allocs: dict) -> list:
        """The allocations the device check reads in full: on the mix's
        `device_check_nodes` device nodes, drawn from the seed on a
        stream of their own (the port check's stays as it was)."""
        return reference.device_sample(
            self.fleet, jobs, allocs, int(self.mix["device_check_nodes"]),
            random.Random(f"{self.seed}/device-check"))

    def _read_device_sample(self, http, jobs: list, allocs: dict,
                            unread: list) -> list:
        t_r = time.perf_counter()
        ids = self.device_sample(jobs, allocs)
        full = []
        for alloc_id in ids:
            status, body = http.request("GET", f"/v1/allocation/{alloc_id}")
            if status == 200:
                full.append(body)
            else:
                unread.append(f"alloc {alloc_id}: HTTP {status}")
        log(f"device check: read {len(full)} allocs in full on "
            f"{len({a['node_id'] for a in full})} device nodes in "
            f"{time.perf_counter() - t_r:.2f}s")
        return full

    def _probe_devices(self, http) -> None:
        """One device node drawn from the seed, read back over HTTP: its
        groups, their instances' ids and their attributes as made."""
        probe = random.Random(f"{self.seed}/device-probe").choice(
            [n for n in self.fleet if n.get("devices")])
        _s, node = http.request("GET", f"/v1/node/{probe['id']}")
        got = [{"vendor": g["vendor"], "type": g["type"], "model": g["name"],
                "attributes": g["attributes"],
                "ids": [i["id"] for i in g["instances"] if i["healthy"]]}
               for g in ((node or {}).get("node_resources") or {}).get(
                   "devices") or []]
        if got != probe["devices"]:
            raise RuntimeError(f"devices on {probe['name']}: read back "
                               f"{got}, the fleet made {probe['devices']}")
        log(f"devices on {probe['name']}: "
            f"{[(g['model'], len(g['ids'])) for g in got]}, as made")

    def _probe_backlog(self, http) -> None:
        """The backlog the capacity check assumes, read back over HTTP
        from one node drawn from the seed."""
        probe = self.fleet[random.Random(self.seed).randrange(
            len(self.fleet))]
        _s, on_node = http.request("GET",
                                   f"/v1/node/{probe['id']}/allocations")
        if self.residents:
            return self._probe_tiers(http, probe, on_node)
        _s, one = http.request("GET", f"/v1/allocation/{on_node[0]['id']}")
        task = next(iter(one["allocated_resources"]["tasks"].values()))
        row = {"cpu": task["cpu"]["cpu_shares"],
               "memory_mb": task["memory"]["memory_mb"],
               "disk_mb": one["allocated_resources"]["shared"]["disk_mb"],
               "mbits": 0}
        if len(on_node) != self.cfg["resident_allocs_per_node"] \
                or row != self.cfg["resident_alloc"]:
            raise RuntimeError(f"backlog on {probe['name']}: "
                               f"{len(on_node)} allocs of {row}")

    # -- the profiler --------------------------------------------------
    def _profile_start(self) -> dict:
        import jax
        from benchmark.lib.tracered import MARK
        self.trace_dir = tempfile.mkdtemp(prefix="nomad-tpu-bench-trace-")
        t_epoch = time.time() - time.perf_counter()
        # device ops and TraceMe annotations only: the Python tracer (on
        # by default) slows the scheduler's host path several times over
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        mark_at = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK):
            time.sleep(0.002)
        return {"h0": time.perf_counter(), "mark_at": mark_at,
                "epoch_offset": -t_epoch}

    def _profile_stop(self, prof: dict) -> None:
        import jax
        prof["h1"] = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"profiler stopped after {prof['h1'] - prof['h0']:.2f}s "
            f"(+{time.perf_counter() - prof['h1']:.2f}s to write)")
        self.prof = prof

    def _reduce_trace(self, t0: float, jobs: list, done: dict,
                      allocs: dict, residents_now) -> None:
        from benchmark.lib import tracered
        prof = self.prof
        path = tracered.find_xplane(self.trace_dir)
        if path is None:
            self.problems.append("the profiler wrote no trace")
            return
        t_r = time.perf_counter()
        events = tracered.load_xplane(path)
        # seconds to add to a profiler time to get this process's clock
        offset = tracered.clock_offset(events, prof["mark_at"])
        if offset is None:
            offset = prof["epoch_offset"]
        h0, h1 = max(prof["h0"], t0), prof["h1"]
        spans = [(name, end + t0 - s - offset, end + t0 - offset)
                 for name, end, s in self.obs["stages"]]
        red = tracered.reduce(events, h0 - offset, h1 - offset, spans)
        self.obs["trace"] = red
        traced = [j for j in jobs if j["id"] in done
                  and h0 <= done[j["id"]] < h1]
        self.obs["traced_evals"] = len(traced)
        self.obs["traced_floor_bytes"] = self.floor_bytes(
            traced, allocs, residents_now)
        log(f"trace: {len(events)} device events reduced in "
            f"{time.perf_counter() - t_r:.2f}s; busy {red['busy_s']:.4f}s "
            f"of {red['window_s']:.2f}s, kernels {red['kernel_s']:.4f}s in "
            f"{red['kernel_runs']} runs, {len(traced)} evals")
        if self.args.out:
            os.makedirs(self.args.out, exist_ok=True)
            first = min((e["start_s"] for e in events
                         if e["start_s"] >= h0 - offset), default=h0 - offset)
            sample = [e for e in events
                      if first <= e["start_s"] < first + 2.0]
            with open(os.path.join(
                    self.args.out,
                    f"trace_{self.cell['name']}_{self.seed}.json"), "w") as f:
                json.dump({"t0": first, "t1": first + 2.0,
                           "events": sample[:4000],
                           "spans": [s for s in spans
                                     if first <= s[2] < first + 2.0][:2000]},
                          f)
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    def floor_bytes(self, traced: list, allocs: dict,
                    residents_now) -> int:
        """The roofline's floor for the traced evals: the least bytes
        any implementation moves for each (kernelcost). The victim
        selection's candidates are charged to an eval only if a commit
        of its own evicted (a resident's modify_index is its evictor's
        create_index): an eval that found room needed none. Their
        count is the least it was in the run: the resident allocations
        of the tiers PRIORITY_DELTA or more below the template's
        priority as loaded, less every one evicted since (replacements,
        which add to it, left out)."""
        spec = self.mix["job"]
        gone = [a for body in (residents_now or {}).values() for a in body
                if a["desired_status"] == "evict"]
        evicting = {int(a.get("modify_index") or 0) for a in gone}
        candidates = 0
        if evicting:
            low = {t for t, tier in enumerate(self.residents["tiers"])
                   if spec.get("priority", 50) - tier["priority"]
                   >= reference.PRIORITY_DELTA}
            candidates = sum(1 for tier, _job, _node
                             in self.residents["allocs"].values()
                             if tier in low) - len(gone)
        return sum(
            kernelcost.select_floor_bytes(
                len(self.fleet), len(fleetlib.DIMS), j["count"],
                spreads=len(spec.get("spreads", [])),
                affinities=len(spec.get("affinities", [])),
                ports=spec.get("dynamic_ports", 0),
                preempt_candidates=candidates if any(
                    int(a.get("create_index") or 0) in evicting
                    for a in allocs.get(j["id"], [])) else 0)
            for j in traced)

    # -- the control: the plain reference in the program's place -------
    def control(self, broken) -> dict:
        plain = reference.PlainScheduler(
            self.fleet, self.backlog, self.port_range, broken=broken,
            residents=self.residents,
            scheduler_configuration=self.cfg.get("scheduler_configuration"))
        warm, timed = self.requests()
        reqs = [r for rnd in warm for r in rnd]
        if self.mix["loop"] == "open":
            reqs += timed
        else:
            # as many bulk requests as the window's length allows for
            # at the mix's own ceiling would overrun any run: take as
            # many jobs as a run of the program completes (--control-jobs)
            reqs += timed[:max(1, self.args.control_jobs
                               // int(self.mix["bulk"]))]
        jobs = [j for r in reqs for j in r.jobs]
        t_c = time.perf_counter()
        for job in jobs:
            plain.submit(job)
        log(f"control {broken!r}: plain scheduler answered {len(jobs)} "
            f"jobs in {time.perf_counter() - t_c:.2f}s")
        rng = random.Random(self.seed)
        ids = reference.port_sample(self.fleet, jobs, plain.allocs,
                                    int(self.mix["port_check_allocs"]), rng)
        dev_ids = self.device_sample(jobs, plain.allocs) \
            if self.has_devices else []
        self.obs.update(setup_s=time.perf_counter() - T_START)
        return {"jobs": jobs, "evals": plain.evals, "allocs": plain.allocs,
                "full": [plain.full[i] for i in ids if i in plain.full],
                "unread": [i for i in ids + dev_ids if i not in plain.full],
                "attempted": len(jobs), "failed": 0, "peak": 0,
                "residents_now": plain.resident_allocs()
                if self.residents else None,
                "device_full": [plain.full[i] for i in dev_ids
                                if i in plain.full]
                if self.has_devices else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop rate instead of the mix's (the sweep)")
    ap.add_argument("--control", default="",
                    help="answer with the plain reference instead of the "
                         "program: 'none' whole, or one of "
                         f"{reference.CONTROLS} broken")
    ap.add_argument("--control-jobs", type=int, default=80)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--nodes", type=int, default=0,
                    help="a smaller fleet (CPU rehearsal only)")
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="another manifest than the repo's (the tests "
                         "rehearse a mix that no cell runs yet)")
    ap.add_argument("--out", default="",
                    help="directory for a sample of the reduced trace")
    args = ap.parse_args(argv)
    if args.nodes and not args.rehearse_cpu:
        ap.error("--nodes is for --rehearse-cpu")
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    plan = plan_cell(load_json(args.manifest), args.workload)
    from benchmark.lib import agent as agentlib
    agentlib.start_watchdog(TIME_LIMIT_S)
    try:
        device = agentlib.init_device(plan["cell"]["chips"],
                                      allow_cpu=args.rehearse_cpu)
    except RuntimeError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    if args.rehearse_cpu and device["platform"] != "cpu":
        print("benchmark: --rehearse-cpu on an accelerator", file=sys.stderr)
        return 4
    log(f"device: {device}")

    run = Run(args, plan)
    got = None
    try:
        if args.control:
            got = run.control(None if args.control == "none"
                              else args.control)
        else:
            got = run.serve(device)
    except Exception as e:
        traceback.print_exc()
        run.problems.append(f"{type(e).__name__}: {e}")
        agent = getattr(run, "agent", None)
        if agent is not None:
            agent.close()
    if got is None:
        for p in run.problems:
            print(f"benchmark: {p}", file=sys.stderr)
        return 1

    # the plain reference judges what the timed path produced, after
    # the window has closed, the peak is read and the program is gone
    t_j = time.perf_counter()
    compared, found = reference.judge(
        run.fleet, run.backlog, got["jobs"], got["evals"], got["allocs"],
        got["full"], got["unread"], run.port_range,
        run.cfg["server"]["num_schedulers"],
        run.cfg["server"].get("decorrelation"),
        reference.ResidentState(run.residents, got["residents_now"])
        if run.residents else None, got["device_full"])
    compared["harness_problems"] = {"value": len(run.problems), "limit": 0}
    correct = reference.is_correct(compared)
    log(f"reference judged {len(got['jobs'])} jobs in "
        f"{time.perf_counter() - t_j:.2f}s")

    metrics = read_metrics(plan["per_layer"] if args.trace
                           else plan["end_to_end"], run.obs)
    device_out = dict(device, memory_peak_bytes=int(got["peak"]))
    result = {"correct": bool(correct), "attempted": int(got["attempted"]),
              "failed": int(got["failed"]), "metrics": metrics,
              "device": device_out}
    tr = run.obs.get("trace")
    if args.trace and tr:
        device_out["busy_s"] = tr["busy_s"]
        device_out["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    if getattr(run, "arms", None):
        # which backend the program's router gave the window's selects
        # ("@cpu" arms ran on the host): two runs that differ here
        # differ by the router's choice, not by the code under test
        result["arms"] = run.arms
    result["compared"] = compared

    for p in run.problems:
        print(f"benchmark: {p}", file=sys.stderr)
    for name, broke in found.items():
        for line in broke[:5]:
            print(f"benchmark: {name}: {line}", file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
