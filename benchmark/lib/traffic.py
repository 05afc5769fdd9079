"""One general traffic generator: a mix is a data file of parameters
(benchmark/traffic/<mix>.json), and this module turns mix + seed +
window length into plain job specs, their wire payloads and, for an
open loop, their due times. No jax, no nomad_tpu.

What a seed may change is ORDER and TIMING only. Every seed gives an
open-loop window the same multiset of job sizes (whole decks plus a
fixed partial deck) and the same multiset of inter-arrival gaps (the
quantiles of the exponential distribution at the mix's rate), each
shuffled by the seed — so two runs differ as two hours of one traffic
do, not as two traffics.
"""

from __future__ import annotations

import itertools
import json
import math
import random
from typing import Dict, List, Optional


def deck_counts(deck: List[int], n: int) -> List[int]:
    """The fixed multiset of `n` job sizes: whole decks, then a partial
    deck that takes evenly spaced cards of the sorted deck."""
    whole, rest = divmod(n, len(deck))
    cards = sorted(deck)
    partial = [cards[int(j * len(cards) / rest)] for j in range(rest)]
    return list(deck) * whole + partial


def exponential_gaps(n: int, span_s: float) -> List[float]:
    """`n` inter-arrival gaps whose multiset is fixed: the (i + 1/2)/n
    quantiles of the exponential distribution, scaled to sum to
    `span_s` (Poisson arrivals with the sampling noise taken out)."""
    if n <= 0:
        return []
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def arrivals(rng: random.Random, n: int, start_s: float,
             span_s: float) -> List[float]:
    gaps = exponential_gaps(n, span_s)
    rng.shuffle(gaps)
    out, t = [], start_s
    for g in gaps:
        out.append(t)
        t += g
    return out


def device_asks(asks: List[dict]) -> List[dict]:
    """Device asks (upstream's device stanza) in the plain form: each a
    name (`type`, `vendor/type` or `vendor/type/model`), a count of at
    least one, constraints [ltarget, operand, rtarget] and affinities
    [ltarget, operand, rtarget, weight] on ${device.*}."""
    out = []
    for a in asks:
        if int(a["count"]) < 1:
            raise ValueError(f"device ask {a['name']!r}: count "
                             f"{a['count']} is under one")
        out.append({"name": a["name"], "count": int(a["count"]),
                    "constraints": [tuple(c) for c in a.get("constraints",
                                                            [])],
                    "affinities": [tuple(x) for x in a.get("affinities",
                                                           [])]})
    return out


def plain_job(mix: dict, job_id: str, count: int,
              datacenters: List[str],
              devices: Optional[List[dict]] = None) -> dict:
    """One job of the mix's template. `devices`: the device asks the job
    was dealt from the mix's `device_deck` (_dealt); a job that asks for
    none has no `devices` key."""
    tpl = mix["job"]
    asks = device_asks(devices or [])
    job = {
        "id": job_id, "type": tpl["type"], "group": tpl["group"],
        "task": tpl["task"], "driver": tpl["driver"], "count": int(count),
        "priority": int(tpl.get("priority", 50)),
        "ask": dict(tpl["ask"]),
        "dynamic_ports": int(tpl.get("dynamic_ports", 0)),
        "datacenters": list(datacenters),
        "constraints": [tuple(c) for c in tpl.get("constraints", [])],
        "affinities": [tuple(a) for a in tpl.get("affinities", [])],
        "spreads": [(s[0], s[1], [tuple(t) for t in s[2]])
                    for s in tpl.get("spreads", [])],
    }
    if asks:
        job["devices"] = asks
    return job


def _dealt(mix: dict, counts: List[int]) -> List[tuple]:
    """The job sizes, each with the device asks it is dealt: a mix's
    `device_deck` (a list of ask lists) deals card d to every size of
    the d-th deck of `counts` (deck_counts' order), so that over as many
    decks as it has cards every size meets every card, and the same
    pairs come on every seed once shuffled; without one, none."""
    cards = mix.get("device_deck")
    if not cards:
        return [(c, None) for c in counts]
    per = len(mix["deck"])
    return [(c, cards[(i // per) % len(cards)]) for i, c in enumerate(counts)]


def wire_job(job: dict) -> dict:
    """The job as PUT /v1/jobs takes it (the agent's snake_case wire
    form); fields left out take the program's defaults."""
    network = None
    if job["ask"].get("mbits", 0) or job["dynamic_ports"]:
        network = {"mode": "host", "mbits": job["ask"].get("mbits", 0),
                   "reserved_ports": [],
                   "dynamic_ports": [
                       {"label": f"p{i}", "value": 0, "to": 0,
                        "host_network": "default"}
                       for i in range(job["dynamic_ports"])]}
    wire = {
        "id": job["id"], "name": job["id"], "namespace": "default",
        "region": "global", "type": job["type"], "priority": job["priority"],
        "datacenters": job["datacenters"],
        "constraints": [], "affinities": [], "spreads": [],
        "task_groups": [{
            "name": job["group"], "count": job["count"],
            "constraints": [{"ltarget": l, "operand": op, "rtarget": r}
                            for l, op, r in job["constraints"]],
            "affinities": [{"ltarget": l, "operand": op, "rtarget": r,
                            "weight": w}
                           for l, op, r, w in job["affinities"]],
            "spreads": [{"attribute": a, "weight": w,
                         "spread_target": [{"value": v, "percent": p}
                                           for v, p in targets]}
                        for a, w, targets in job["spreads"]],
            "networks": [],
            "tasks": [{
                "name": job["task"], "driver": job["driver"],
                "config": {"command": "/bin/date"}
                if job["driver"] == "exec" else {"run_for": "500ms"},
                "resources": {"cpu": job["ask"]["cpu"],
                              "memory_mb": job["ask"]["memory_mb"],
                              "networks": [network] if network else []},
            }],
            "ephemeral_disk": {"size_mb": job["ask"]["disk_mb"]},
        }],
    }
    if job.get("devices"):
        wire["task_groups"][0]["tasks"][0]["resources"]["devices"] = [
            {"name": a["name"], "count": a["count"],
             "constraints": [{"ltarget": l, "operand": op, "rtarget": r}
                             for l, op, r in a["constraints"]],
             "affinities": [{"ltarget": l, "operand": op, "rtarget": r,
                             "weight": w}
                            for l, op, r, w in a["affinities"]]}
            for a in job["devices"]]
    return wire


def payload(jobs: List[dict]) -> bytes:
    """Body of one PUT /v1/jobs: a single register or a bulk array."""
    if len(jobs) == 1:
        return json.dumps({"Job": wire_job(jobs[0])}).encode()
    return json.dumps([{"Job": wire_job(j)} for j in jobs]).encode()


class Request:
    """One PUT the generator will send: its jobs, body and due time
    (seconds from the window's start; negative in the rehearsal)."""
    __slots__ = ("jobs", "body", "due_s")

    def __init__(self, jobs: List[dict], due_s: Optional[float] = None):
        self.jobs = jobs
        self.body = payload(jobs)
        self.due_s = due_s


def _ids(mix: dict, seed: int, phase: str):
    k = 0
    while True:
        yield f"{mix['name']}-{seed}-{phase}{k:05d}"
        k += 1


def warmup_requests(mix: dict, seed: int,
                    datacenters: List[str]) -> List[List[Request]]:
    """The warm-up as rounds; the requests of a round are sent together
    and the round is awaited before the next. A mix lists solo counts
    (one job alone: the solo arms' buckets) and bursts (several jobs
    in one bulk PUT: the batched lanes)."""
    ids = _ids(mix, seed, "w")
    cards = mix.get("device_deck") or [None]
    k = itertools.count()

    def job(count):
        return plain_job(mix, next(ids), count, datacenters,
                         cards[next(k) % len(cards)])
    rounds = []
    warm = mix.get("warmup", {})
    for count in warm.get("solo", []):
        rounds.append([Request([job(count)])])
    for burst in warm.get("bursts", []):
        rounds.append([Request([job(c) for c in burst])])
    return rounds


def open_loop(mix: dict, seed: int, seconds: float,
              datacenters: List[str], rate: float) -> List[Request]:
    """Rehearsal (due < 0) and window (0 <= due < seconds) requests of
    an open loop, in due order."""
    rng = random.Random(seed * 2 + 1)
    ids = _ids(mix, seed, "r")
    out = []
    rehearse_s = float(mix.get("rehearse_s", 0.0))
    n_re = int(round(rate * rehearse_s))
    cards = _dealt(mix, deck_counts(mix["deck"], n_re))
    rng.shuffle(cards)
    for due, (count, asks) in zip(
            arrivals(rng, n_re, -rehearse_s, rehearse_s), cards):
        out.append(Request([plain_job(mix, next(ids), count, datacenters,
                                      asks)], due))
    ids = _ids(mix, seed, "j")
    n = int(round(rate * seconds))
    cards = _dealt(mix, deck_counts(mix["deck"], n))
    rng.shuffle(cards)
    for due, (count, asks) in zip(arrivals(rng, n, 0.0, seconds), cards):
        out.append(Request([plain_job(mix, next(ids), count, datacenters,
                                      asks)], due))
    return out


def closed_loop(mix: dict, seed: int, seconds: float,
                datacenters: List[str]) -> List[Request]:
    """Bulk requests of a closed loop, in the order they are sent; as
    many as the fastest program the mix allows for could take."""
    rng = random.Random(seed * 2 + 1)
    ids = _ids(mix, seed, "j")
    bulk = int(mix["bulk"])
    span = seconds + float(mix.get("rehearse_s", 0.0)) + 5.0
    n_req = int(math.ceil(span * mix["max_jobs_per_s"] / bulk)) + 4
    cards = _dealt(mix, deck_counts(mix["deck"], n_req * bulk))
    rng.shuffle(cards)
    return [Request([plain_job(mix, next(ids), count, datacenters, asks)
                     for count, asks in cards[r * bulk:(r + 1) * bulk]])
            for r in range(n_req)]


def scale_counts(mix: dict, factor: float) -> None:
    """Shrink every job size of `mix` in place (a toy rehearsal on a
    fraction of the fleet keeps count / nodes as the mix has it)."""
    def scaled(count):
        return max(1, int(round(count * factor)))
    mix["deck"] = [scaled(c) for c in mix["deck"]]
    warm = mix.get("warmup", {})
    if "solo" in warm:
        warm["solo"] = [scaled(c) for c in warm["solo"]]
    if "bursts" in warm:
        warm["bursts"] = [[scaled(c) for c in b] for b in warm["bursts"]]
    if "max_jobs_per_s" in mix:         # smaller jobs, more of them
        mix["max_jobs_per_s"] = mix["max_jobs_per_s"] / factor


def datacenters_of(cfg: dict) -> List[str]:
    return [f"dc{d + 1}" for d in range(cfg["datacenters"])]


def load_mix(path: str, cell_overrides: Optional[Dict] = None) -> dict:
    with open(path) as f:
        mix = json.load(f)
    mix.update(cell_overrides or {})
    return mix
