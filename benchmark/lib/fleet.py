"""The fleet of a configuration as plain data from a seed.

No jax, no nomad_tpu: the plain reference reads these dicts, and
agent.py turns the same dicts into the program's Node objects. Every
seed gives the same fleet SHAPE (datacenter, rack and machine class
follow the node's ordinal); the seed draws the node ids, and so the
table's row order and every argmax tie-break.
"""

from __future__ import annotations

import random
import uuid
from typing import Dict, List

DIMS = ("cpu", "memory_mb", "disk_mb", "mbits")


def class_of(cfg: dict, i: int) -> dict:
    """Machine class of node ordinal `i`: classes repeat in blocks of
    64 ordinals so that every (datacenter, rack) pair holds every class
    in the configured tenths."""
    slot = (i // 64) % 10
    upto = 0
    for cls in cfg["machine_classes"]:
        upto += cls["tenths"]
        if slot < upto:
            return cls
    raise ValueError("machine_classes' tenths do not sum to 10")


def build_fleet(cfg: dict, seed: int, n_nodes: int = 0) -> List[dict]:
    """Plain node dicts sorted by id — the server's table row order.

    A machine class may carry `devices`, a list of groups {vendor, type,
    model, count, attributes} as a device plugin fingerprints them
    (attributes: numbers, strings, or numbers with a unit such as
    "16 GiB"); each node of the class then holds them as
    {vendor, type, model, attributes, ids}, `count` healthy instances
    whose ids are UUIDs drawn from a stream of their own, so that the
    node ids, and with them the row order, are the same with or without
    devices."""
    rng = random.Random(seed)
    dev_rng = random.Random(f"{seed}/devices")
    n_nodes = n_nodes or cfg["nodes"]
    n_dcs, n_racks = cfg["datacenters"], cfg["racks"]
    res, rsv = cfg["node"]["resources"], cfg["node"]["reserved"]
    fleet = []
    for i in range(n_nodes):
        cls = class_of(cfg, i)
        meta = dict(cfg["node"]["meta"])
        meta["rack"] = f"r{(i // n_dcs) % n_racks}"
        fleet.append({
            "id": str(uuid.UUID(int=rng.getrandbits(128), version=4)),
            "name": f"node-{i}",
            "datacenter": f"dc{i % n_dcs + 1}",
            "class": cls["name"],
            "scale": cls["scale"],
            "attributes": dict(cfg["node"]["attributes"]),
            "meta": meta,
            "drivers": list(cfg["node"]["drivers"]),
            "capacity": {d: res[d] * cls["scale"] - rsv[d] for d in DIMS},
        })
        if cls.get("devices"):
            fleet[-1]["devices"] = [{
                "vendor": g["vendor"], "type": g["type"],
                "model": g["model"], "attributes": dict(g["attributes"]),
                "ids": [str(uuid.UUID(int=dev_rng.getrandbits(128),
                                      version=4))
                        for _ in range(g["count"])]}
                for g in cls["devices"]]
    fleet.sort(key=lambda n: n["id"])
    return fleet


def backlog_usage(cfg: dict, fleet: List[dict]) -> Dict[str, Dict[str, float]]:
    """Per-node usage of the resident backlog: the replay loader deals
    alloc i to node (i mod n) of the fleet in the order it was given,
    which is this list's, so every node holds the same number. (A
    configuration with `resident_tiers`: `residents`' "usage".)"""
    per = cfg["resident_allocs_per_node"]
    row = cfg["resident_alloc"]
    return {n["id"]: {d: per * row[d] for d in DIMS} for n in fleet}


def residents(cfg: dict, fleet: List[dict]) -> dict:
    """The backlog a configuration describes (`resident_tiers`), as
    plain data: a tier is `jobs` jobs whose allocations are dealt over
    the fleet's nodes in the fleet's order, `allocs_per_node` times the
    node's class scale on each, so that a 4x node is as full as a 1x
    one. Slot s of a tier (node by node, in order) is
    allocation s // jobs of the tier's job s % jobs, so a node's
    allocations of a tier belong to as many jobs as it can; ids and
    names follow from the tier, the job and that ordinal, and the node
    from the seed through the fleet's order.

      tiers   the configuration's tiers, lowest priority first
      jobs    {job id: {"tier", "type", "priority", "group", "count"}}
      allocs  {alloc id: (tier index, job id, node id)}
      usage   {node id: {dim: sum over its resident allocations}}"""
    tiers = sorted(cfg["resident_tiers"], key=lambda t: t["priority"])
    out = {"tiers": tiers, "jobs": {}, "allocs": {},
           "usage": {n["id"]: dict.fromkeys(DIMS, 0) for n in fleet}}
    for ti, tier in enumerate(tiers):
        n_jobs = int(tier["jobs"])
        job_ids = [f"{tier['name']}-{j:03d}" for j in range(n_jobs)]
        counts = [0] * n_jobs
        slot = 0
        for node in fleet:
            k = tier["allocs_per_node"] * node["scale"]
            row = out["usage"][node["id"]]
            for d in DIMS:
                row[d] += k * tier["alloc"][d]
            for _ in range(k):
                j, i = slot % n_jobs, slot // n_jobs
                out["allocs"][f"{job_ids[j]}-{i:06d}"] = (ti, job_ids[j],
                                                          node["id"])
                counts[j] += 1
                slot += 1
        for jid, count in zip(job_ids, counts):
            out["jobs"][jid] = {"tier": ti, "type": tier["job_type"],
                                "priority": tier["priority"],
                                "group": "resident", "count": count}
    return out


def resident_name(alloc_id: str, group: str = "resident") -> str:
    """`<job>.<group>[<ordinal>]`, as the scheduler names a job's allocs."""
    job_id, ordinal = alloc_id.rsplit("-", 1)
    return f"{job_id}.{group}[{int(ordinal)}]"
