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
    """Plain node dicts sorted by id — the server's table row order."""
    rng = random.Random(seed)
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
    fleet.sort(key=lambda n: n["id"])
    return fleet


def backlog_usage(cfg: dict, fleet: List[dict]) -> Dict[str, Dict[str, float]]:
    """Per-node usage of the resident backlog: the replay loader deals
    alloc i to node (i mod n) of the fleet in the order it was given,
    which is this list's, so every node holds the same number."""
    per = cfg["resident_allocs_per_node"]
    row = cfg["resident_alloc"]
    return {n["id"]: {d: per * row[d] for d in DIMS} for n in fleet}
