"""Multi-chip scheduling: the node axis sharded over a device mesh.

The reference scales the node dimension by sampling (log2(n) candidates,
stack.go:77-89); we scale it by sharding: the NodeTable's (N, dims)
arrays live sharded over the `nodes` mesh axis, the fused select kernel
runs SPMD under jit, and XLA inserts the cross-shard collectives for the
argmax/top-k reduction and the one-hot carry updates (all-gather of the
chosen index). This is the orchestrator's analog of data parallelism:
feasibility+scoring are embarrassingly parallel per node; only the
winner reduction crosses ICI (SURVEY.md §2.6/§2.7).

Multi-host: the same jit program runs under multi-process JAX, with the
node axis sharded across hosts' devices; DCN only carries the per-eval
ask vectors and result placements (small), never the node table.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.select import (PACK_SHARD_KINDS, SelectRequest, _bucket_k,
                          _note_trace, _select_scan, cost_model,
                          pack_request, unpack_result)
from ..utils import stages
from .sharded_table import (ShardedDeviceNodeTable, pad_for_mesh,
                            resident_enabled)

# capacity-only fallback cache bound (tables WITHOUT a mirror token —
# private builds, older snapshots): evict-oldest past this many entries
CAPACITY_CACHE_MAX = 16


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), axis_names=("nodes",))


class ShardedSelect:
    """Dispatches the fused placement kernel with the node axis sharded
    over a mesh. The same _select_scan program is used — sharding is
    expressed purely through input shardings (SPMD via pjit)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.node_sharding = NamedSharding(mesh, P("nodes"))
        self.node2_sharding = NamedSharding(mesh, P("nodes", None))
        self.code_sharding = NamedSharding(mesh, P(None, "nodes"))
        self.replicated = NamedSharding(mesh, P())
        # mesh-resident node table (sharded_table.py): ALL hot columns
        # — capacity, used, free_ports — live sharded across evals,
        # advanced by the cache's delta journal; steady-state dispatches
        # ship only per-request arrays (ask, feasible, pre_score, ...)
        self.resident = ShardedDeviceNodeTable(mesh)
        # capacity-only fallback for tables without a mirror token
        # (keyed by the host array's identity — NodeTable versions
        # share the capacity array until a node-set rebuild)
        self._resident: dict = {}
        self.stats = {"capacity_evictions": 0}

    def pad_to_shards(self, n: int) -> int:
        """Pad N so it divides evenly over the mesh."""
        return pad_for_mesh(self.mesh, n)

    def _sharding_for(self, kind: str):
        return {"node": self.node_sharding, "node2": self.node2_sharding,
                "code": self.code_sharding, "rep": self.replicated,
                "scalar": None}[kind]

    def select(self, req: SelectRequest):
        """Full sharded dispatch of a SelectRequest: identical semantics
        to SelectKernel.select, with the node axis spread over the mesh.
        Packing is shared with the single-device path (pack_request);
        only the device placement differs. When the request carries a
        live mirror token, the table-shaped columns come off the
        mesh-resident table instead of crossing the bus."""
        n_pad = self.pad_to_shards(len(req.feasible))
        k = _bucket_k(max(req.count, 1))
        t0 = time.perf_counter()
        # the @mesh window holds pack + sharded placement + dispatch +
        # unpack, so `fresh` is known only inside it; it feeds the
        # per-arm device stats only — no routing estimate reads an
        # @mesh arm
        with stages.span("kernel", arm="scan@mesh", n_pad=int(n_pad),
                         lanes=1) as sp:
            args, statics = pack_request(req, n_pad)
            resident = self.resident_args(req, n_pad)
            placed_args = {}
            for name, value in args.items():
                if resident is not None and name in resident:
                    placed_args[name] = resident[name]
                    continue
                if name == "capacity":
                    placed_args[name] = self._resident_capacity(
                        req.capacity, value)
                    continue
                sharding = self._sharding_for(PACK_SHARD_KINDS[name])
                placed_args[name] = (value if sharding is None
                                     else jax.device_put(value, sharding))
            fresh = _note_trace("scan@mesh", n_pad, k_steps=k, **statics)
            sp.note(fresh=bool(fresh))
            with self.mesh:
                _carry, outs = _select_scan(**placed_args, k_steps=k,
                                            **statics)
            out = unpack_result(req, outs)
            seconds = time.perf_counter() - t0  # before the span's report
        cost_model.observe("scan@mesh", n_pad, seconds, compiled=fresh)
        return out

    def resident_args(self, req: SelectRequest,
                      n_pad: int) -> Optional[dict]:
        """Mesh-resident replacements for the table-shaped inputs
        (capacity, used0, free_ports) — the sharded analog of
        SelectKernel._resident_args, sharing the same assembly
        (device_table.resident_request_args): used0 computed ON the
        mesh as resident-used + the sparse per-eval plan overlay, with
        dense fallback for stale snapshots, shape mismatches, or
        overlays too wide to scatter."""
        if not resident_enabled():
            return None
        from ..ops.device_table import resident_request_args
        return resident_request_args(self.resident, req, n_pad,
                                     "nomad.select.mesh_resident")

    def _resident_capacity(self, src, padded):
        """Device-put the padded capacity once per (source array, pad)
        and keep it sharded on the mesh across evals — the fallback for
        tables without a mirror token (the full resident table serves
        tokened requests). `src` is the host NodeTable's capacity array
        whose identity keys the cache; eviction is oldest-first, never
        a wholesale clear (dropping the hot table on churn re-uploads
        it on the very next eval)."""
        key = (id(src), padded.shape[0])
        hit = self._resident.get(key)
        if hit is not None and hit[0] is src:
            return hit[1]
        arr = jax.device_put(padded, self.node2_sharding)
        while len(self._resident) >= CAPACITY_CACHE_MAX:
            # dicts preserve insertion order: drop the oldest entry
            self._resident.pop(next(iter(self._resident)))
            self.stats["capacity_evictions"] += 1
        self._resident[key] = (src, arr)
        return arr

    def stats_snapshot(self) -> dict:
        """One read for the governor gauges, the telemetry device.*
        family, and the bench artifact (ops/select.mesh_stats_snapshot
        fronts this for the process-wide instance)."""
        ndev = int(self.mesh.devices.size)
        total = self.resident.device_bytes()
        out = {
            "devices": ndev,
            "resident_bytes": total,
            "resident_bytes_per_device": total / max(ndev, 1),
            "capacity_cache_entries": len(self._resident),
            "capacity_cache_evictions": self.stats["capacity_evictions"],
        }
        out.update(self.resident.snapshot())
        return out

    def _resident_capacity_for_table(self, table, n_pad: int):
        """The mesh-resident capacity column for a tokened table, or
        None (caller falls back to the identity-keyed cache). Batched
        lanes share one capacity array but carry per-lane used0, so
        only capacity rides the full resident table here."""
        if table is None or not resident_enabled():
            return None
        state = self.resident.arrays_for(table)
        if state is None or state.n_pad != n_pad:
            return None
        return state.capacity

    def place_batched_chunked_args(self, cargs: dict,
                                   capacity_src=None,
                                   table=None) -> dict:
        """Shard the BATCHED K-way kernel's argument dict: per-lane
        arrays carry a leading batch axis (B, ...) that stays
        replicated while the node axis shards — the multi-eval batch
        (select_many) runs as one SPMD program over the mesh. Capacity
        is unstacked (all lanes share one table; that's the batching
        precondition) and rides the mesh-resident table when a mirror
        token is available, else the identity-keyed cache."""
        batched = {
            "node": NamedSharding(self.mesh, P(None, "nodes")),
            "node2": NamedSharding(self.mesh, P(None, "nodes", None)),
            "code": NamedSharding(self.mesh, P(None, None, "nodes")),
            "rep": self.replicated,
            "scalar": self.replicated,      # scalars stack to (B,)
        }
        placed = {}
        for name, value in cargs.items():
            if name == "capacity":
                cap = self._resident_capacity_for_table(
                    table, value.shape[0])
                if cap is not None:
                    placed[name] = cap
                elif capacity_src is not None:
                    placed[name] = self._resident_capacity(capacity_src,
                                                           value)
                else:
                    placed[name] = jax.device_put(
                        value, self.node2_sharding)
                continue
            sharding = batched[PACK_SHARD_KINDS[name]]
            placed[name] = jax.device_put(np.asarray(value), sharding)
        return placed

    def place_chunked_args(self, cargs: dict,
                           capacity_src=None,
                           req: Optional[SelectRequest] = None) -> dict:
        """Shard the K-way kernel's argument dict over the mesh (same
        kind table as the scan). When `req` carries a live mirror
        token, the table-shaped columns (capacity, used0, free_ports)
        come off the mesh-resident table; else capacity_src rides the
        identity-keyed cache."""
        resident = None
        if req is not None:
            resident = self.resident_args(req,
                                          cargs["capacity"].shape[0])
        placed = {}
        for name, value in cargs.items():
            if resident is not None and name in resident:
                placed[name] = resident[name]
                continue
            if name == "capacity" and capacity_src is not None:
                placed[name] = self._resident_capacity(capacity_src,
                                                       value)
                continue
            sharding = self._sharding_for(PACK_SHARD_KINDS[name])
            placed[name] = (value if sharding is None
                            else jax.device_put(value, sharding))
        return placed

    def place(self, capacity, used, feasible, ask, count, *,
              tg_collisions=None, job_count=None, spread_alg=False):
        """Convenience wrapper: basic sharded multi-placement."""
        n = capacity.shape[0]
        req = SelectRequest(
            ask=np.asarray(ask, np.float32), count=count,
            feasible=feasible, capacity=capacity, used=used,
            desired_count=float(max(count, 1)),
            tg_collisions=(tg_collisions if tg_collisions is not None
                           else np.zeros(n, np.int32)),
            job_count=(job_count if job_count is not None
                       else np.zeros(n, np.int32)),
            algorithm="spread" if spread_alg else "binpack",
        )
        res = self.select(req)
        return res.node_idx, res.final_score
