"""The system under test, booted in-process the way `nomad-tpu agent
-server` boots it, and the taps the traced run reads.

This is the only module of the benchmark that imports the program. It
takes from it the Server / RpcServer / HTTPApiServer trio, the replay
loader that fills the store (the restore analog), the stage report
hook (utils/stages), the routing counters (device_stats_snapshot) and
the compile-cache placement. It does nothing cmd_agent would not do:
no gc.freeze(), no gc.collect(), no pinned dispatch.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


def init_device(chips: int, allow_cpu: bool = False) -> dict:
    """Initialize JAX's ambient backend in this process (the compile
    cache goes where the program's own rule puts it: the environment's
    JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) and describe
    the device as JAX reports it. No accelerator, or fewer chips than
    the cell asks for, is an error: there is no CPU fallback."""
    from nomad_tpu.utils.platform import init_backend
    info = init_backend()
    # every program goes into the persistent cache, the quick ones too
    # (JAX's default keeps only compiles of a second or more): only a
    # checkout's first run of a cell compiles
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = {"platform": str(info["platform"]),
              "kind": str(info["device_kind"]),
              "count": int(info["device_count"])}
    if allow_cpu:
        return device
    if device["platform"] == "cpu":
        raise RuntimeError("JAX found no accelerator (platform 'cpu'); "
                           "the benchmark has no CPU fallback")
    if device["count"] < chips:
        raise RuntimeError(f"the cell asks for {chips} chip(s), JAX "
                           f"reports {device['count']}")
    return device


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the runtime reports."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use",
                                       stats.get("bytes_in_use", 0))))
    return peak


class CompileCounter:
    """Programs this process met for the first time, with the host time
    and the seconds each cost: JAX reports one backend_compile_duration
    per program, whether XLA built it (a checkout's first run) or the
    persistent cache returned it (later runs) — either way a stall when
    it falls inside the window. `fetched` counts the cache's returns."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring
        self.met: List[Tuple[float, str, float]] = []
        self.fetched = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.COMPILE:
            self.met.append((time.perf_counter(), "program", duration))
        elif event == self.FETCH:
            self.fetched += 1


class GcWatch:
    """Collector pauses as gc.callbacks reports them (start/stop pairs
    of this process's collections, whoever asked for them)."""

    def __init__(self):
        self.pauses: List[Tuple[float, float, int]] = []   # start, s, gen
        self._t0: Optional[float] = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:
            self.pauses.append((self._t0, now - self._t0,
                                int(info.get("generation", -1))))
            self._t0 = None

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)


class StageTap:
    """Every utils/stages report (stage, seconds, attrs), stamped with
    the host time it ended at, forwarded to the flight recorder that
    owned the hook before. A companion `<stage>_cpu` is the CPU clock
    read over its span's own interval and sent right after the span by
    the same thread: it is stamped with the span's end, not with its
    own arrival, which trails by whatever held the interpreter lock
    while the flight recorder took the span."""

    def __init__(self):
        from nomad_tpu.utils import stages
        self._stages = stages
        self._prev = stages._trace_hook
        self._prev_on = stages._trace_on
        self._last = threading.local()      # this thread's last report
        self.samples: List[Tuple[str, float, float]] = []  # stage, end, s
        stages.set_trace_hook(self._on, on=True)

    def _on(self, stage: str, seconds: float, attrs=None) -> None:
        end = time.perf_counter()
        last = getattr(self._last, "report", None)
        if last is not None and stage == last[0] + self._stages.CPU_SUFFIX:
            end = last[1]
        self._last.report = (stage, end)
        self.samples.append((stage, end, seconds))
        if self._prev is not None and self._prev_on:
            self._prev(stage, seconds, attrs)

    def close(self) -> None:
        self._stages.set_trace_hook(self._prev, on=self._prev_on)


class _ReplayIndex:
    """What the replay loader needs of a harness: the store, and raft
    indices drawn from the server's own counter (a replay, like a
    snapshot restore, writes the store under fresh indices)."""

    def __init__(self, srv):
        self.store = srv.store
        self._srv = srv

    def next_index(self) -> int:
        with self._srv._raft_l:
            self._srv._raft_index += 1
            return self._srv._raft_index


def _model_node(plain: dict, cfg: dict):
    from nomad_tpu.mock import fixtures as mock
    node = mock.node()
    node.id = plain["id"]
    node.name = plain["name"]
    node.datacenter = plain["datacenter"]
    node.node_class = plain["class"]
    node.attributes = dict(plain["attributes"])
    node.meta = dict(plain["meta"])
    res, k = cfg["node"]["resources"], plain["scale"]
    node.node_resources.cpu.cpu_shares = res["cpu"] * k
    node.node_resources.memory.memory_mb = res["memory_mb"] * k
    node.node_resources.disk.disk_mb = res["disk_mb"] * k
    node.node_resources.networks[0].mbits = res["mbits"] * k
    rsv = cfg["node"]["reserved"]
    node.reserved_resources.cpu_shares = rsv["cpu"]
    node.reserved_resources.memory_mb = rsv["memory_mb"]
    node.reserved_resources.disk_mb = rsv["disk_mb"]
    if plain.get("devices"):
        from nomad_tpu.models import NodeDevice, NodeDeviceResource
        # the attributes as a node registered over RPC holds them: the
        # fingerprint's values, which the program types as it compares
        # them (plugins/psstructs)
        node.node_resources.devices = [NodeDeviceResource(
            vendor=g["vendor"], type=g["type"], name=g["model"],
            attributes=dict(g["attributes"]),
            instances=[NodeDevice(id=i, healthy=True) for i in g["ids"]])
            for g in plain["devices"]]
    node.compute_class()
    return node


def _load_tiers(replay: _ReplayIndex, cfg: dict, plain: dict) -> None:
    """The backlog a configuration describes (`resident_tiers`; `plain`
    is its plain form, fleet.residents) through the store's replay
    paths, as seed_c2m_allocs loads its one job: upsert_job, then
    bulk_load_allocs with one shared resource row per tier. Every
    Allocation carries its `job` — the tier job's own object, with the
    job_modify_index the store gave it — as upstream's store
    denormalizes the job into every allocation it takes: without it a
    resident is never a candidate for eviction, and an eval of its job
    replaces it as a destructive update."""
    from nomad_tpu.mock import fixtures as mock
    from nomad_tpu.models import Allocation
    from nomad_tpu.models.resources import (AllocatedCpuResources,
                                            AllocatedMemoryResources,
                                            AllocatedResources,
                                            AllocatedSharedResources,
                                            AllocatedTaskResources)
    from .fleet import resident_name
    from .traffic import datacenters_of
    jobs, shared = {}, []
    for job_id, pj in plain["jobs"].items():
        tier = plain["tiers"][pj["tier"]]
        if tier["alloc"]["mbits"]:
            raise ValueError(f"tier {tier['name']}: resident network "
                             f"bandwidth is outside the loader's vocabulary")
        job = mock.batch_job() if pj["type"] == "batch" else mock.job()
        job.id = job.name = job_id
        job.priority = pj["priority"]
        job.datacenters = datacenters_of(cfg)
        tg = job.task_groups[0]
        tg.name = pj["group"]
        tg.count = pj["count"]
        tg.networks = []
        tg.ephemeral_disk.size_mb = tier["alloc"]["disk_mb"]
        task = tg.tasks[0]
        task.resources.cpu = tier["alloc"]["cpu"]
        task.resources.memory_mb = tier["alloc"]["memory_mb"]
        task.resources.networks = []
        replay.store.upsert_job(replay.next_index(), job)
        jobs[job_id] = job
        if len(shared) <= pj["tier"]:
            shared.append(AllocatedResources(
                tasks={task.name: AllocatedTaskResources(
                    cpu=AllocatedCpuResources(
                        cpu_shares=tier["alloc"]["cpu"]),
                    memory=AllocatedMemoryResources(
                        memory_mb=tier["alloc"]["memory_mb"]))},
                shared=AllocatedSharedResources(
                    disk_mb=tier["alloc"]["disk_mb"])))
    allocs = []
    for alloc_id, (ti, job_id, node_id) in plain["allocs"].items():
        job = jobs[job_id]
        allocs.append(Allocation(
            id=alloc_id, namespace="default", job_id=job_id, job=job,
            task_group=job.task_groups[0].name,
            name=resident_name(alloc_id, job.task_groups[0].name),
            node_id=node_id, eval_id=f"{job_id}-eval",
            client_status="running", desired_status="run",
            allocated_resources=shared[ti]))
        if len(allocs) >= 250_000:
            replay.store.bulk_load_allocs(replay.next_index(), allocs)
            allocs = []
    if allocs:
        replay.store.bulk_load_allocs(replay.next_index(), allocs)


class Agent:
    """Server + RpcServer + HTTPApiServer as cmd_agent wires them:
    default ServerConfig but for the fields the configuration's file
    names (gc_safepoints, as the CLI sets it; a long node TTL, since no
    client agents exist to heartbeat) and a data_dir, so that raft, the
    WAL and the ingest gateway are live."""

    def __init__(self, cfg: dict, log: Callable[[str], None]):
        self.cfg = cfg
        self.log = log
        self.data_dir = tempfile.mkdtemp(prefix="nomad-tpu-bench-")
        self.srv = self.rpc = self.api = None

    def _timed(self, name: str, t0: float) -> None:
        self.log(f"{name}: {time.perf_counter() - t0:.3f}s")

    def boot(self) -> str:
        from nomad_tpu.api import HTTPApiServer
        from nomad_tpu.rpc import RpcServer
        from nomad_tpu.server import Server, ServerConfig
        t0 = time.perf_counter()
        # `decorrelation` states the rule the program ships with, for
        # the reference to recompute: a property, not a ServerConfig field
        fields = {k: v for k, v in self.cfg["server"].items()
                  if k != "decorrelation"}
        self.srv = Server(ServerConfig(data_dir=self.data_dir, **fields))
        self.rpc = RpcServer(self.srv, port=0)
        self.srv.rpc_server = self.rpc
        self.srv.start()
        self.rpc.start()
        self.api = HTTPApiServer(self.srv, port=0)
        self.api.start()
        self._timed("boot", t0)
        return f"127.0.0.1:{self.api.port}"

    def load(self, fleet: List[dict], residents: Optional[dict] = None
             ) -> dict:
        """The fleet and its resident backlog through the store's replay
        paths (upsert_node is linear; Server.register_node is not, PR
        21), then the first resident-table build. `residents`: the
        plain form of a tiered configuration's backlog
        (fleet.residents), which the harness's own loader builds;
        without tiers the program's one-job loader runs."""
        from nomad_tpu.bench.ladder import seed_c2m_allocs
        srv, cfg = self.srv, self.cfg
        replay = _ReplayIndex(srv)
        t0 = time.perf_counter()
        nodes = [_model_node(p, cfg) for p in fleet]
        for node in nodes:
            srv.store.upsert_node(replay.next_index(), node)
        self._timed("load_nodes", t0)
        t0 = time.perf_counter()
        if residents is not None:
            _load_tiers(replay, cfg, residents)
        else:
            n_allocs = len(fleet) * cfg["resident_allocs_per_node"]
            seed_c2m_allocs(replay, nodes, n_allocs, sched_allocs=0)
        self._timed("load_backlog", t0)
        t0 = time.perf_counter()
        table = srv.store.snapshot().node_table()
        self._timed("table_build", t0)
        return {"nodes": srv.store.node_count(),
                "rows_in_id_order": table.ids == [n["id"] for n in fleet],
                "table_rows": table.n}

    def routing(self) -> dict:
        from nomad_tpu.ops.select import device_stats_snapshot
        snap = device_stats_snapshot()
        return {"dispatches": dict(snap["dispatches"]),
                "device_op_failures": dict(snap["device_op_failures"])}

    def counters(self) -> Dict[str, float]:
        """Program counters the readers take at the window's open and
        close: the snapshot counts, the WAL's absolute stream position
        (bytes ever appended), what it has taken since the last
        snapshot was triggered, and the two triggers. The since-trigger
        pair and the triggers are Persistence's own attributes: its
        `stats` does not carry them yet (PERF.md, Open questions)."""
        p = self.srv.persistence
        if p is None:
            return {}
        stats, size = p.stats, p.log.size()
        return {"persistence.background_snapshots":
                float(stats.get("background_snapshots", 0)),
                "persistence.snapshots": float(stats.get("snapshots", 0)),
                "persistence.wal_bytes": float(size),
                "persistence.wal_bytes_since_snapshot":
                float(size - p._bytes_at_snapshot),
                "persistence.wal_entries_since_snapshot":
                float(p._since_snapshot),
                "persistence.snapshot_wal_bytes":
                float(p.SNAPSHOT_WAL_BYTES),
                "persistence.snapshot_every": float(p.snapshot_every)}

    def settle(self, timeout_s: float = 30.0) -> None:
        """Wait out a snapshot writer that is still running, so that
        its `snapshot_write` span reaches the tap before it closes."""
        if self.srv.persistence is not None:
            self.srv.persistence.wait_idle(timeout_s)

    def quiesce(self, timeout_s: float) -> Tuple[bool, float]:
        """Wait until the broker holds no eval that is ready, out with a
        worker or queued behind one of its own job (twice in a row: an
        eval in a worker's hands may enqueue another as it ends).
        Whether it went calm before `timeout_s` ran out, and the
        seconds it took. Blocked evals stay: a follow-up eval of an
        evicted job blocks on a full fleet by design."""
        t0 = time.perf_counter()
        calm = 0
        while calm < 2 and time.perf_counter() - t0 < timeout_s:
            st = self.srv.eval_broker.stats
            busy = st.total_ready + st.total_unacked + st.total_blocked
            calm = 0 if busy else calm + 1
            time.sleep(0.05)
        return calm >= 2, time.perf_counter() - t0

    @staticmethod
    def signatures() -> Dict[str, set]:
        """The compile keys the program's own recompile counter has seen
        (analysis/sanitizer): names the shapes a window met first."""
        from nomad_tpu.analysis.sanitizer import traces
        return traces.signatures()

    def worker_failures(self) -> int:
        return sum(w.stats["failed"] for w in self.srv.workers)

    def close(self) -> None:
        for part in (self.api, self.rpc, self.srv):
            if part is not None:
                try:
                    part.shutdown()
                except Exception as e:      # the result is already out
                    self.log(f"shutdown: {type(e).__name__}: {e}")
        shutil.rmtree(self.data_dir, ignore_errors=True)


def start_watchdog(limit_s: float) -> None:
    """A run that hangs would hold the chip: hard stop inside the limit."""
    import os
    t = threading.Timer(limit_s, lambda: os._exit(5))
    t.daemon = True
    t.start()
