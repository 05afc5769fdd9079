"""The server: state store + FSM apply + broker + plan pipeline + workers.

Reference semantics: nomad/server.go (NewServer:295, setupWorkers:1438),
nomad/fsm.go (the ~45 log-type dispatch collapses to the raft_apply
switch here), nomad/leader.go (establishLeadership:222 — broker/blocked/
plan-queue enablement, restoreEvals:496, reapFailedEvaluations:766),
nomad/heartbeat.go (TTL timers -> node down -> createNodeEvals,
node_endpoint.go:1318).

Round-1 consensus: a single-node raft shim (monotonic index + serialized
apply). The FSM surface is kept narrow and explicit so a replicated log
can replace `raft_apply` without touching callers.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..models import (
    Allocation, Evaluation, Job, Node,
    EVAL_STATUS_CANCELED, EVAL_STATUS_FAILED, EVAL_STATUS_PENDING,
    JOB_STATUS_PENDING, JOB_STATUS_RUNNING,
    JOB_TYPE_CORE, JOB_TYPE_SERVICE, JOB_TYPE_SYSTEM,
    NODE_STATUS_DOWN, NODE_STATUS_READY,
    TRIGGER_JOB_DEREGISTER, TRIGGER_JOB_REGISTER, TRIGGER_NODE_UPDATE,
)
from ..models.evaluation import (
    CORE_JOB_DEPLOYMENT_GC, CORE_JOB_EVAL_GC, CORE_JOB_FORCE_GC,
    CORE_JOB_JOB_GC, CORE_JOB_NODE_GC, TRIGGER_SCHEDULED,
)
from ..state import StateStore
from ..utils import metrics, stages
from ..utils.timetable import TimeTable
from .blocked_evals import BlockedEvals
from .deployment_watcher import (
    DeploymentsWatcher, fail_deployment, pause_deployment,
    promote_deployment,
)
from .drainer import NodeDrainer, drain_allocs
from .eval_broker import EvalBroker, FAILED_QUEUE
from .event_broker import EventBroker, events_from_apply
from .periodic import PeriodicDispatch
from .persistence import PLAN_ENTRIES
from .plan_applier import PlanApplier
from .plan_queue import PlanQueue
from .worker import Worker
from ..utils.locks import make_lock, make_rlock

CORE_JOB_PRIORITY = 200  # structs.go CoreJobPriority = 2 * JobMaxPriority

LOG = logging.getLogger("nomad_tpu.server")


@dataclass
class ServerConfig:
    num_schedulers: int = 2
    enabled_schedulers: tuple = ("service", "batch", "system")
    # this server's federation region (nomad/config.go Region); requests
    # stamped with a foreign region forward to that region's agent
    region: str = "global"
    # federation peers: region name -> that region's agent HTTP address
    # (the reference discovers via WAN serf; here configured)
    region_peers: dict = field(default_factory=dict)
    # ACL/namespace replication source (nomad/config.go
    # AuthoritativeRegion + ReplicationToken): non-authoritative
    # leaders replicate policies, GLOBAL tokens, and namespaces from it
    authoritative_region: str = ""
    replication_token: str = ""
    # max READY evals one worker drains into a single batched dispatch
    # (SURVEY §2.6 row 1; 1 disables batching). DEFAULT 1: measured on
    # real TPU at C2M scale, concurrent workers overlapping device
    # round trips (decorrelated solo dispatches) beat coalescing lanes
    # into one vmapped dispatch (BENCH r5: stream 10.0k/s solo vs
    # 6.5k/s batched — the mega-dispatch serializes lane host work
    # under the GIL). The gateway stays available for queue-depth
    # regimes where dispatch slots, not host time, are the bottleneck.
    eval_batch_size: int = 1
    # driver/config for injected connect proxy tasks (the reference
    # hardcodes docker+envoy, job_endpoint_hook_connect.go:23)
    connect_sidecar_driver: str = "docker"
    connect_sidecar_config: Optional[dict] = None
    # GC safepoints (server/worker.py): disable automatic CPython
    # collection and collect young gens between evals, keeping
    # collector pauses out of scheduling latency. Process-wide side
    # effect, so off by default; the CLI agent turns it on.
    gc_safepoints: bool = False
    heartbeat_ttl_s: float = 10.0
    # cluster rollup staleness (ISSUE 13): a node whose heartbeat
    # host-stats payload is older than this counts as a stale
    # heartbeat in the cluster.* series and drops out of the fleet
    # used-vs-allocated economics (its capacity still counts)
    stats_stale_after_s: float = 30.0
    failed_eval_unblock_delay_s: float = 60.0
    dev_mode: bool = True
    data_dir: str = ""              # empty == in-memory only
    # WAL entries between snapshots (1 GiB of WAL triggers one too:
    # server/persistence.py SNAPSHOT_WAL_BYTES)
    snapshot_every: int = 8192
    # columnar snapshot & cold-start recovery pipeline (ISSUE 8,
    # server/persistence.py + state/columnar.py):
    # write format-2 columnar snapshots (struct-of-arrays framed in
    # msgpack) instead of the legacy per-object dump; restore reads
    # BOTH formats regardless, so flipping this is always safe
    snapshot_columnar: bool = True
    # serialize snapshots on a background thread off an O(1) MVCC
    # store snapshot — maybe_snapshot only triggers, the applier never
    # blocks on a dump of a large store
    snapshot_background: bool = True
    # WAL durability: fsync appends (False matches the pre-r12
    # flush-only behavior — tests and benches stay fast); with fsync
    # on, wal_group_fsync pays ONE fsync per committed apply batch
    # (the raft FSM batch / dev-mode entry) instead of one per frame
    wal_fsync: bool = False
    wal_group_fsync: bool = True
    # GC cadence + retention (nomad/config.go *GCInterval/*GCThreshold)
    gc_interval_s: float = 60.0
    eval_gc_threshold_s: float = 3600.0
    job_gc_threshold_s: float = 4 * 3600.0
    node_gc_threshold_s: float = 24 * 3600.0
    deployment_gc_threshold_s: float = 3600.0
    # ACL subsystem (nomad/config.go ACLEnabled)
    acl_enabled: bool = False
    # autopilot dead-server cleanup (nomad/autopilot.go): a voter with
    # no replication contact for this long is removed from the member
    # set; 0 disables
    dead_server_cleanup_s: float = 60.0
    # lease TTL for derived vault tokens (vault.go ttl on CreateToken);
    # clients renew at ttl/2 via Node.RenewVaultToken
    vault_token_ttl_s: float = 3600.0
    # steady-state governor (governor/): accounting cadence, watermark
    # levels for the pressure gauges, and structure bounds. Levels are
    # deliberately high — backpressure is an overload valve, not a
    # scheduler tune
    governor_enabled: bool = True
    governor_interval_s: float = 1.0
    governor_broker_depth_high: int = 8192
    governor_plan_depth_high: int = 256
    governor_p99_high_ms: float = 1000.0
    # p99 watermark needs a WARM, populated latency reservoir before
    # it means anything — a fresh agent's first evals carry
    # multi-second JIT compiles that must not engage backpressure
    # (r6 e2e verify). Gates on observed LATENCIES, not uptime, and
    # MUST exceed Governor.P99_WINDOW (512): the gauge reads the most
    # recent 512 samples, so anything smaller opens the gauge while
    # the compile-era latencies still sit inside the p99 window
    governor_p99_min_samples: int = 640
    governor_version_debt_high: int = 100_000
    # byte watermark for early event-history shedding; the ring's own
    # count/byte caps are the hard bound, this is the soft one (0 =
    # disabled: never truncate below the ring's own caps)
    governor_event_bytes_high: int = 12 << 20
    # 0 = derive from the shape-LRU bound (2 caches x KERNEL_CACHE_MAX
    # + slack for jax's internal per-function caches)
    governor_kernel_cache_high: int = 0
    # device-resident node table (ops/device_table.py): scattered-row
    # debt that triggers the fold-to-rebuild reclaim (one contiguous
    # re-upload replacing the scatter history)
    governor_table_delta_debt_high: int = 200_000
    # backpressure escalation: when the broker's delayed/requeue heap
    # itself crosses this depth, the HTTP job-register path starts
    # returning 429 + Retry-After (0 disables)
    governor_broker_delayed_high: int = 16384
    # pipelined worker loop: eval N's ack-side bookkeeping overlaps
    # eval N+1's host phase, and the resident table's device scatter
    # is dispatched right after the snapshot fence
    worker_pipeline: bool = True
    # group-commit plan applier (plan_applier.py): max queued plans
    # drained into ONE overlay-aware verify pass + ONE raft entry +
    # ONE state-store transaction + ONE event flush. 1 restores the
    # one-entry-per-plan pipeline; the NOMAD_TPU_PLAN_GROUP=0 env
    # kill switch forces that at runtime (bisection)
    plan_group_max: int = 32
    # intra-group conflict demotions in the applier's 10s window above
    # this shrink the group bound (reclaim halves it; a clean streak
    # re-widens) instead of letting demoted plans thrash verify-retry
    # round trips
    governor_plan_group_conflict_high: int = 64
    # columnar reconcile engine (state/alloc_index.py +
    # scheduler/reconcile_columnar.py): the per-job struct-of-arrays
    # alloc index the reconciler's masks read. False disables index
    # maintenance and the schedulers fall back to the reference
    # per-alloc reconciler (NOMAD_TPU_COLUMNAR_RECONCILE=0 is the
    # runtime kill switch for bisection)
    reconcile_columnar: bool = True
    # bound on live per-job index entries (FIFO eviction)
    reconcile_index_max_jobs: int = 512
    # pending write-through deltas beyond this drop the entry — a cold
    # job nobody reconciles must not hoard a delta log; the next read
    # rebuilds dense
    reconcile_index_delta_max: int = 4096
    # total pending columnar-index delta debt across jobs: crossing it
    # folds the index back to dense rebuild (governor reclaim)
    governor_reconcile_index_debt_high: int = 65536
    # adaptive micro-batch eval dispatch (server/worker.py
    # MicroBatchGateway): concurrent evals' kernel requests accumulate
    # for up to this window and ship as ONE vmapped padded device call.
    # The live window adapts off the per-lane arrival-rate EWMA
    # (idle lanes dispatch immediately) and queue depth (see
    # governor_gateway_depth_high); on an accelerator backend the
    # base widens to half the measured round trip. 0 disables the gateway
    # entirely (exactly the pre-gateway dispatch path);
    # NOMAD_TPU_MICROBATCH=0 is the runtime kill switch
    gateway_window_us: int = 2000
    # occupancy trigger: a lane holding this many parked requests
    # fires without waiting out the window
    gateway_min_batch: int = 4
    # broker READY depth above which the gateway widens its window
    # (occupancy over per-eval latency while a backlog exists; decays
    # back once the queue drains). The governor's READY-depth
    # watermark reclaim also widens it directly
    governor_gateway_depth_high: int = 512
    # startup calibration probe (ops/select.calibrate_cost_model):
    # measure the solo + batched dispatch arms at the restored table
    # shape and seed the dispatch cost model, so batched lanes are
    # cost-favored from the first dispatch instead of after 3+
    # organic samples. Pays two XLA compiles at start, so off by
    # default; the CLI agent and the benches turn it on
    dispatch_calibration: bool = False
    # batched columnar preemption (scheduler/preemption.py): victim
    # selection for all candidate nodes runs as ONE struct-of-arrays
    # pass + vectorized greedy instead of a per-node Python Preemptor.
    # False restores the per-node reference path everywhere
    # (NOMAD_TPU_COLUMNAR_PREEMPT=0 is the runtime kill switch)
    preempt_columnar: bool = True
    # candidate-matrix row cap: a node with more eligible candidate
    # allocs than this takes the per-node reference path instead of
    # padding every other node's matrix row to its width
    preempt_rows_max: int = 4096
    # victim-set memo bound (NodeTable.preempt_cache); crossing it
    # clears the memo wholesale — the governor watermark below
    # reclaims earlier and gradually
    preempt_cache_max: int = 200_000
    # watermark on live victim-memo entries (each pins a live-alloc
    # row + its victim allocs); crossing it drops the memo via the
    # governor reclaim (preemption.victim_cache_entries gauge)
    governor_preempt_cache_high: int = 150_000
    # compiled feasibility engine (scheduler/feasible_compiler.py +
    # state/node_attr_index.py, ISSUE 17): constraint trees compile to
    # predicate programs over interned node-attribute columns; False
    # restores the per-node scalar checks everywhere
    # (NOMAD_TPU_COLUMNAR_FEAS=0 is the runtime kill switch)
    feas_columnar: bool = True
    # distinct-value cap per interned attribute column: a column
    # exceeding it (near-unique values — ids, addresses) flags
    # overflow and its constraints take the scalar path, keeping
    # verdict LUTs small
    feas_intern_max_values: int = 4096
    # compiled-program/mask cache bound (FIFO past it); the governor
    # watermark below reclaims masks earlier and keeps intern tables
    feas_mask_cache_max: int = 256
    # watermark on live mask-cache entries (each pins bool[N] rows per
    # static check); crossing it drops cached masks via the governor
    # reclaim but KEEPS the intern tables — the next eval rebuilds
    # masks from columns, not columns from nodes
    governor_feas_mask_cache_high: int = 192
    # residue-compiled feasibility (ISSUE 20): CSI-claim/quota/
    # preferred-node residue rides the device-resident mask as a
    # sparse per-eval scatter (the FeasMaskStore token survives
    # residue mutations), device inventory checks only flagged rows,
    # and spread/distinct scoring inputs build vectorized over the
    # interned columns; False restores the dense re-upload + per-node
    # walks (NOMAD_TPU_FEAS_RESIDUE=0 is the runtime kill switch)
    feas_residue: bool = True
    # watermark on accumulated residue-scatter rows atop the parked
    # device masks; crossing it folds the FeasMaskStore (drops parked
    # entries) so the next eval re-parks a fresh combined mask instead
    # of compounding per-eval scatter debt
    governor_feas_residue_high: int = 262_144
    # eval flight recorder (nomad_tpu/trace/): always-on per-eval span
    # tracing — enqueue -> gateway -> kernel -> group commit -> ack —
    # with a byte-bounded completed-trace ring, pinned tail exemplars,
    # and per-stage percentile reservoirs. Surfaced at
    # /v1/operator/trace and `nomad operator trace [-o chrome]`;
    # NOMAD_TPU_TRACE=0 is the kill switch
    trace_ring_bytes: int = 4 << 20
    # pinned exemplar slots: evals whose full enqueue->ack latency
    # clears the adaptive threshold keep their whole span tree plus a
    # governor-gauge snapshot (worst-K retention; drift findings
    # auto-pin the current set)
    trace_exemplar_slots: int = 8
    # promotion threshold as a percent of the governor-tracked
    # full-latency p99 (100 = promote anything at/above p99)
    trace_exemplar_threshold_pct: float = 100.0
    # retained telemetry collector (nomad_tpu/telemetry/, ISSUE 11):
    # background sampling cadence for the history ring behind
    # /v1/operator/telemetry, /v1/operator/flatness, and `nomad
    # operator top`. 0 disables the collector entirely (snapshot-only
    # /v1/metrics, flatness route reports disabled);
    # NOMAD_TPU_TELEMETRY=0 is the runtime kill switch
    telemetry_sample_interval_s: float = 1.0
    # history ring depth: slots per series (struct-of-arrays float64
    # columns; with the 256-series cap the ring's hard byte ceiling is
    # slots x 256 x 8 bytes — 1 MiB at the default 512)
    telemetry_ring_slots: int = 512
    # mesh-sharded resident node table (parallel/sharded_table.py):
    # keep the hot columns sharded-resident across evals when mesh
    # routing is active (NOMAD_TPU_MESH). Off falls back to the
    # capacity-only per-eval upload path; NOMAD_TPU_MESH_RESIDENT=0 is
    # the runtime kill switch and wins over this knob
    mesh_resident: bool = True
    # scattered-row debt on the mesh-resident table that triggers the
    # fold-to-rebuild reclaim (one contiguous sharded re-upload
    # replacing the scatter history) — the mesh analog of
    # governor_table_delta_debt_high
    mesh_reshard_debt_high: int = 500_000
    # runtime deadlock & race sanitizer (analysis/race.py via the
    # utils/locks.py factory, ISSUE 14): a lock held at/beyond this
    # long keeps a worst-K exemplar (stack at release) in the `locks`
    # block of /v1/operator/governor — the worst holders are exactly
    # the sites that serialize the fleet under contention. The shims
    # themselves only exist for locks constructed under
    # NOMAD_TPU_RACE=1; these knobs tune the process-global monitor
    race_lock_hold_warn_ms: float = 50.0
    # worst-holder exemplar slots retained (sorted by hold time)
    race_exemplar_slots: int = 8
    # findings ring bound (lock-order cycles, self-deadlocks,
    # unguarded mutations) — dedup by site keeps this small anyway
    race_max_findings: int = 256
    # scenario matrix + fault injection (nomad_tpu/chaos/, ISSUE 15):
    # default seed for injected fault schedules when a chaos cell
    # doesn't pin its own (0 = the matrix derives one per cell); the
    # hook points themselves cost one module-bool read per site and
    # are inert until a cell installs a FaultInjector
    chaos_seed: int = 0
    # bound within which cluster.nodes_down / stale_heartbeats must
    # reflect an injected failure — the failure-visibility invariant's
    # deadline (chaos/invariants.py)
    chaos_visibility_bound_s: float = 15.0
    # distributed scheduler plane (server/follower_sched.py, ISSUE 16):
    # when clustered, followers run worker pools against their LOCAL
    # replicated store, dequeuing evals from the leader's broker over
    # RPC and submitting plans back for leader-only verify/commit.
    # Off = leader schedules alone (the pre-plane topology);
    # NOMAD_TPU_FOLLOWER_SCHED=0 is the runtime kill switch
    follower_sched: bool = True
    # leader-side lease on a remotely dequeued eval: a dead follower's
    # evals return to READY after this long (with zero re-enqueue
    # delay — the follower failed, not the eval), instead of waiting
    # out the broker's full 60 s unack timer
    follower_lease_s: float = 30.0
    # follower-side snapshot fence budget: how long a follower worker
    # waits for local raft catch-up to reach the eval's modify index
    # before NACKing it back (a lagging replica must not schedule from
    # the past, and must not silently drop the eval either)
    follower_fence_timeout_s: float = 5.0
    # remote worker pool size per follower
    follower_max_remote: int = 2
    # batched write ingest (server/ingest.py, ISSUE 19): job registers,
    # client alloc-status updates and desired-transition writes that
    # arrive while a raft apply is in flight park and land as ONE
    # `ingest_batch` entry / store transaction / event flush. Entries
    # per batch cap:
    ingest_batch_max: int = 64
    # coalescing window (microseconds) a lone streaming write waits for
    # companions; governor reclaim halves it under queue pressure, a
    # clean streak re-widens it. <0 disables the gateway entirely (the
    # one-entry-per-write path); NOMAD_TPU_INGEST_BATCH=0 is the
    # runtime kill switch
    ingest_window_us: float = 200.0
    # queued-write depth at which check_admission sheds new writes with
    # 429/Retry-After BEFORE body decode (the byte watermark derives
    # from this: depth x 64 KiB)
    ingest_queue_high: int = 256
    # governor watermark on ingest.queue_depth that fires the
    # shrink_window reclaim (distinct from the shed watermark above —
    # the governor reclaims well before the edge starts refusing)
    governor_ingest_queue_high: int = 64


class Server:
    def __init__(self, config: Optional[ServerConfig] = None):
        self.config = config or ServerConfig()
        self.store = StateStore()
        self.store.alloc_index.enabled = self.config.reconcile_columnar
        self.store.alloc_index.max_jobs = \
            self.config.reconcile_index_max_jobs
        self.store.alloc_index.delta_max = \
            self.config.reconcile_index_delta_max
        # batched columnar preemption knobs (module-level, the
        # store.alloc_index idiom — the scheduler has no ServerConfig)
        from ..scheduler import preemption as _preemption
        _preemption.configure(columnar=self.config.preempt_columnar,
                              rows_max=self.config.preempt_rows_max,
                              cache_max=self.config.preempt_cache_max)
        # compiled feasibility knobs (module-level, same idiom); the
        # env kill switch NOMAD_TPU_COLUMNAR_FEAS wins inside enabled()
        from ..scheduler import feasible_compiler as _feas
        _feas.configure(
            enabled=self.config.feas_columnar,
            intern_max_values=self.config.feas_intern_max_values,
            mask_cache_max=self.config.feas_mask_cache_max,
            residue=self.config.feas_residue)
        self.store.attr_index.enabled = self.config.feas_columnar
        # mesh-sharded residency knob (module-level, same idiom — the
        # process-wide ShardedSelect has no ServerConfig); the env kill
        # switch NOMAD_TPU_MESH_RESIDENT wins inside resident_enabled()
        from ..parallel import sharded_table as _sharded_table
        _sharded_table.configure(resident=self.config.mesh_resident)
        # runtime race sanitizer knobs (module-level, same idiom —
        # the lock shims are process-global)
        from ..analysis import race as _race
        _race.configure(
            hold_warn_ms=self.config.race_lock_hold_warn_ms,
            exemplar_slots=self.config.race_exemplar_slots,
            max_findings=self.config.race_max_findings)
        # chaos fault-injection knobs (module-level, same idiom — the
        # injector hook points are process-global; ISSUE 15)
        from ..chaos import faults as _chaos_faults
        _chaos_faults.configure(
            seed=self.config.chaos_seed,
            visibility_bound_s=self.config.chaos_visibility_bound_s)
        # RLock: FSM appliers can nest (e.g. a node-register unblocking a
        # blocked eval re-enters raft_apply on the same thread)
        self._raft_l = make_rlock()
        self._raft_index = 10
        self.eval_broker = EvalBroker()
        self.eval_broker.on_superseded = self.cancel_evals
        # backpressure escalation threshold lives on the broker even
        # with the governor off — the HTTP register path reads it
        self.eval_broker.delayed_depth_high = \
            self.config.governor_broker_delayed_high
        self.blocked_evals = BlockedEvals(self._unblock_enqueue)
        self.plan_queue = PlanQueue()
        self.plan_applier = PlanApplier(self.plan_queue, self)
        # distributed scheduler plane (ISSUE 16): the lease table is
        # the leader-side half (remote-dequeue leases + cluster_sched
        # counters, empty on non-leaders); the follower half is built
        # in attach_raft — dev-mode servers never construct one
        from .follower_sched import EvalLeaseTable
        self.eval_leases = EvalLeaseTable(self)
        self.follower_sched = None
        self.time_table = TimeTable()
        self.periodic = PeriodicDispatch(self)
        self.deployments_watcher = DeploymentsWatcher(self)
        self.node_drainer = NodeDrainer(self)
        self.events = EventBroker()
        from .event_sink import EventSinkManager
        self.event_sinks = EventSinkManager(self)
        # adaptive micro-batch eval dispatch (ISSUE 7): one gateway per
        # server — every worker's (and every lane thread's) kernel
        # dispatches coalesce here. window=0 and the env kill switch
        # both mean NO gateway object, so the worker path degenerates
        # exactly to the pre-gateway one
        import os as _os
        self.gateway = None
        if self.config.gateway_window_us > 0 and \
                _os.environ.get("NOMAD_TPU_MICROBATCH", "1") \
                not in ("0", "off"):
            from .worker import MicroBatchGateway
            self.gateway = MicroBatchGateway(
                window_us=self.config.gateway_window_us,
                min_batch=self.config.gateway_min_batch,
                depth_fn=lambda: self.eval_broker.stats.total_ready,
                depth_high=self.config.governor_gateway_depth_high)
        # batched write ingest (ISSUE 19): the write-side twin of the
        # gateway above — same no-object degeneration under window<0
        # or the env kill switch, so every write takes the unchanged
        # one-raft-entry-per-object path
        self.ingest = None
        from .ingest import IngestGateway, ingest_batch_enabled
        if self.config.ingest_window_us >= 0 and ingest_batch_enabled():
            self.ingest = IngestGateway(
                self,
                batch_max=self.config.ingest_batch_max,
                window_us=self.config.ingest_window_us,
                queue_high=self.config.ingest_queue_high)
        self.governor = None
        if self.config.governor_enabled:
            from ..governor import Governor
            self.governor = Governor(
                interval_s=self.config.governor_interval_s)
            self._register_governor_gauges()
        # eval flight recorder (ISSUE 9): the process-wide tracer is
        # configured from this server's knobs and wired to its
        # governor — the exemplar threshold tracks the FULL-latency
        # p99 (queue wait included: what the eval experienced), each
        # promoted exemplar snapshots the gauge rows, and a drift
        # finding that names a suspect structure auto-pins the current
        # exemplar set (the ROADMAP "automatic operator debug capture"
        # item, done at the trace layer)
        from ..trace import tracer as _flight
        self.tracer = _flight
        _flight.configure(
            ring_bytes=self.config.trace_ring_bytes,
            exemplar_slots=self.config.trace_exemplar_slots,
            threshold_pct=self.config.trace_exemplar_threshold_pct)
        self._tracer_fns = None
        # one gauge-snapshot closure serves BOTH the tracer's exemplar
        # snapshots and the telemetry collector's per-slot sampling —
        # the two consumers must never silently diverge on how gauge
        # rows are read
        gauge_snapshot_fn = None
        if self.governor is not None:
            gov = self.governor
            gauge_snapshot_fn = lambda g=gov: {  # noqa: E731
                r["name"]: r["value"] for r in g.registry.rows()}
            _flight.threshold_fn = \
                lambda g=gov: g.latency_percentile_ms(99)
            _flight.gauge_fn = gauge_snapshot_fn
            # remembered so shutdown can detach THESE closures (and
            # only these — a newer server may have rebound them):
            # the module-global tracer outlives this server, and the
            # lambdas would otherwise pin the whole dead governor
            # graph (gauge closures reach broker/applier/store)
            self._tracer_fns = (_flight.threshold_fn, _flight.gauge_fn)
            gov.drift_hooks.append(self._auto_pin_exemplars)
        # retained telemetry collector (ISSUE 11): history rings over
        # governor gauges, counter rates, stage percentile reservoirs,
        # device economics, and RSS — the instrument behind
        # /v1/operator/telemetry, /v1/operator/flatness, and `nomad
        # operator top`. Kill switch (env or interval=0) builds no
        # collector: /v1/metrics degenerates to snapshot-only
        from ..telemetry import TelemetryCollector
        from ..telemetry import enabled as _telemetry_enabled
        self.telemetry = None
        if _telemetry_enabled() and \
                self.config.telemetry_sample_interval_s > 0:
            gov = self.governor
            self.telemetry = TelemetryCollector(
                interval_s=self.config.telemetry_sample_interval_s,
                slots=self.config.telemetry_ring_slots,
                gauges_fn=gauge_snapshot_fn,
                latency_fn=(None if gov is None
                            else gov.latency_percentile_ms),
                stage_fn=_flight.stage_percentiles,
                # device-mirror residency + the cluster.* rollup
                # (ISSUE 13) read through self (the table cache is
                # replaced on snapshot restore)
                extra_fn=self._telemetry_extra)
        self.workers: List[Worker] = []
        # node TTL timers: one heap on one thread (server/heartbeat.py)
        from .heartbeat import HeartbeatTimers
        self._heartbeats = HeartbeatTimers(self._invalidate_heartbeat)
        # per-node host-stats payloads carried by heartbeats (ISSUE
        # 13): node_id -> {payload..., received_at}; folded into the
        # cluster.* rollup by cluster_stats(), pruned when the node
        # record disappears
        self._node_stats: Dict[str, dict] = {}
        self._node_stats_l = make_lock()
        self._leader = False
        self._member_l = make_lock()   # join/leave RMW serialization
        # serializes enforced (-check-index) registrations: the CAS
        # check and the apply must not interleave across HTTP threads
        self._register_l = make_lock()
        self._acl_cache: Dict = {}      # (policies, index) -> compiled ACL
        self.raft = None                # multi-server consensus (raft.py)
        self.swim = None                # peer failure detection (swim.py)
        # thread-local: set on the FSM applier thread while an applier
        # runs, so nested raft_apply side effects are detected per
        # thread — an instance-wide flag would make a concurrent client
        # write on another thread look nested and silently drop it
        # (r3 advisor, medium)
        self._apply_tl = threading.local()

        # restore persisted state AFTER all subsystems exist: WAL replay
        # drives the same FSM appliers (broker/blocked are disabled until
        # leadership, so replay has no scheduling side effects, and no
        # change events publish — replay is not new history)
        self.persistence = None
        self.cold_start_stats: Dict[str, float] = {}
        if self.config.data_dir:
            from .persistence import Persistence
            self.persistence = Persistence(
                self.config.data_dir, self.config.snapshot_every,
                columnar=self.config.snapshot_columnar,
                background=self.config.snapshot_background,
                wal_fsync=self.config.wal_fsync,
                wal_group_fsync=self.config.wal_group_fsync)
            self.persistence.extra_provider = lambda: {
                "time_table": self.time_table.dump()}
            t0 = time.perf_counter()
            highest, entries = self.persistence.restore_into(self.store)
            restore_s = time.perf_counter() - t0
            self.time_table.restore(
                self.persistence.restored_extra.get("time_table", []))
            self._raft_index = max(self._raft_index, highest)
            # cold-start pipeline (ISSUE 8): prime the resident node
            # table ONCE at the restored index — from the snapshot's
            # decoded columns when the format provides them — then let
            # the device H2D upload overlap the WAL tail replay below;
            # the first eval after recovery rides the delta path, and
            # the eagerly rebuilt alloc index (state/store.py restore)
            # keeps reconcile.index_rebuilds at zero
            table_build_s = 0.0
            if highest > 0:
                t0 = time.perf_counter()
                self.store.table_cache.prime(self.store.snapshot(),
                                             self.store.pop_cold_columns())
                table_build_s = time.perf_counter() - t0
                threading.Thread(target=self.store.table_cache
                                 .prefetch_device, daemon=True,
                                 name="table-prefetch").start()
            t0 = time.perf_counter()
            replayed = self._replay_entries(entries, highest)
            wal_replay_s = time.perf_counter() - t0
            self.cold_start_stats = {
                "restore_s": restore_s,
                "table_build_s": table_build_s,
                "wal_replay_s": wal_replay_s,
                "wal_entries_replayed": float(replayed),
                "snapshot_format": float(
                    self.persistence.stats["restore_format"]),
            }
        # event history starts HERE: restore/replay publish no events,
        # so sink progress at or below this floor has a proven gap
        self.events.epoch_floor = self._raft_index
        if self.persistence is not None:
            # measured per-(arm, n_pad) dispatch costs persist next to
            # the WAL snapshot (ISSUE 7): a restarted server routes and
            # batches off its last life's measurements instead of
            # re-learning from cold (first live sample per shape is
            # dropped — it pays this process's XLA compile)
            from ..ops.select import cost_model
            seeds = self.persistence.load_cost_model()
            if seeds:
                loaded = cost_model.load_snapshot(seeds)
                LOG.info("cost model restored: %d measured shapes",
                         loaded)
            self.persistence.cost_model_provider = cost_model.snapshot
            if self.governor is not None:
                self._register_persistence_gauges()

    # -- lifecycle -----------------------------------------------------
    def attach_raft(self, rpc_server, peers, self_addr: str = "") -> None:
        """Join a multi-server cluster: the raft node drives leadership
        (nomad/server.go setupRaft + leader.go monitorLeadership)."""
        from .raft import RaftNode
        self.raft = RaftNode(self, self_addr or rpc_server.addr,
                             list(peers), data_dir=self.config.data_dir)
        rpc_server.methods.update(self.raft.rpc_methods())
        rpc_server.raft = self.raft
        # reconcile REPLICATED membership over the static boot config:
        # a restarted server must adopt the grown/shrunk voter set its
        # WAL/snapshot recorded (and an evicted server must come back
        # inert), or its quorum math is wrong from the first election
        members = self.store.server_members()
        if members:
            self.raft.update_members(members)
        # peer-to-peer failure detection (SWIM; nomad/serf.go): every
        # member probes, not just the leader's replication threads
        from .swim import SwimDetector
        self.swim = SwimDetector(self)
        # distributed scheduler plane (ISSUE 16): the remote-dequeue
        # verb surface rides the same RPC transport raft does, and the
        # follower worker pool is built here — started by start(),
        # inert whenever this server is (or becomes) the leader
        from .follower_sched import FollowerScheduler, rpc_handlers
        rpc_server.methods.update(rpc_handlers(self))
        self.follower_sched = FollowerScheduler(self)

    def start(self) -> None:
        if self.raft is None:
            self.establish_leadership()
        else:
            self.raft.start()
            if self.swim is not None:
                self.swim.start()
            if self.follower_sched is not None:
                self.follower_sched.start()
        self.plan_applier.start()
        if self.ingest is not None:
            self.ingest.start()
        for i in range(self.config.num_schedulers):
            w = Worker(self, list(self.config.enabled_schedulers)
                       + [JOB_TYPE_CORE], wid=i)
            self.workers.append(w)
            w.start()
        self._reaper = threading.Thread(target=self._reap_failed_evals,
                                        daemon=True, name="eval-reaper")
        self._reaper.start()
        self._gc_ticker = threading.Thread(target=self._schedule_periodic_gc,
                                           daemon=True, name="gc-ticker")
        self._gc_ticker.start()
        self._stats_ticker = threading.Thread(target=self._emit_stats,
                                              daemon=True,
                                              name="stats-ticker")
        self._stats_ticker.start()
        self._volume_watcher = threading.Thread(target=self._watch_volumes,
                                                daemon=True,
                                                name="volume-watcher")
        self._volume_watcher.start()
        if self.governor is not None:
            self.governor.start()
        if self.telemetry is not None:
            self.telemetry.start()
        if self.config.dispatch_calibration:
            # seed the dispatch cost model at the restored table shape
            # BEFORE traffic: the solo and batched arms both carry
            # measured numbers from the first organic dispatch (no
            # nodes yet == nothing to calibrate; benches with
            # programmatic node seeding call calibrate_cost_model
            # themselves after seeding)
            n = self.store.node_count()
            if n >= 8:
                from ..ops.select import calibrate_cost_model
                calibrate_cost_model(
                    n, lanes=self.config.gateway_min_batch)

    def _register_governor_gauges(self) -> None:
        """Wire every long-lived structure into the governor's
        accounting registry, with watermark policies and targeted
        reclamation where a bound exists (ISSUE r6 tentpole; the
        reference keeps these flat via core_sched GC + EmitStats)."""
        from ..governor import WatermarkPolicy
        from ..ops.select import (clear_kernel_caches,
                                  kernel_cache_entries)
        cfg = self.config
        gov = self.governor
        broker = self.eval_broker   # .stats is REPLACED on flush —
        # gauges must read through the broker, never a captured stats

        # broker queues: depth gauges; READY depth is the admission
        # signal (backpressure sheds enqueues, workers shrink lanes).
        # With the micro-batch gateway live, the watermark reclaim
        # WIDENS its dispatch window — under a backlog, batch occupancy
        # beats per-eval dispatch latency (ISSUE 7)
        gov.register("broker.ready", lambda: broker.stats.total_ready,
                     WatermarkPolicy(cfg.governor_broker_depth_high,
                                     pressure=True),
                     reclaim=(self.gateway.widen_window
                              if self.gateway is not None else None))
        gov.register("broker.unacked",
                     lambda: broker.stats.total_unacked)
        gov.register("broker.waiting",
                     lambda: broker.stats.total_waiting)
        gov.register("broker.shed", lambda: broker.stats.total_shed,
                     suspect=False)  # monotone counter, not a structure
        gov.register("broker.redelivered",
                     lambda: broker.stats.total_redelivered,
                     suspect=False)  # monotone counter too
        gov.register("blocked_evals.blocked",
                     self.blocked_evals.blocked_count)
        gov.register("plan_queue.depth", self.plan_queue.depth,
                     WatermarkPolicy(cfg.governor_plan_depth_high,
                                     pressure=True))

        # sampled service p99 from the workers' latency reservoir: the
        # primary backpressure gauge (SOAK_r05: p99 drifted 69->208 ms).
        # The gauge reports 0 until the reservoir holds enough REAL
        # latencies — gating on observed evals, not sampler uptime, so
        # an idle-then-cold-start agent can't trip it on JIT compiles
        def p99_gauge():
            if gov.latency_samples() < cfg.governor_p99_min_samples:
                return 0.0
            # recent_: a reservoir with no fresh latencies reads 0, so
            # an engaged-backpressure idle period can't latch the
            # watermark shut on frozen samples
            return gov.recent_p99_ms()
        # suspect=False: this IS the perf signal, not a structure
        # whose growth could explain it
        gov.register("service.p99_ms", p99_gauge,
                     WatermarkPolicy(cfg.governor_p99_high_ms,
                                     pressure=True),
                     unit="ms", suspect=False)

        # event broker: the ring enforces its own count+byte caps on
        # publish (the hard bound). The governor watermark is the SOFT
        # byte bound — set BELOW the ring's max_bytes so it can only
        # fire on genuine payload-byte pressure, never sit permanently
        # 'over' on a legitimately full ring of small events
        gov.register("event_broker.events", self.events.buffered_events)
        if cfg.governor_event_bytes_high > 0:
            gov.register("event_broker.bytes",
                         self.events.buffered_bytes,
                         WatermarkPolicy(cfg.governor_event_bytes_high),
                         reclaim=lambda: self.events.truncate(0.5),
                         unit="bytes")
        else:
            gov.register("event_broker.bytes",
                         self.events.buffered_bytes, unit="bytes")

        # state store: uncompacted layer-overlay debt (the version
        # chains the r5 soak showed growing between snapshots) with
        # fold compaction as the reclaim; changelog is already bounded
        # force=True: crossing the watermark IS the escalation — the
        # per-table proportional fold floor must not veto every table
        # and leave the reclaim a permanent no-op while debt grows
        gov.register("state.version_debt", self.store.version_debt,
                     WatermarkPolicy(cfg.governor_version_debt_high),
                     reclaim=lambda: self.store.compact(min_tip=1024,
                                                        force=True))
        gov.register("state.changelog",
                     lambda: self.store.changelog_stats()["len"])
        # the log's trims, one a publish past CHANGELOG_MAX: monotone,
        # never a drift suspect (one series: the ring is near MAX_SERIES)
        gov.register("state.changelog_trims",
                     lambda: self.store.changelog_stats()["trims"],
                     suspect=False)
        gov.register("state.allocs",
                     lambda: len(self.store._root.table("allocs")))
        gov.register("state.evals",
                     lambda: len(self.store._root.table("evals")))

        # JIT kernel caches (ops/select.py): the shape-LRUs bound
        # themselves at KERNEL_CACHE_MAX each; the watermark (derived
        # from that bound unless overridden, so NOMAD_TPU_KERNEL_CACHE_MAX
        # retunes both together) alarms on jax's unbounded internal
        # per-function caches, where the break-glass full clear is the
        # only reclaim
        from ..ops.select import KERNEL_CACHE_MAX
        kc_high = cfg.governor_kernel_cache_high or \
            (2 * KERNEL_CACHE_MAX + 512)
        gov.register("kernel_cache.entries", kernel_cache_entries,
                     WatermarkPolicy(kc_high),
                     reclaim=clear_kernel_caches)

        # resident-table identity memos (ops/tables.py): FIFO-bounded,
        # but accounted — every entry pins a resources graph
        from ..ops.tables import BUILD_STATS, resource_memo_len
        gov.register("node_table.resource_memo", resource_memo_len)

        # device-resident node table (ops/device_table.py): scattered-
        # row debt with fold-to-rebuild as the reclaim — when the
        # scatter history since the last contiguous upload crosses the
        # watermark, one full re-upload replaces it and resets the
        # delta log. Gauges read through self.store: the table cache
        # is REPLACED on snapshot restore (store.py), so captured
        # references would go stale
        gov.register("node_table.delta_debt",
                     lambda: self.store.table_cache.device_delta_debt(),
                     WatermarkPolicy(cfg.governor_table_delta_debt_high),
                     reclaim=lambda: self.store.table_cache.fold_device())
        gov.register("node_table.delta_log",
                     lambda: self.store.table_cache.device_delta_log_len())
        gov.register("node_table.full_builds",
                     lambda: BUILD_STATS["full_builds"], suspect=False)
        gov.register("node_table.delta_refreshes",
                     lambda: BUILD_STATS["delta_refreshes"],
                     suspect=False)

        # mesh-sharded resident node table (parallel/sharded_table.py):
        # device count, sharded residency footprint, and the reshard /
        # delta-scatter traffic split — `mesh.reshard_uploads` flat
        # across a warm eval run IS the zero-reupload steady state. All
        # read through the process-wide snapshot (empty dict -> 0 while
        # no mesh dispatcher exists).
        # The scattered-row debt carries the watermark, with a
        # contiguous sharded re-upload as the reclaim (the mesh analog
        # of node_table.delta_debt's fold-to-rebuild)
        from ..ops.select import mesh_stats_snapshot

        def _mesh(key):
            return lambda: float(mesh_stats_snapshot().get(key, 0) or 0)

        gov.register("mesh.devices", _mesh("devices"), suspect=False)
        gov.register("mesh.resident_bytes_per_device",
                     _mesh("resident_bytes_per_device"))
        gov.register("mesh.reshard_uploads", _mesh("reshard_uploads"),
                     suspect=False)
        gov.register("mesh.delta_scatters", _mesh("delta_scatters"),
                     suspect=False)
        gov.register("mesh.resident_hits", _mesh("resident_hits"),
                     suspect=False)
        gov.register("mesh.reshard_debt",
                     lambda: self.store.table_cache.mesh_reshard_debt(),
                     WatermarkPolicy(cfg.mesh_reshard_debt_high),
                     reclaim=lambda: self.store.table_cache.fold_mesh())

        # backpressure escalation (ROADMAP open item): the delayed/
        # requeue heap depth — when admission deferral itself backs up,
        # the HTTP register path starts shedding with 429s
        gov.register("broker.delayed_depth", broker.delayed_depth)

        # group-commit plan applier (plan_applier.py): group sizing and
        # intra-group conflict visibility. The conflict gauge reads a
        # sliding 10s window (a monotone total would latch the
        # watermark over forever); its reclaim SHRINKS the group bound
        # so optimistic siblings stop trampling each other, and the
        # applier re-widens after a clean streak
        applier = self.plan_applier
        gov.register("plan_group.size", applier.mean_group_size,
                     suspect=False)
        gov.register("plan_group.conflict_retries",
                     applier.conflict_pressure,
                     WatermarkPolicy(
                         cfg.governor_plan_group_conflict_high),
                     reclaim=applier.shrink_group_bound, suspect=False)
        gov.register("plan_group.singleton_fallbacks",
                     lambda: applier.stats["singleton_fallbacks"],
                     suspect=False)

        # cross-eval engine host-phase reuse (scheduler/stack.py):
        # bounded keyed cache of per-(job, task-group) static state
        from ..scheduler.stack import engine_cache_entries
        gov.register("engine_cache.entries", engine_cache_entries)

        # columnar reconcile engine (state/alloc_index.py): index
        # sizing, dense rebuilds, the tasks_updated memo hit rate, and
        # pending write-through delta debt with fold-to-rebuild as the
        # reclaim. Gauges read through self.store — the cache is
        # replaced on snapshot restore
        from ..scheduler.stack import tasks_updated_hit_rate
        gov.register("reconcile.index_rows",
                     lambda: self.store.alloc_index.rows())
        gov.register("reconcile.index_rebuilds",
                     lambda: self.store.alloc_index.stats["rebuilds"],
                     suspect=False)
        gov.register("reconcile.tasks_updated_hit_rate",
                     tasks_updated_hit_rate, unit="ratio",
                     suspect=False)
        gov.register("reconcile.index_debt",
                     lambda: self.store.alloc_index.debt(),
                     WatermarkPolicy(
                         cfg.governor_reconcile_index_debt_high),
                     reclaim=lambda: self.store.alloc_index.fold())

        # batched columnar preemption (scheduler/preemption.py, ISSUE
        # 10): candidate-matrix volume, cross-eval victim-memo traffic,
        # and dirty-row invalidations — all monotone, never drift
        # suspects. The memo SIZE gauge carries the watermark: every
        # entry pins a live-alloc row list plus its victim allocs, so
        # a churning fleet must not let it grow to the hard
        # preempt_cache_max clear-all; reads go through self.store
        # (the table cache is replaced on snapshot restore)
        from ..scheduler.preemption import PREEMPT_STATS as _ps
        gov.register("preemption.candidate_rows",
                     lambda: _ps["candidate_rows"], suspect=False)
        gov.register("preemption.victim_cache_hits",
                     lambda: _ps["cache_hits"], suspect=False)
        gov.register("preemption.cache_invalidations",
                     lambda: _ps["invalidations"], suspect=False)
        gov.register("preemption.victim_cache_entries",
                     lambda: self.store.table_cache.preempt_cache_len(),
                     WatermarkPolicy(cfg.governor_preempt_cache_high),
                     reclaim=lambda:
                     self.store.table_cache.clear_preempt_cache())

        # compiled feasibility engine (scheduler/feasible_compiler.py,
        # ISSUE 17): intern-table volume, cached mask count, and the
        # steady-state hit rate. The mask-entry gauge carries the
        # watermark: each entry pins bool[N] rows per static check, so
        # the reclaim drops MASKS only — intern tables survive (the
        # next eval rebuilds masks from columns in one np.take, not
        # columns from an O(N) node walk). Reads go through
        # self.store.attr_index (replaced on snapshot restore); the
        # hit rate and recompile count are module-level like the
        # preemption stats
        from ..scheduler import feasible_compiler as _feas_mod
        gov.register("feas.intern_values",
                     lambda: self.store.attr_index.gauge_stats()
                     ["intern_values"], suspect=False)
        gov.register("feas.mask_cache_entries",
                     lambda: self.store.attr_index.gauge_stats()
                     ["mask_cache_entries"],
                     WatermarkPolicy(cfg.governor_feas_mask_cache_high),
                     reclaim=lambda: self.store.attr_index.drop_masks())
        gov.register("feas.mask_cache_hit_rate", _feas_mod.hit_rate,
                     unit="ratio", suspect=False)
        gov.register("feas.recompiles",
                     lambda: _feas_mod.stats()["recompiles"],
                     suspect=False)

        # residue-compiled feasibility (ISSUE 20): token survival vs
        # invalidation counts how often the device-resident combined
        # mask outlives a CSI/preferred-node mutation (survival = the
        # eval shipped a sparse residue scatter instead of a dense
        # re-upload). The residue-rows gauge carries the watermark:
        # accumulated scatter rows atop parked masks are debt, and the
        # reclaim FOLDS the FeasMaskStore — parked entries drop, the
        # next eval re-parks a fresh combined mask (fold is safe
        # mid-wave: residue is applied per-eval on a copy, never
        # stored). spread_score_evals counts vectorized scoring-input
        # builds (ops/spread.py)
        from ..ops import spread as _spread_mod
        gov.register("feas.token_survivals",
                     lambda: _feas_mod.stats()["token_survivals"],
                     suspect=False)
        gov.register("feas.token_invalidations",
                     lambda: _feas_mod.stats()["token_invalidations"],
                     suspect=False)
        gov.register("feas.residue_rows",
                     lambda: self.store.table_cache.device.feas.debt(),
                     WatermarkPolicy(cfg.governor_feas_residue_high),
                     reclaim=lambda:
                     self.store.table_cache.device.feas.fold())
        gov.register("feas.spread_score_evals",
                     lambda: _spread_mod.stats()["spread_score_evals"],
                     suspect=False)

        # adaptive micro-batch gateway (server/worker.py, ISSUE 7):
        # live window, mean lanes per device dispatch, and the trigger
        # split — immediate (idle lane / unprofitable shape) vs
        # deadline (window expired while streaming). All monotone or
        # performance gauges, never drift suspects
        if self.gateway is not None:
            gw = self.gateway
            gov.register("gateway.window_us", gw.window_us, unit="us",
                         suspect=False)
            gov.register("gateway.batch_occupancy", gw.occupancy_mean,
                         unit="ratio", suspect=False)
            gov.register("gateway.immediate_dispatches",
                         lambda: gw.stats["immediate_dispatches"],
                         suspect=False)
            gov.register("gateway.deadline_dispatches",
                         lambda: gw.stats["deadline_dispatches"],
                         suspect=False)

        # batched write ingest (server/ingest.py, ISSUE 19): queue
        # depth carries the watermark whose reclaim HALVES the window
        # (a deep queue means the committer is saturated — waiting for
        # companions only adds latency; the drain trigger already
        # self-clocks batch formation). The shed/coalesced counters
        # are monotone, never drift suspects
        if self.ingest is not None:
            ing = self.ingest
            gov.register("ingest.queue_depth", ing.queue_depth,
                         WatermarkPolicy(cfg.governor_ingest_queue_high,
                                         pressure=True),
                         reclaim=ing.shrink_window)
            gov.register("ingest.queue_bytes", ing.queue_bytes,
                         suspect=False)
            gov.register("ingest.window_us", ing.window_us, unit="us",
                         suspect=False)
            gov.register("ingest.batch_size", ing.mean_batch_size,
                         suspect=False)
            gov.register("ingest.coalesced_writes",
                         lambda: ing.stats["coalesced_writes"],
                         suspect=False)
            gov.register("ingest.shed", lambda: ing.stats["shed"],
                         suspect=False)
            gov.register("ingest.write_p99_ms", ing.write_p99_ms,
                         unit="ms", suspect=False)

        # recompile visibility (analysis/sanitizer.py): distinct
        # compiled trace signatures across every kernel arm — a
        # recompile storm shows up in /v1/operator/governor as a
        # climbing gauge, not a mystery p99. suspect=False: monotone
        # by construction, it must not out-rank a real leak in drift
        # findings
        from ..analysis.sanitizer import traces as lint_traces
        gov.register("lint.recompiles", lint_traces.count,
                     suspect=False)

        # lock traffic (analysis/race.py, ISSUE 14): populated only
        # when NOMAD_TPU_RACE=1 armed the shims — zeros otherwise.
        # All monotone counters or bounded structures, never drift
        # suspects. The worst-holder exemplars ride the `locks` block
        # of /v1/operator/governor (extra_status below)
        from ..analysis import race as _race_mod
        gov.register("lock.tracked", _race_mod.monitor.tracked_locks,
                     suspect=False)
        gov.register("lock.order_edges", _race_mod.monitor.edge_count,
                     suspect=False)
        gov.register("lock.contended_acquires",
                     _race_mod.monitor.contended_total, suspect=False)
        gov.register("lock.hold_warnings",
                     _race_mod.monitor.hold_warns_total, suspect=False)
        gov.register("lock.findings",
                     _race_mod.monitor.unsuppressed_count,
                     suspect=False)
        gov.extra_status["locks"] = _race_mod.monitor.status_snapshot

        # flight-recorder visibility (ISSUE 9): ring occupancy and the
        # exemplar count in /v1/operator/governor. suspect=False: both
        # are bounded by construction
        from ..trace import tracer as _flight
        gov.register("trace.ring_traces", _flight.ring_len,
                     suspect=False)
        gov.register("trace.exemplars", _flight.exemplar_count,
                     suspect=False)

        # distributed scheduler plane (server/follower_sched.py, ISSUE
        # 16). Leader-side reads come from the lease table (remote
        # dequeue/demotion counters, leases outstanding — the bounded
        # in-flight remote set carries no watermark: the lease sweeper
        # IS its reclaim); the fence-wait p99 reads the FOLLOWER-side
        # reservoir through self.follower_sched, which attach_raft may
        # build after these lambdas are registered — hence the getattr
        leases = self.eval_leases
        gov.register("cluster_sched.remote_dequeues",
                     lambda: leases.stats["remote_dequeues"],
                     suspect=False)
        gov.register("cluster_sched.remote_demotions",
                     lambda: leases.stats["remote_demotions"],
                     suspect=False)
        gov.register("cluster_sched.leases_outstanding",
                     leases.outstanding)
        gov.register("cluster_sched.lease_expiries",
                     lambda: leases.stats["expired"], suspect=False)
        gov.register("cluster_sched.fence_wait_p99_ms",
                     lambda: (self.follower_sched.fence_wait_p99_ms()
                              if self.follower_sched is not None
                              else 0.0),
                     unit="ms", suspect=False)

        # admission control: the broker sheds fresh enqueues while any
        # pressure gauge is over
        self.eval_broker.pressure_fn = gov.backpressure

    def _auto_pin_exemplars(self, finding: dict) -> None:
        """Drift hook (ISSUE 9 satellite): a drift finding that names
        a suspect structure pins the flight recorder's CURRENT
        exemplar set — the worst span trees recorded while the drift
        was building are the capture an operator would have wanted
        `operator debug` to take automatically."""
        suspect = finding.get("suspect_structure")
        if not suspect:
            return
        reason = (f"drift:{finding.get('metric', '?')}"
                  f"->{suspect}")
        pinned = self.tracer.pin_exemplars(reason=reason)
        if pinned and self.governor is not None:
            self.governor.emit({"kind": "trace_pin",
                                "exemplars": pinned,
                                "suspect": suspect,
                                "metric": finding.get("metric")})

    def _register_persistence_gauges(self) -> None:
        """Snapshot cadence, off-thread serialization time, and skipped
        triggers (ISSUE 8 cold-start pipeline) — a snapshot that keeps
        getting skipped-in-flight means the store outgrew the writer
        and the WAL tail is ballooning. Registered separately from
        _register_governor_gauges because Persistence is constructed
        after the governor. All monotone/perf gauges, never drift
        suspects."""
        p = self.persistence
        gov = self.governor
        gov.register("persistence.snapshots",
                     lambda: p.stats["snapshots"], suspect=False)
        gov.register("persistence.snapshot_skipped_inflight",
                     lambda: p.stats["snapshot_skipped_inflight"],
                     suspect=False)
        gov.register("persistence.last_snapshot_s",
                     lambda: p.stats["last_snapshot_s"], unit="s",
                     suspect=False)
        gov.register("persistence.snapshot_errors",
                     lambda: p.stats["snapshot_errors"], suspect=False)

    def _emit_stats(self) -> None:
        """Periodic gauge emission (eval_broker.go:825 EmitStats,
        blocked_evals stats, worker counters)."""
        from ..utils import metrics
        while not getattr(self, "_shutdown", False):
            time.sleep(1.0)
            try:
                bs = self.eval_broker.stats
                metrics.set_gauge("nomad.broker.total_ready",
                                  bs.total_ready)
                metrics.set_gauge("nomad.broker.total_unacked",
                                  bs.total_unacked)
                metrics.set_gauge("nomad.broker.total_blocked",
                                  bs.total_blocked)
                metrics.set_gauge("nomad.broker.total_waiting",
                                  bs.total_waiting)
                metrics.set_gauge(
                    "nomad.blocked_evals.total_blocked",
                    len(getattr(self.blocked_evals, "_captured", {}))
                    + len(getattr(self.blocked_evals, "_escaped", {})))
                metrics.set_gauge(
                    "nomad.worker.total_processed",
                    sum(w.stats["processed"] for w in self.workers))
                metrics.set_gauge(
                    "nomad.worker.total_failed",
                    sum(w.stats["failed"] for w in self.workers))
                metrics.set_gauge("nomad.state.latest_index",
                                  self.store.latest_index())
            except Exception:       # pragma: no cover — best effort
                pass

    def revoke_leadership(self) -> None:
        """leader.go revokeLeadership:1038 — disable leader-only
        services; workers stay up, parked on the disabled broker."""
        self._leader = False
        rep = getattr(self, "_replication", None)
        if rep is not None:
            rep.stop()
            self._replication = None
        # remote-dequeue leases are leader state: the broker flush
        # below cancels every unack they covered, and the NEW leader
        # re-enqueues non-terminal evals from the store — stale leases
        # here would only nack evals we no longer own
        self.eval_leases.flush()
        self.eval_broker.set_enabled(False)
        self.blocked_evals.set_enabled(False)
        self.plan_queue.set_enabled(False)
        self.periodic.set_enabled(False)
        self.deployments_watcher.set_enabled(False)
        self.node_drainer.set_enabled(False)
        self.event_sinks.set_enabled(False)
        self._heartbeats.clear()

    def scheduler_plane_status(self) -> dict:
        """Per-member scheduler-plane status for `nomad server
        members`, /v1/agent/members, and `operator debug` (ISSUE 16
        satellite): raft role + applied index per member, fence lag
        (the leader's last log index minus the member's applied index
        — exactly the gap a follower's snapshot fence would wait out),
        leased evals per follower from the leader's lease table, and
        this server's own plane counters."""
        raft = self.raft
        status = {
            "enabled": bool(self.config.follower_sched),
            "leases": self.eval_leases.snapshot_stats(),
            "follower": (self.follower_sched.snapshot_stats()
                         if self.follower_sched is not None else None),
            "members": [],
        }
        if raft is None:
            return status
        leased = self.eval_leases.by_follower()
        rows = {raft.self_addr: raft._handle_status({})}
        from ..rpc.client import RpcClient
        for addr in (self.store.server_members() or []):
            if addr in rows:
                continue
            try:
                c = RpcClient(addr, dial_timeout_s=0.5)
                try:
                    rows[addr] = c.call("Raft.Status", {}, timeout_s=1.0)
                finally:
                    c.close()
            except Exception:
                rows[addr] = None
        leader_last = 0
        for st in rows.values():
            if st and st.get("role") == "leader":
                leader_last = int(st.get("last_log_index") or 0)
        for addr in sorted(rows):
            st = rows[addr]
            if st is None:
                status["members"].append(
                    {"addr": addr, "role": "unreachable",
                     "applied_index": None, "fence_lag": None,
                     "leased_evals": leased.get(addr, 0)})
                continue
            applied = int(st.get("applied_index") or 0)
            status["members"].append(
                {"addr": addr, "role": st.get("role"),
                 "applied_index": applied,
                 "fence_lag": (max(0, leader_last - applied)
                               if leader_last else 0),
                 "leased_evals": leased.get(addr, 0)})
        return status

    def apply_replicated(self, index: int, msg_type: str,
                         enc_payload: dict) -> None:
        """Apply a COMMITTED log entry — leaders and followers share
        this path (raft.py _fsm_loop calls it in log order once the
        commit index covers the entry). Nested raft_apply calls from
        FSM side effects append their own log entries on the leader and
        are suppressed on followers — either way the effect arrives as
        its own committed entry, so replicas converge. Change events
        publish here, i.e. only for committed writes (the r3 advisor's
        follower-dirty-read finding)."""
        from .persistence import decode_payload
        payload = decode_payload(msg_type, enc_payload)
        tl = self._apply_tl
        called = time.perf_counter()
        with self._raft_l:
            if index <= self._raft_index:
                return              # duplicate delivery (batch overlap)
            tl.in_fsm_apply = True
            try:
                self._raft_index = index
                # the group-fsync barrier is the FSM loop's, once per
                # committed batch (raft.py _fsm_loop)
                self._apply_locked(index, msg_type, payload, called,
                                   barrier=False)
            finally:
                tl.in_fsm_apply = False
            self._publish_applied(index, msg_type, payload)

    def install_snapshot(self, data: dict,
                         base_index: Optional[int] = None) -> None:
        """Full-state reseed from the leader (fsm.go Restore:1374). The
        snapshot's raft base index is authoritative for the applied
        index: store.latest_index() undercounts whenever the tail holds
        entries that touch no table (election no-ops), and an applied
        index below the log base would let this node reissue
        already-used log indexes after winning an election (r3 advisor,
        high)."""
        with self._raft_l:
            self.store.restore(data)
            floor = self.store.latest_index() if base_index is None \
                else base_index
            self._raft_index = max(floor, self.store.latest_index())
            # snapshot-covered indexes were never published as events
            # on this node: raise the sink gap floor accordingly
            self.events.epoch_floor = max(self.events.epoch_floor,
                                          self._raft_index)
            if self.persistence is not None:
                self.persistence.snapshot(self.store)
        # adopt the snapshot's replicated membership
        if self.raft is not None:
            members = self.store.server_members()
            if members:
                self.raft.update_members(members)

    def shutdown(self) -> None:
        self._shutdown = True
        # scheduler plane FIRST (ISSUE 16 satellite: clean multi-server
        # teardown): follower dequeue loops and the lease sweeper talk
        # to REMOTE transports — detach them before any local subsystem
        # starts dying, so no loop is mid-RPC against a peer that this
        # process's teardown (or a concurrent peer's) already killed
        if self.follower_sched is not None:
            self.follower_sched.stop()
        self.eval_leases.stop()
        if self.persistence is not None:
            try:
                # a background snapshot writer racing teardown could
                # leave a half-written .tmp for the next boot to skip;
                # wait it out, then flush any fsync-pending WAL bytes
                self.persistence.wait_idle()
                self.persistence.commit_barrier()
                self.persistence.save_cost_model()
            except Exception:   # pragma: no cover — best effort
                LOG.exception("cost model save failed")
        if self.telemetry is not None:
            self.telemetry.stop()
        if self.governor is not None:
            self.governor.stop()
        # detach the flight recorder from this server's governor — but
        # only if a newer server hasn't already rebound the hooks (the
        # tracer is process-global; holding our closures past shutdown
        # would keep the dead governor graph reachable forever)
        fns = getattr(self, "_tracer_fns", None)
        if fns is not None:
            if self.tracer.threshold_fn is fns[0]:
                self.tracer.threshold_fn = None
            if self.tracer.gauge_fn is fns[1]:
                self.tracer.gauge_fn = None
        if getattr(self, "swim", None) is not None:
            self.swim.stop()
        if self.raft is not None:
            self.raft.stop()
        self._leader = False
        self.event_sinks.set_enabled(False)
        self.deployments_watcher.set_enabled(False)
        self.node_drainer.set_enabled(False)
        self.periodic.stop()
        for w in self.workers:
            w.stop()
        self.plan_applier.stop()
        if self.ingest is not None:
            self.ingest.stop()
        self.eval_broker.set_enabled(False)
        self.blocked_evals.set_enabled(False)
        self.plan_queue.set_enabled(False)
        self._heartbeats.stop()

    def establish_leadership(self) -> None:
        """leader.go establishLeadership:222."""
        self.eval_broker.set_enabled(True)
        self.blocked_evals.set_enabled(True)
        self.plan_queue.set_enabled(True)
        self._leader = True
        self._restore_evals()
        # restored nodes need TTL timers or a dead node stays ready
        # forever (heartbeat.go initializeHeartbeatTimers)
        for node in self.store.nodes():
            if not node.terminal_status():
                self.reset_heartbeat_timer(node.id)
        # leader.go restorePeriodicDispatcher:222 — re-track periodic jobs
        self.periodic.set_enabled(True)
        for job in self.store.jobs():
            if job.is_periodic():
                self.periodic.add(job)
        self.deployments_watcher.set_enabled(True)
        self.node_drainer.set_enabled(True)
        # durable event sinks are a leader duty: workers resume from
        # each sink's raft-committed progress (event_sink_manager.go)
        self.event_sinks.set_enabled(True)
        # non-authoritative regions replicate ACL policies, global
        # tokens, and namespaces from the authoritative region
        # (leader.go:327-331)
        if self.config.authoritative_region and \
                self.config.authoritative_region != self.config.region:
            from .replication import ReplicationManager
            self._replication = ReplicationManager(self)
            self._replication.start()
        if self.raft is not None:
            # seed the replicated member set from static boot config on
            # first leadership (later joins/leaves mutate it), then run
            # the autopilot reaper. Threaded: establish_leadership runs
            # under the raft lock (same reason the election no-op is)
            def _seed():
                try:
                    if not self.store.server_members():
                        self.raft_apply(
                            "server_membership",
                            dict(members=[self.raft.self_addr]
                                 + list(self.raft.peers)))
                except Exception:
                    LOG.exception("membership seed failed")
            threading.Thread(target=_seed, daemon=True,
                             name="member-seed").start()
            # always spawned: the loop idles when the threshold is 0,
            # so `operator autopilot-set-config` can enable cleanup on
            # a live leader
            threading.Thread(target=self._autopilot_loop,
                             daemon=True, name="autopilot").start()

    def _reap_failed_evals(self) -> None:
        """Drain the broker's failed queue: mark the eval failed and
        create a delayed failed-follow-up so the work retries after the
        storm passes (leader.go reapFailedEvaluations:766)."""
        while self._leader:
            ev, token = self.eval_broker.dequeue([FAILED_QUEUE], timeout_s=0.5)
            if ev is None:
                continue
            if ev.type == JOB_TYPE_CORE:
                # core evals are in-memory only — drop, never persist;
                # the GC ticker will enqueue a fresh one next interval
                self.eval_broker.ack(ev.id, token)
                continue
            failed = ev.copy()
            failed.status = EVAL_STATUS_FAILED
            follow_up = ev.create_failed_follow_up_eval(
                self.config.failed_eval_unblock_delay_s)
            failed.next_eval = follow_up.id
            try:
                self.raft_apply("eval_update", dict(evals=[failed, follow_up]))
                self.eval_broker.ack(ev.id, token)
            except Exception:
                LOG.exception("failed-eval reap for %s", ev.id)

    def _schedule_periodic_gc(self) -> None:
        """leader.go schedulePeriodic:689 — enqueue `_core` GC evals on a
        ticker. These evals are in-memory only (never raft-applied)."""
        last = time.monotonic()
        while self._leader:
            time.sleep(min(self.config.gc_interval_s / 4.0, 0.5))
            if time.monotonic() - last < self.config.gc_interval_s:
                continue
            last = time.monotonic()
            for core_job in (CORE_JOB_EVAL_GC, CORE_JOB_JOB_GC,
                             CORE_JOB_NODE_GC, CORE_JOB_DEPLOYMENT_GC):
                self.eval_broker.enqueue(self._core_eval(core_job))

    def _core_eval(self, core_job: str) -> Evaluation:
        return Evaluation(
            priority=CORE_JOB_PRIORITY, type=JOB_TYPE_CORE,
            triggered_by=TRIGGER_SCHEDULED, job_id=core_job,
            status=EVAL_STATUS_PENDING,
            modify_index=self._raft_index)

    def force_gc(self) -> None:
        """`nomad system gc` (system_endpoint.go): a forced full GC pass."""
        self.eval_broker.enqueue(self._core_eval(CORE_JOB_FORCE_GC))

    def _restore_evals(self) -> None:
        """Re-enqueue non-terminal evals after leadership (leader.go:496)."""
        for ev in self.store.evals():
            if ev.should_enqueue():
                self.eval_broker.enqueue(ev)
            elif ev.should_block():
                self.blocked_evals.block(ev)

    # -- WAL replay (cold start; ISSUE 8 batched replay) ---------------
    # entry types whose replay batches through the store's bulk paths;
    # a batch flushes when the incoming entry shares a (namespace, job)
    # with one already pending, so the grouped transaction is EXACTLY
    # state-equivalent to sequential per-entry replay (the per-entry
    # side-effect loops only ever read/write their own job's rows)
    _REPLAY_BATCH_TYPES = ("eval_update", "alloc_client_update")

    def _replay_entries(self, entries, highest: int) -> int:
        """Replay the WAL tail into the FSM. Event publication is
        suppressed throughout (replay is not new history — the epoch
        floor is raised after), and runs of eval/alloc-update entries
        group into single store transactions
        (NOMAD_TPU_WAL_REPLAY_BATCH=0 forces the sequential path for
        bisection)."""
        import os as _os

        batch_on = _os.environ.get("NOMAD_TPU_WAL_REPLAY_BATCH", "1") \
            not in ("0", "off")
        t0 = time.perf_counter() if stages.enabled else 0.0
        pending: List = []          # one same-type run
        pending_jobs: set = set()
        applied = 0

        def job_keys(msg_type: str, p: dict) -> set:
            keys = {(e.namespace, e.job_id) for e in p.get("evals", [])}
            if msg_type == "alloc_client_update":
                keys |= {(a.namespace, a.job_id)
                         for a in p.get("allocs", [])}
            return keys

        def flush() -> None:
            if not pending:
                return
            if len(pending) == 1:
                self._replay_one(*pending[0])
            else:
                try:
                    if pending[0][1] == "eval_update":
                        self._replay_eval_updates(pending)
                    else:
                        self._replay_alloc_client_updates(pending)
                    for index, _mt, _p, ts in pending:
                        self._raft_index = max(self._raft_index, index)
                        if ts:
                            self.time_table.witness(index, ts)
                except Exception:
                    LOG.exception("batched WAL replay failed "
                                  "(%d %s entries)", len(pending),
                                  pending[0][1])
            pending.clear()
            pending_jobs.clear()

        for index, msg_type, payload, ts in entries:
            if index <= highest:
                continue
            applied += 1
            if batch_on and msg_type in self._REPLAY_BATCH_TYPES:
                keys = job_keys(msg_type, payload)
                if pending and (pending[0][1] != msg_type
                                or keys & pending_jobs):
                    flush()
                pending.append((index, msg_type, payload, ts))
                pending_jobs.update(keys)
                continue
            flush()
            self._replay_one(index, msg_type, payload, ts)
        flush()
        if stages.enabled:
            stages.add("wal_replay", time.perf_counter() - t0)
        return applied

    def _replay_one(self, index: int, msg_type: str, payload: dict,
                    ts: float) -> None:
        try:
            getattr(self, f"_apply_{msg_type}")(index, payload)
            self._raft_index = max(self._raft_index, index)
            if ts:
                self.time_table.witness(index, ts)
        except Exception:
            LOG.exception("WAL replay failed at %d/%s", index, msg_type)

    def _replay_eval_updates(self, pending: List) -> None:
        """N job-disjoint eval_update entries as ONE store transaction;
        the per-eval side effects run per entry exactly as
        _apply_eval_update would (broker/blocked are disabled during
        replay, so enqueue is a no-op; reconcile writes are real)."""
        self.store.upsert_evals_batch(
            [(index, p["evals"]) for index, _mt, p, _ts in pending])
        for index, _mt, p, _ts in pending:
            for ev in p["evals"]:
                self.enqueue_eval(ev)
                if ev.job_id and ev.type != JOB_TYPE_CORE:
                    self.store.reconcile_job_status(index, ev.namespace,
                                                    ev.job_id)

    def _replay_alloc_client_updates(self, pending: List) -> None:
        """N job-disjoint alloc_client_update entries: one batched
        store transaction for the alloc merges, then each entry's
        unblock/eval/status side effects in order (job-disjointness
        makes this exactly sequential-equivalent)."""
        self.store.update_allocs_from_client_batch(
            [(index, p["allocs"]) for index, _mt, p, _ts in pending])
        for index, _mt, p, _ts in pending:
            for stub in p["allocs"]:
                alloc = self.store.alloc_by_id(stub.id)
                if alloc is None or not alloc.client_terminal_status():
                    continue
                node = self.store.node_by_id(alloc.node_id)
                if node is not None:
                    self.blocked_evals.unblock(node.computed_class,
                                               index)
            for ev in p.get("evals", []):
                self.store.upsert_evals(index, [ev])
                self.enqueue_eval(ev)
            self._reconcile_job_statuses(index,
                                         {"allocs_placed": p["allocs"]})

    # -- raft apply ----------------------------------------------------
    def raft_apply(self, msg_type: str, payload: dict) -> int:
        """Serialized FSM apply (fsm.go Apply:210-300). Returns the
        index. Dev mode (no raft): record+apply+snapshot run inline
        under the raft lock so WAL order == apply order. Clustered: the
        leader appends the entry to the replication log and blocks
        until a majority holds it AND the local FSM has applied it
        (apply-at-commit — hashicorp/raft runs the FSM only up to the
        commit index, nomad/server.go:1214); non-leaders forward the
        write to the leader (rpc.go forward())."""
        index, waiter = self.raft_apply_async(msg_type, payload)
        if waiter is not None:
            waiter()
        return index

    def raft_apply_async(self, msg_type: str, payload: dict):
        """The non-blocking half of raft_apply: log append now, commit
        + FSM apply deferred. Returns (index, waiter) where waiter is
        None (nested/forwarded/no-raft: nothing to wait for at this
        frame) or a callable that blocks until the entry is
        majority-replicated in the term it was stamped with and applied
        locally, raising otherwise. The plan applier uses this to
        overlap plan N's replication with plan N+1's verification
        (plan_apply.go:44-70 pipelining). On a clustered leader NOTHING
        is applied at this point — a caller that needs to read its own
        write must invoke the waiter (raft_apply does); this is what
        closes the uncommitted-read window on a partitioned leader."""
        if self.raft is not None:
            if getattr(self._apply_tl, "in_fsm_apply", False):
                # nested FSM side effect during a committed apply: on
                # the leader it becomes its own log entry (applied when
                # it commits); on a follower the leader's equivalent
                # entry arrives via the log — suppress. Narrow window:
                # if leadership changes between an entry's commit and
                # its apply, NO node re-emits the nested write (every
                # replica applies it as a non-leader). The only such
                # write is the blocked-eval wake (_unblock_enqueue),
                # and the woken eval stays in state as blocked — the
                # new leader re-tracks it on establish_leadership, the
                # same stall-until-next-capacity-change the reference
                # accepts across failovers (blocked_evals.go:316).
                if self.raft.is_leader():
                    try:
                        idx, _term = self.raft.append_entry(
                            msg_type, payload)
                        return idx, None
                    except RuntimeError:
                        LOG.warning(
                            "nested %s write dropped: deposed during "
                            "FSM apply; state-derived recovery applies",
                            msg_type)
                        return self._raft_index, None
                return self._raft_index, None
            if not self.raft.is_leader():
                return self.raft.forward_apply(msg_type, payload), None
            # raises "not the leader" on a deposed leader — nothing
            # recorded, nothing applied
            index, term = self.raft.append_entry(msg_type, payload)
            raft = self.raft
            return index, lambda: raft.wait_for_applied(index, term)
        # dev / single-node: inline serialized apply. Change events fan
        # out inside the lock; WAL replay bypasses raft_apply so
        # restores don't replay the event history.
        called = time.perf_counter()
        with self._raft_l:
            index = self._raft_index + 1
            self._raft_index = index
            # dev mode: the entry IS the commit unit, so the
            # group-fsync barrier sits right here
            self._apply_locked(index, msg_type, payload, called,
                               barrier=True)
            self._publish_applied(index, msg_type, payload)
        return index, None

    def _apply_locked(self, index: int, msg_type: str, payload: dict,
                      called: float, barrier: bool) -> None:
        """What both apply paths (the inline dev apply above,
        apply_replicated) do to one entry under the raft lock: WAL
        record, FSM apply, time table, the commit barrier where the
        entry is the commit unit, the snapshot trigger. For an entry
        the plan applier commits it names what plan_commit waited for
        (utils/stages.py): raft_lock_wait from `called` (the caller's
        perf_counter before it took the lock) to here, wal_encode and
        wal_write inside record() and round the barrier's fsync,
        fsm_apply round the store's transaction."""
        plan = stages.enabled and msg_type in PLAN_ENTRIES
        if plan:
            stages.add("raft_lock_wait", time.perf_counter() - called)
        if self.persistence is not None:
            self.persistence.record(index, msg_type, payload)
        with (stages.span("fsm_apply", kind=msg_type) if plan
              else stages.NULL_SPAN):
            getattr(self, f"_apply_{msg_type}")(index, payload)
        self.time_table.witness(index)
        if self.persistence is not None:
            if barrier:
                with (stages.span("wal_write", synced=True)
                      if plan and self.persistence.group_fsync
                      else stages.NULL_SPAN):
                    self.persistence.commit_barrier()
            self.persistence.maybe_snapshot(self.store)

    def _publish_applied(self, index: int, msg_type: str,
                         payload: dict) -> None:
        """The applied entry's change events, built and fanned out (a
        plan entry's as stage event_publish)."""
        try:
            with (stages.span("event_publish")
                  if stages.enabled and msg_type in PLAN_ENTRIES
                  else stages.NULL_SPAN) as sp:
                events = events_from_apply(msg_type, payload, index)
                sp.note(events=len(events))
                self.events.publish(events)
        except Exception:
            LOG.exception("event publish for %s", msg_type)

    def _apply_noop(self, index: int, p: dict) -> None:
        """Leadership no-op (hashicorp/raft LogNoop): commits the new
        term without mutating state."""

    # -- FSM appliers --------------------------------------------------
    def _apply_job_register(self, index: int, p: dict) -> None:
        job: Job = p["job"]
        self.store.upsert_job(index, job)
        self.blocked_evals.untrack(job.namespace, job.id)
        self.store.reconcile_job_status(index, job.namespace, job.id)
        self.periodic.add(self.store.job_by_id(job.namespace, job.id) or job)
        for ev in p.get("evals", []):
            if not ev.job_modify_index:
                # ingest-embedded eval (ISSUE 19): the register and its
                # eval share one entry, so the fence is stamped at
                # apply time — deterministic on WAL replay too
                ev.job_modify_index = index
            self.store.upsert_evals(index, [ev])
            self.enqueue_eval(ev)

    def _apply_job_deregister(self, index: int, p: dict) -> None:
        namespace, job_id = p["namespace"], p["job_id"]
        if p.get("purge"):
            self.store.delete_job(index, namespace, job_id)
            self.periodic.remove(namespace, job_id)
        else:
            job = self.store.job_by_id(namespace, job_id)
            if job is not None:
                stopped = job.copy()
                stopped.stop = True
                self.store.upsert_job(index, stopped)
                self.store.reconcile_job_status(index, namespace, job_id)
                self.periodic.add(stopped)  # untracks a stopped periodic
        for ev in p.get("evals", []):
            self.store.upsert_evals(index, [ev])
            self.enqueue_eval(ev)

    def _apply_eval_update(self, index: int, p: dict) -> None:
        evals: List[Evaluation] = p["evals"]
        self.store.upsert_evals(index, evals)
        for ev in evals:
            self.enqueue_eval(ev)
            if ev.job_id and ev.type != JOB_TYPE_CORE:
                self.store.reconcile_job_status(index, ev.namespace, ev.job_id)

    def _apply_eval_delete(self, index: int, p: dict) -> None:
        self.store.delete_evals(index, p["eval_ids"], p.get("alloc_ids"))

    def _apply_node_register(self, index: int, p: dict) -> None:
        node: Node = p["node"]
        self.store.upsert_node(index, node)
        stored = self.store.node_by_id(node.id)
        if stored is not None and stored.ready():
            self.blocked_evals.unblock(stored.computed_class, index)

    def _apply_node_deregister(self, index: int, p: dict) -> None:
        self.store.delete_node(index, p["node_ids"])

    def _apply_node_status_update(self, index: int, p: dict) -> None:
        node_id, status = p["node_id"], p["status"]
        self.store.update_node_status(index, node_id, status, int(time.time()))
        node = self.store.node_by_id(node_id)
        if node is None:
            return
        if status == NODE_STATUS_READY:
            self.blocked_evals.unblock(node.computed_class, index)
        evals = p.get("evals", [])
        if evals:
            self.store.upsert_evals(index, evals)
            for ev in evals:
                self.enqueue_eval(ev)

    def _apply_node_eligibility_update(self, index: int, p: dict) -> None:
        self.store.update_node_eligibility(index, p["node_id"], p["eligibility"])
        node = self.store.node_by_id(p["node_id"])
        if node is not None and node.ready():
            self.blocked_evals.unblock(node.computed_class, index)

    def _apply_node_drain_update(self, index: int, p: dict) -> None:
        self.store.update_node_drain(index, p["node_id"], p["drain_strategy"],
                                     p.get("mark_eligible", False))

    def _apply_alloc_desired_transition(self, index: int, p: dict) -> None:
        self.store.update_alloc_desired_transitions(
            index, p["alloc_ids"], p["transition"], p.get("evals"))
        for ev in p.get("evals", []):
            self.enqueue_eval(ev)

    def _apply_alloc_client_update(self, index: int, p: dict) -> None:
        allocs: List[Allocation] = p["allocs"]
        self.store.update_allocs_from_client(index, allocs)
        # failed/stopped allocs free capacity -> unblock by node class
        for stub in allocs:
            alloc = self.store.alloc_by_id(stub.id)
            if alloc is None or not alloc.client_terminal_status():
                continue
            node = self.store.node_by_id(alloc.node_id)
            if node is not None:
                self.blocked_evals.unblock(node.computed_class, index)
        for ev in p.get("evals", []):
            self.store.upsert_evals(index, [ev])
            self.enqueue_eval(ev)
        self._reconcile_job_statuses(index, {"allocs_placed": allocs})

    def _apply_plan_results(self, index: int, p: dict) -> None:
        self.store.upsert_plan_results(
            index,
            allocs_stopped=p["allocs_stopped"],
            allocs_placed=p["allocs_placed"],
            allocs_preempted=p["allocs_preempted"],
            deployment=p.get("deployment"),
            deployment_updates=p.get("deployment_updates"),
            evals=p.get("evals"),
        )
        self._reconcile_job_statuses(index, p)

    def _apply_plan_group_results(self, index: int, p: dict) -> None:
        """One committed entry carrying a whole plan GROUP (the
        group-commit applier): N verified plans land as ONE state-store
        transaction — a single layer push instead of N — and publish
        their change events in one flush."""
        self.store.upsert_plan_group_results(index, p["groups"])
        for g in p["groups"]:
            self._reconcile_job_statuses(index, g)

    def _apply_ingest_batch(self, index: int, p: dict) -> None:
        """One committed entry carrying a whole ingest GROUP (ISSUE 19,
        server/ingest.py): coalesced registers / client alloc updates /
        desired transitions land in submission order, with each
        consecutive same-kind run collapsed to ONE store transaction
        (upsert_jobs_batch / update_allocs_from_client_batch). Per-kind
        side effects run per entry exactly as the singleton appliers
        would, so the final state is sequential-equivalent by
        construction."""
        entries = p["entries"]
        i = 0
        while i < len(entries):
            kind = entries[i]["kind"]
            j = i
            while j < len(entries) and entries[j]["kind"] == kind:
                j += 1
            run = entries[i:j]
            if kind == "job_register":
                self._ingest_apply_registers(index, run)
            elif kind == "alloc_client_update":
                self._ingest_apply_client_updates(index, run)
            else:
                for e in run:
                    self._apply_alloc_desired_transition(index, e)
            i = j

    def _ingest_apply_registers(self, index: int, run: List[dict]) -> None:
        # one store transaction for the run's jobs (in order, so a
        # same-job re-register in one batch still sees the version
        # bump), then the singleton applier's side-effect tail per job
        self.store.upsert_jobs_batch(index, [e["job"] for e in run])
        evals: List[Evaluation] = []
        for e in run:
            job: Job = e["job"]
            self.blocked_evals.untrack(job.namespace, job.id)
            self.store.reconcile_job_status(index, job.namespace, job.id)
            self.periodic.add(
                self.store.job_by_id(job.namespace, job.id) or job)
            for ev in e.get("evals", []):
                if not ev.job_modify_index:
                    ev.job_modify_index = index
                evals.append(ev)
        if evals:
            self.store.upsert_evals_batch([(index, evals)])
            for ev in evals:
                self.enqueue_eval(ev)

    def _ingest_apply_client_updates(self, index: int,
                                     run: List[dict]) -> None:
        # the r12 WAL-replay batch promoted to the live path: one store
        # transaction for the alloc merges, then each entry's
        # unblock/eval/status side effects in submission order
        self.store.update_allocs_from_client_batch(
            [(index, e["allocs"]) for e in run])
        for e in run:
            for stub in e["allocs"]:
                alloc = self.store.alloc_by_id(stub.id)
                if alloc is None or not alloc.client_terminal_status():
                    continue
                node = self.store.node_by_id(alloc.node_id)
                if node is not None:
                    self.blocked_evals.unblock(node.computed_class,
                                               index)
            for ev in e.get("evals", []):
                self.store.upsert_evals(index, [ev])
                self.enqueue_eval(ev)
            self._reconcile_job_statuses(index,
                                         {"allocs_placed": e["allocs"]})

    def _apply_scheduler_config(self, index: int, p: dict) -> None:
        self.store.set_scheduler_config(index, p["config"])

    # ACL appliers (fsm.go applyACL*; nomad/acl_endpoint.go)
    def _apply_acl_policy_upsert(self, index: int, p: dict) -> None:
        self.store.upsert_acl_policies(index, p["policies"])

    def _apply_acl_policy_delete(self, index: int, p: dict) -> None:
        self.store.delete_acl_policies(index, p["names"])

    def _apply_acl_token_upsert(self, index: int, p: dict) -> None:
        self.store.upsert_acl_tokens(index, p["tokens"])

    def _apply_acl_token_delete(self, index: int, p: dict) -> None:
        self.store.delete_acl_tokens(index, p["accessor_ids"])

    # namespace appliers (fsm.go applyNamespace*)
    def _apply_namespace_upsert(self, index: int, p: dict) -> None:
        self.store.upsert_namespaces(index, p["namespaces"])

    def _apply_namespace_delete(self, index: int, p: dict) -> None:
        self.store.delete_namespaces(index, p["names"])

    # service registry appliers (built-in catalog; the reference sends
    # these to Consul, command/agent/consul/service_client.go)
    def _apply_service_registration_upsert(self, index: int,
                                           p: dict) -> None:
        self.store.upsert_service_registrations(index, p["services"])

    def _apply_service_registration_delete(self, index: int,
                                           p: dict) -> None:
        self.store.delete_service_registrations(
            index, ids=p.get("ids"), alloc_ids=p.get("alloc_ids"))

    # CSI volume appliers (fsm.go applyCSIVolume*)
    def _apply_csi_volume_register(self, index: int, p: dict) -> None:
        self.store.upsert_csi_volumes(index, p["volumes"])

    def _apply_csi_volume_deregister(self, index: int, p: dict) -> None:
        self.store.delete_csi_volume(index, p["namespace"], p["volume_id"])

    def _apply_csi_volume_claim(self, index: int, p: dict) -> None:
        self.store.csi_volume_claim(index, p["namespace"], p["volume_id"],
                                    p["alloc_id"], p["node_id"],
                                    p["read_only"])

    def _apply_csi_volume_release(self, index: int, p: dict) -> None:
        self.store.csi_volume_release(index, p["namespace"],
                                      p["volume_id"], p["alloc_id"])

    def _apply_vault_accessor_upsert(self, index: int, p: dict) -> None:
        from ..server.vault import VaultAccessor
        from ..utils.codec import from_wire
        self.store.upsert_vault_accessors(
            index, [from_wire(VaultAccessor, w) for w in p["accessors"]])

    def _apply_vault_accessor_renew(self, index: int, p: dict) -> None:
        a = self.store.vault_accessor(p["accessor"])
        if a is not None:
            from dataclasses import replace
            self.store.upsert_vault_accessors(
                index, [replace(a, expire_time=p["expire_time"])])

    def _apply_vault_accessor_delete(self, index: int, p: dict) -> None:
        self.store.delete_vault_accessors(index, list(p["accessors"]))

    def _apply_periodic_launch(self, index: int, p: dict) -> None:
        self.store.upsert_periodic_launch(index, p["namespace"], p["job_id"],
                                          p["launch_time"])

    def _apply_deployment_delete(self, index: int, p: dict) -> None:
        self.store.delete_deployments(index, p["deployment_ids"])

    def _apply_deployment_status_update(self, index: int, p: dict) -> None:
        self.store.update_deployment_status(
            index, p["update"], p.get("job"), p.get("evals"))
        st = p.get("stability")
        if st:
            # same raft entry as the status change: success + stable marker
            # commit or replay together
            self.store.update_job_stability(
                index, st["namespace"], st["job_id"], st["version"],
                st["stable"])
        for ev in p.get("evals", []):
            self.enqueue_eval(ev)

    def _apply_deployment_promotion(self, index: int, p: dict) -> None:
        self.store.update_deployment_promotion(
            index, p["deployment_id"], p.get("groups"), p.get("evals"))
        for ev in p.get("evals", []):
            self.enqueue_eval(ev)

    def _apply_job_stability(self, index: int, p: dict) -> None:
        self.store.update_job_stability(
            index, p["namespace"], p["job_id"], p["version"], p["stable"])

    def _reconcile_job_statuses(self, index: int, p: dict) -> None:
        """Derive job status from alloc states (fsm setJobStatus analog)."""
        seen = set()
        for stub in (p.get("allocs_placed", []) + p.get("allocs_stopped", [])
                     + p.get("allocs_preempted", [])):
            a = self.store.alloc_by_id(stub.id) or stub
            key = (a.namespace, a.job_id)
            if key in seen or not key[1]:
                continue
            seen.add(key)
            self.store.reconcile_job_status(index, *key)

    # -- eval routing --------------------------------------------------
    def enqueue_eval(self, ev: Evaluation) -> None:
        if ev.should_enqueue():
            self.eval_broker.enqueue(ev)
        elif ev.should_block():
            self.blocked_evals.block(ev)

    def cancel_evals(self, evals: List[Evaluation]) -> None:
        """Write back as canceled the waiting evals the broker shed
        when a later eval of their job superseded them
        (EvalBroker._shed_superseded): one raft entry for the lot."""
        out = []
        for ev in evals:
            gone = ev.copy()
            gone.status = EVAL_STATUS_CANCELED
            gone.status_description = (
                "canceled: a later preemption eval of the job is pending "
                "and reconciles for this one's evictions too")
            out.append(gone)
        self.raft_apply("eval_update", dict(evals=out))

    def _unblock_enqueue(self, ev: Evaluation) -> None:
        """Blocked eval woken: back to pending + broker."""
        woke = ev.copy()
        woke.status = EVAL_STATUS_PENDING
        index = self.raft_apply("eval_update", dict(evals=[woke]))

    # -- north-bound API (the RPC endpoint surface) --------------------
    def register_job(self, job: Job,
                     triggered_by: str = TRIGGER_JOB_REGISTER,
                     enforce_index: bool = False,
                     job_modify_index: int = 0
                     ) -> Optional[Evaluation]:
        """Job.Register (nomad/job_endpoint.go:79): the admission
        pipeline — canonicalize, implied constraints, validate — then
        upsert and create an eval. Periodic and parameterized jobs
        get no eval — the dispatcher / Job.Dispatch creates child jobs
        which do (job_endpoint.go:236-247). With `enforce_index`, the
        register is a compare-and-set against the job's current modify
        index (`job run -check-index`; job_endpoint.go:175
        RegisterEnforceIndexErrPrefix): 0 means "must not exist"."""
        # call -> job committed and eval enqueued, as the server sees
        # it (the client's clock round the PUT adds HTTP and decode)
        with stages.span("job_register", jobs=1):
            if enforce_index:
                # check-and-apply must be atomic w.r.t. sibling
                # enforced registrations (two HTTP threads both reading
                # index 7 and both winning would be the lost update CAS
                # exists to stop)
                with self._register_l:
                    current = self.store.job_by_id(job.namespace, job.id)
                    cur_idx = current.job_modify_index \
                        if current is not None else 0
                    if current is None and job_modify_index != 0:
                        raise ValueError(
                            "Enforcing job modify index "
                            f"{job_modify_index}: job does not exist")
                    if current is not None and \
                            job_modify_index != cur_idx:
                        raise ValueError(
                            "Enforcing job modify index "
                            f"{job_modify_index}: job exists with "
                            f"conflicting job modify index: {cur_idx}")
                    return self._register_job_validated(job,
                                                        triggered_by)
            return self._register_job_validated(job, triggered_by)

    def _register_job_validated(self, job: Job,
                                triggered_by: str
                                ) -> Optional[Evaluation]:
        job.canonicalize()
        # multiregion fan-out (job_endpoint.go:328 multiregionRegister
        # — enterprise in the reference, implemented here over the
        # federation peers): an unpinned multiregion job localizes one
        # copy per region entry; copies are region-pinned so they never
        # re-fan when they arrive at the peer
        if job.multiregion is not None and \
                job.region in ("", "global"):
            return self._multiregion_register(job, triggered_by)
        self._validate_register(job)
        return self._commit_register(job, triggered_by)

    def _validate_register(self, job: Job) -> None:
        """Post-canonicalize admission checks for one register —
        namespace existence, connect/expose hooks, implied constraints,
        spec validation. Raises ValueError; runs in the SUBMITTER's
        thread so a bad job in a bulk batch fails only its own slot,
        before anything is parked on the gateway."""
        # the requested namespace must exist (job_endpoint.go Register:
        # "non-existent namespace"); "default" exists implicitly
        if self.store.namespace_by_name(job.namespace) is None:
            raise ValueError(
                f"job {job.id!r} is in nonexistent namespace "
                f"{job.namespace!r}")
        # connect + expose-check hooks (job_endpoint_hook_connect.go,
        # job_endpoint_hook_expose_check.go): inject sidecar/gateway
        # proxy tasks and check expose paths before implied
        # constraints and validation
        from .connect_hook import (connect_mutate, connect_validate,
                                   expose_check_mutate,
                                   expose_check_validate)
        connect_mutate(job, self.config.connect_sidecar_driver,
                       self.config.connect_sidecar_config)
        errs = expose_check_validate(job)
        if not errs:
            expose_check_mutate(job)
        self._implied_constraints(job)
        errs = errs + connect_validate(job) + job.validate()
        if errs:
            raise ValueError("; ".join(errs))

    def _commit_register(self, job: Job,
                         triggered_by: str) -> Optional[Evaluation]:
        """Land one fully validated register. Through the ingest
        gateway (ISSUE 19) the job and its eval ride ONE coalesced
        entry — the eval's job-modify fence is stamped at apply time so
        WAL replay stays deterministic; without a gateway the unchanged
        two-entry path runs."""
        ev = None
        if not (job.is_periodic() or job.is_parameterized()):
            ev = Evaluation(
                namespace=job.namespace, priority=job.priority,
                type=job.type, triggered_by=triggered_by, job_id=job.id,
                status=EVAL_STATUS_PENDING)
        if self.ingest is not None:
            index = self.ingest.submit(
                "job_register",
                dict(job=job, evals=[ev] if ev is not None else []))
            if ev is None:
                return None
            ev.job_modify_index = index
            ev.modify_index = index
            return ev
        index = self.raft_apply("job_register", dict(job=job, evals=[]))
        if ev is None:
            return None
        ev.job_modify_index = index
        ev.modify_index = index
        self.raft_apply("eval_update", dict(evals=[ev]))
        return ev

    def register_jobs_bulk(self, jobs: List[Job],
                           triggered_by: str = TRIGGER_JOB_REGISTER
                           ) -> List:
        """Array-body bulk register (ISSUE 19, `PUT /v1/jobs` with a
        list): validate each job in the caller's thread, park every
        admitted one on the gateway, then gather — one raft entry /
        store transaction for the whole admitted run. Returns one
        result PER INPUT in order: an Evaluation (or None for
        periodic/parameterized jobs) on success, the Exception
        otherwise — a validation failure fails ONLY its own slot, a
        batch-commit failure fails every parked slot."""
        if self.ingest is None:
            out = []
            for job in jobs:
                try:
                    out.append(self.register_job(job, triggered_by))
                except Exception as e:
                    out.append(e)
            return out
        with stages.span("job_register", jobs=len(jobs)):
            return self._register_jobs_coalesced(jobs, triggered_by)

    def _register_jobs_coalesced(self, jobs: List[Job],
                                 triggered_by: str) -> List:
        """register_jobs_bulk through the ingest gateway: park every
        admitted job, then gather."""
        slots = []              # (future | None, ev | result, err | None)
        for job in jobs:
            try:
                job.canonicalize()
                if job.multiregion is not None and \
                        job.region in ("", "global"):
                    # multiregion fans out over federation peers —
                    # inherently per-job, never coalesced
                    slots.append((None, self._multiregion_register(
                        job, triggered_by), None))
                    continue
                self._validate_register(job)
                ev = None
                if not (job.is_periodic() or job.is_parameterized()):
                    ev = Evaluation(
                        namespace=job.namespace, priority=job.priority,
                        type=job.type, triggered_by=triggered_by,
                        job_id=job.id, status=EVAL_STATUS_PENDING)
                fut = self.ingest.submit_async(
                    "job_register",
                    dict(job=job, evals=[ev] if ev is not None else []))
                slots.append((fut, ev, None))
            except Exception as e:
                slots.append((None, None, e))
        out = []
        for fut, ev, err in slots:
            if err is not None:
                out.append(err)
                continue
            if fut is None:
                out.append(ev)      # multiregion result, already final
                continue
            try:
                index = fut.result()
                if ev is not None:
                    ev.job_modify_index = index
                    ev.modify_index = index
                out.append(ev)
            except Exception as e:
                out.append(e)
        return out

    def deregister_job_global(self, namespace: str, job_id: str,
                              purge: bool = False):
        """Multiregion stop (job_endpoint_oss.go multiregionStop):
        fan the deregister to every region in the stored job's
        multiregion block, then stop locally."""
        job = self.store.job_by_id(namespace, job_id)
        failed = []
        if job is not None and job.multiregion is not None:
            for entry in job.multiregion.regions:
                if entry.name == self.config.region:
                    continue
                peer = self.config.region_peers.get(entry.name)
                if not peer:
                    failed.append(f"{entry.name} (no federation peer)")
                    continue
                req = urllib.request.Request(
                    f"http://{peer}/v1/job/{job_id}?region={entry.name}"
                    f"&purge={str(purge).lower()}"
                    f"&namespace={namespace}",
                    method="DELETE")
                if self.config.replication_token:
                    req.add_header("X-Nomad-Token",
                                   self.config.replication_token)
                try:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        r.read()
                except Exception as e:
                    LOG.exception("multiregion stop in %s failed",
                                  entry.name)
                    failed.append(f"{entry.name} ({e})")
        ev = self.deregister_job(namespace, job_id, purge=purge)
        if failed:
            # the local stop stuck, but the operator must hear that
            # other regions did NOT stop
            raise RuntimeError(
                f"job stopped in {self.config.region!r} but deregister "
                f"failed in: {', '.join(failed)}")
        return ev

    def _multiregion_register(self, job: Job, triggered_by: str):
        """Localize one copy per multiregion region entry and land it
        in that region: the local region registers directly, remote
        regions get an HTTP push through their federation peer. Region
        entries override datacenters, fill zero group counts, and merge
        meta (the documented enterprise semantics). Cross-region
        deployment pacing (max_parallel/on_failure) is not enforced —
        regions roll independently."""
        import copy
        errs = job.validate()
        if errs:
            raise ValueError("; ".join(errs))
        mr = job.multiregion
        missing = [r.name for r in mr.regions
                   if r.name != self.config.region
                   and r.name not in self.config.region_peers]
        if missing:
            raise ValueError(
                f"no federation peer for multiregion regions {missing}")
        local_eval = None
        for entry in mr.regions:
            local = copy.deepcopy(job)
            local.region = entry.name
            if entry.datacenters:
                local.datacenters = list(entry.datacenters)
            if entry.meta:
                local.meta = {**local.meta, **entry.meta}
            if entry.count > 0:
                for tg in local.task_groups:
                    if tg.count == 0:
                        tg.count = entry.count
            if entry.name == self.config.region:
                local_eval = self.register_job(local, triggered_by)
            else:
                self._push_job_to_region(entry.name, local)
        return local_eval

    def _push_job_to_region(self, region: str, job: Job) -> None:
        import urllib.request
        from ..utils.codec import to_wire
        peer = self.config.region_peers[region]
        body = json.dumps({"Job": to_wire(job)}).encode()
        headers = {"Content-Type": "application/json"}
        if self.config.replication_token:
            headers["X-Nomad-Token"] = self.config.replication_token
        req = urllib.request.Request(
            f"http://{peer}/v1/jobs?region={region}", data=body,
            method="PUT", headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                resp.read()
        except urllib.error.HTTPError as e:
            try:
                msg = json.loads(e.read()).get("error", str(e))
            except Exception:
                msg = str(e)
            raise ValueError(f"multiregion register in {region!r} "
                             f"failed: {msg}")
        except urllib.error.URLError as e:
            raise RuntimeError(f"multiregion register: no route to "
                               f"region {region!r}: {e.reason}")

    def evaluate_job(self, namespace: str, job_id: str) -> Evaluation:
        """Force a fresh evaluation of a job (job_endpoint.go
        Evaluate) — `nomad job eval`."""
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            raise KeyError(f"job {job_id} not found")
        ev = Evaluation(
            namespace=namespace, priority=job.priority, type=job.type,
            triggered_by=TRIGGER_JOB_REGISTER, job_id=job_id,
            status=EVAL_STATUS_PENDING)
        self.raft_apply("eval_update", dict(evals=[ev]))
        return ev

    def stop_alloc(self, alloc_id: str) -> Evaluation:
        """Stop one allocation and evaluate its job for a replacement
        (alloc_endpoint.go Stop: a desired transition plus an eval)."""
        from ..models.alloc import DesiredTransition
        alloc = self.store.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(f"alloc {alloc_id[:8]} not found")
        job = alloc.job or self.store.job_by_id(alloc.namespace,
                                                alloc.job_id)
        ev = Evaluation(
            namespace=alloc.namespace,
            priority=job.priority if job else 50,
            type=job.type if job else "service",
            triggered_by="alloc-stop", job_id=alloc.job_id,
            status=EVAL_STATUS_PENDING)
        payload = dict(alloc_ids=[alloc_id],
                       transition=DesiredTransition(migrate=True),
                       evals=[ev])
        if self.ingest is not None:
            self.ingest.submit("alloc_desired_transition", payload)
        else:
            self.raft_apply("alloc_desired_transition", payload)
        return ev

    def dispatch_job(self, namespace: str, job_id: str,
                     payload: bytes = b"",
                     meta: Optional[Dict[str, str]] = None) -> Evaluation:
        """Job.Dispatch (nomad/job_endpoint.go Dispatch): instantiate a
        parameterized job as a one-shot child with the given payload and
        meta. Child ID is `<parent>/dispatch-<unix>-<rand>`."""
        import os
        meta = dict(meta or {})
        parent = self.store.job_by_id(namespace, job_id)
        if parent is None:
            raise KeyError(f"job {job_id} not found")
        if not parent.is_parameterized():
            raise ValueError(f"job {job_id} is not parameterized")
        if parent.stopped():
            raise ValueError(f"job {job_id} is stopped")
        cfg = parent.parameterized_job
        if cfg.payload == "forbidden" and payload:
            raise ValueError("payload forbidden by the parameterized job")
        if cfg.payload == "required" and not payload:
            raise ValueError("payload required by the parameterized job")
        if len(payload) > 16 * 1024:
            raise ValueError("payload exceeds the 16KiB maximum")
        required = set(cfg.meta_required)
        allowed = required | set(cfg.meta_optional)
        missing = required - set(meta)
        if missing:
            raise ValueError(f"missing required meta keys: {sorted(missing)}")
        unexpected = set(meta) - allowed
        if unexpected:
            raise ValueError(f"unpermitted meta keys: {sorted(unexpected)}")

        child = parent.copy()
        child.id = (f"{parent.id}/dispatch-{int(time.time())}-"
                    f"{os.urandom(4).hex()}")
        child.parent_id = parent.id
        child.dispatched = True
        child.payload = payload
        child.meta = {**parent.meta, **meta}
        child.status = ""
        child.stable = False
        child.version = 0
        ev = self.register_job(child)
        assert ev is not None
        return ev

    def deregister_job(self, namespace: str, job_id: str,
                       purge: bool = False) -> Evaluation:
        job = self.store.job_by_id(namespace, job_id)
        ev = Evaluation(
            namespace=namespace,
            priority=job.priority if job else 50,
            type=job.type if job else JOB_TYPE_SERVICE,
            triggered_by=TRIGGER_JOB_DEREGISTER, job_id=job_id,
            status=EVAL_STATUS_PENDING)
        self.raft_apply("job_deregister",
                        dict(namespace=namespace, job_id=job_id, purge=purge,
                             evals=[ev]))
        return ev

    def plan_job(self, job: Job, diff: bool = True) -> dict:
        """Job.Plan (nomad/job_endpoint.go Plan:600): dry-run the
        scheduler against a copy of current state; nothing is committed.
        Returns the annotated plan, failed placements, and the job diff."""
        from ..models.diff import job_diff
        from ..scheduler.harness import Harness
        job = job.copy()
        job.canonicalize()
        errs = job.validate()
        if errs:
            raise ValueError("; ".join(errs))
        old_job = self.store.job_by_id(job.namespace, job.id)

        shadow = StateStore()
        shadow.restore(self.store.dump())
        h = Harness(shadow)
        index = self.store.latest_index() + 1
        shadow.upsert_job(index, job)
        ev = Evaluation(
            namespace=job.namespace, priority=job.priority, type=job.type,
            triggered_by=TRIGGER_JOB_REGISTER, job_id=job.id,
            status=EVAL_STATUS_PENDING, annotate_plan=True)
        ev.job_modify_index = index
        h.process(job.type if job.type in self.config.enabled_schedulers
                  else JOB_TYPE_SERVICE, ev)
        plan = h.plans[-1] if h.plans else None
        from ..utils.codec import to_wire
        annotations = (to_wire(plan.annotations)
                       if plan is not None and plan.annotations else None)
        final_eval = h.evals[-1] if h.evals else ev
        the_diff = None
        if diff:
            # the diff carries human-readable annotations (update
            # counts, forces-* markers — scheduler/annotate.go)
            from ..scheduler.annotate import annotate
            the_diff = annotate(
                job_diff(old_job, job),
                {"DesiredTGUpdates": annotations["desired_tg_updates"]}
                if annotations else None)
        return {
            "annotations": annotations,
            "failed_tg_allocs": {tg: to_wire(m) for tg, m in
                                 (final_eval.failed_tg_allocs or {}).items()},
            "diff": the_diff,
            "job_modify_index": old_job.job_modify_index if old_job else 0,
            "next_version": (old_job.version + 1
                             if old_job is not None
                             and old_job.specchanged(job) else
                             old_job.version if old_job else 0),
        }

    def scale_job(self, namespace: str, job_id: str, group: str,
                  count: Optional[int] = None, message: str = "",
                  error: bool = False) -> Optional[Evaluation]:
        """Job.Scale (nomad/job_endpoint.go Scale:969): adjust one task
        group's count within its scaling policy bounds; always records a
        scaling event (the autoscaler's audit trail)."""
        from ..models.evaluation import TRIGGER_JOB_SCALE
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            raise KeyError(f"job {job_id} not found")
        if job.stopped():
            raise ValueError(f"job {job_id} is stopped")
        job = job.copy()
        tg = job.lookup_task_group(group)
        if tg is None:
            raise KeyError(f"task group {group!r} not found in {job_id}")
        ev = None
        if count is not None and not error:
            if tg.scaling is not None:
                if count < tg.scaling.min:
                    raise ValueError(
                        f"count {count} below scaling policy minimum "
                        f"{tg.scaling.min}")
                if tg.scaling.max and count > tg.scaling.max:
                    raise ValueError(
                        f"count {count} above scaling policy maximum "
                        f"{tg.scaling.max}")
            prev = tg.count
            tg.count = count
            ev = self.register_job(job, triggered_by=TRIGGER_JOB_SCALE)
            message = message or f"scaled from {prev} to {count}"
        self.raft_apply("scaling_event", dict(
            namespace=namespace, job_id=job_id,
            event=dict(task_group=group, count=count, message=message,
                       error=error, eval_id=ev.id if ev else "",
                       time=int(time.time()))))
        return ev

    # -- dynamic membership (nomad/serf.go + nomad/autopilot.go) -------
    def _apply_server_membership(self, index: int, p: dict) -> None:
        members = list(p.get("members") or [])
        self.store.set_server_members(index, members)
        if self.raft is not None:
            self.raft.update_members(members)

    def join_member(self, addr: str) -> List[str]:
        """Add a server to the voter set (Server.Join; the joiner calls
        this through any member — writes forward to the leader).
        Returns the post-join member list. The read-modify-write of
        the full list is serialized per leader so concurrent joins
        cannot overwrite each other's membership."""
        if self.raft is None:
            raise RuntimeError("not a clustered server")
        with self._member_l:
            current = self.store.server_members() or \
                [self.raft.self_addr] + list(self.raft.peers)
            if addr not in current:
                self.raft_apply("server_membership",
                                dict(members=current + [addr]))
        return self.store.server_members()

    def leave_member(self, addr: str) -> List[str]:
        """Remove a server from the voter set (operator leave or
        autopilot dead-server cleanup)."""
        if self.raft is None:
            raise RuntimeError("not a clustered server")
        with self._member_l:
            current = self.store.server_members() or \
                [self.raft.self_addr] + list(self.raft.peers)
            if addr in current:
                self.raft_apply(
                    "server_membership",
                    dict(members=[m for m in current if m != addr]))
        return self.store.server_members()

    def join_cluster(self, via_addr: str) -> None:
        """Joiner side: ask an existing member to add us, then adopt
        the returned member list (the serf-join analog)."""
        if self.raft is None:
            raise RuntimeError("attach_raft first")
        from ..rpc.client import RpcClient
        c = RpcClient(via_addr, dial_timeout_s=3.0)
        try:
            res = c.call("Server.Join",
                         {"addr": self.raft.self_addr}, timeout_s=30.0)
        finally:
            c.close()
        members = list(res.get("members") or [])
        if members:
            self.raft.update_members(members)

    def handle_peer_failure_report(self, addr: str,
                                   reporter: str = "") -> bool:
        """A peer's SWIM verdict arrived (Server.ReportFailed). Leader
        only: verify the target is unreachable from HERE too (implicit
        refutation — a live server answers and the report is dropped),
        then remove it under the same quorum guard autopilot uses.
        Returns True when the member was removed."""
        raft = self.raft
        if raft is None or not raft.is_leader():
            from ..rpc.codec import RpcRefused
            raise RpcRefused("not the leader")
        if addr == raft.self_addr:
            return False
        members = self.store.server_members() or \
            [raft.self_addr] + list(raft.peers)
        if addr not in members:
            return False                # already gone
        if self.swim is not None and self.swim.probe_for_peer(addr):
            LOG.info("swim report for %s from %s refuted by leader "
                     "probe", addr, reporter)
            return False
        alive = len(members) - 1
        if alive * 2 <= len(members):
            LOG.warning("swim: not removing %s — quorum guard", addr)
            return False
        LOG.warning("swim: removing failed server %s (reported by %s)",
                    addr, reporter)
        self.leave_member(addr)
        return True

    def _autopilot_loop(self) -> None:
        """Leader-side dead-server cleanup (nomad/autopilot.go): a
        voter with no successful replication contact past the cleanup
        threshold is removed from the member set, as long as a quorum
        of the REMAINING members is intact."""
        import time as _time
        while self._leader and not getattr(self, "_shutdown", False):
            # re-read per tick: `operator autopilot-set-config` mutates
            # the threshold at runtime (0 disables without killing the
            # loop, so re-enabling works too)
            threshold = self.config.dead_server_cleanup_s
            _time.sleep(max(min(threshold / 4.0, 2.0), 0.5)
                        if threshold > 0 else 1.0)
            raft = self.raft
            if raft is None or not raft.is_leader() or threshold <= 0:
                continue
            now = _time.monotonic()
            peers = list(raft.peers)
            dead = [p for p in peers
                    if now - raft.last_contact.get(p, now) > threshold]
            if not dead:
                continue
            alive = len(peers) - len(dead) + 1
            for p in dead:
                # quorum guard: committing the removal itself needs a
                # majority of the CURRENT cluster — without it the
                # leave write just times out and blocks join/leave
                if alive * 2 <= len(peers) + 1:
                    break
                try:
                    LOG.warning("autopilot: removing dead server %s "
                                "(no contact for %.0fs)", p,
                                now - raft.last_contact.get(p, now))
                    self.leave_member(p)
                except Exception:
                    LOG.exception("autopilot cleanup of %s failed", p)

    # -- event sinks (nomad/stream/sink.go + event_sink_manager.go) ----
    def upsert_event_sink(self, sink) -> int:
        return self.raft_apply("event_sink_upsert", dict(sink=sink))

    def delete_event_sink(self, sink_id: str) -> int:
        return self.raft_apply("event_sink_delete", dict(sink_id=sink_id))

    def _apply_event_sink_upsert(self, index: int, p: dict) -> None:
        self.store.upsert_event_sink(index, p["sink"])

    def _apply_event_sink_delete(self, index: int, p: dict) -> None:
        self.store.delete_event_sink(index, p["sink_id"])

    def _apply_event_sink_progress(self, index: int, p: dict) -> None:
        self.store.update_event_sink_progress(index, p["sink_id"],
                                              int(p["index"]))

    def _apply_scaling_event(self, index: int, p: dict) -> None:
        self.store.add_scaling_event(index, p["namespace"], p["job_id"],
                                     p["event"])

    # -- deployment endpoints (nomad/deployment_endpoint.go) -----------
    def promote_deployment(self, deployment_id: str,
                           groups: Optional[List[str]] = None) -> Evaluation:
        return promote_deployment(self, deployment_id, groups)

    def fail_deployment(self, deployment_id: str,
                        **kw) -> Optional[Evaluation]:
        return fail_deployment(self, deployment_id, **kw)

    def pause_deployment(self, deployment_id: str, pause: bool) -> None:
        pause_deployment(self, deployment_id, pause)

    def revert_job(self, namespace: str, job_id: str,
                   version: int) -> Optional[Evaluation]:
        """Job.Revert (nomad/job_endpoint.go Revert): re-register an
        older version's spec as a new version."""
        target = self.store.job_by_id_and_version(namespace, job_id, version)
        if target is None:
            raise KeyError(f"job {job_id} version {version} not found")
        current = self.store.job_by_id(namespace, job_id)
        if current is not None and current.version == version:
            raise ValueError(
                f"job {job_id} is already at version {version}")
        rolled = target.copy()
        rolled.stable = False
        rolled.version = 0          # reassigned by upsert_job
        return self.register_job(rolled)

    # -- node drain (nomad/node_endpoint.go UpdateDrain) ---------------
    def update_node_drain(self, node_id: str, drain_strategy,
                          mark_eligible: bool = False) -> None:
        """Start or clear a drain. Stamps the force deadline from the
        spec's relative deadline (structs.go DrainStrategy.DeadlineTime)."""
        if drain_strategy is not None \
                and drain_strategy.drain_spec.deadline_s > 0 \
                and drain_strategy.force_deadline == 0:
            drain_strategy.force_deadline = (
                time.time() + drain_strategy.drain_spec.deadline_s)
        self.raft_apply("node_drain_update",
                        dict(node_id=node_id, drain_strategy=drain_strategy,
                             mark_eligible=mark_eligible))

    def drain_allocs(self, allocs, jobs) -> None:
        drain_allocs(self, allocs, jobs)

    def register_node(self, node: Node) -> None:
        node.canonicalize()
        if not node.computed_class:
            node.compute_class()
        self.raft_apply("node_register", dict(node=node))
        self.reset_heartbeat_timer(node.id)

    def update_node_status(self, node_id: str, status: str) -> None:
        evals = []
        if status == NODE_STATUS_DOWN:
            evals = self._node_evals(node_id)
        self.raft_apply("node_status_update",
                        dict(node_id=node_id, status=status, evals=evals))

    def update_alloc_status_from_client(self, allocs: List[Allocation]) -> None:
        """Node.UpdateAlloc: client pushes task states; failed allocs
        trigger alloc-failure evals (node_endpoint.go:1065)."""
        evals = self._client_update_evals(allocs)
        payload = dict(allocs=allocs, evals=evals)
        if self.ingest is not None:
            self.ingest.submit("alloc_client_update", payload)
        else:
            self.raft_apply("alloc_client_update", payload)
        self._revoke_terminal_accessors(allocs)

    def update_alloc_status_from_client_batch(
            self, groups: List[List[Allocation]]) -> None:
        """Node.UpdateAllocBatch (ISSUE 19): N clients' update pushes
        in one verb. Each group keeps its own gateway entry (its evals
        are derived from pre-batch state exactly as N concurrent
        Node.UpdateAlloc calls would be), but all of them park together
        and land as one coalesced raft entry / store transaction."""
        if self.ingest is None:
            for g in groups:
                self.update_alloc_status_from_client(g)
            return
        futures = []
        for g in groups:
            evals = self._client_update_evals(g)
            futures.append(self.ingest.submit_async(
                "alloc_client_update", dict(allocs=g, evals=evals)))
        err = None
        for f in futures:
            try:
                f.result()
            except Exception as e:
                err = e
        for g in groups:
            self._revoke_terminal_accessors(g)
        if err is not None:
            raise err

    def _client_update_evals(self, allocs: List[Allocation]
                             ) -> List[Evaluation]:
        evals = []
        seen = set()
        for stub in allocs:
            existing = self.store.alloc_by_id(stub.id)
            if existing is None:
                continue
            if stub.client_status == "failed" and (existing.namespace,
                                                   existing.job_id) not in seen:
                job = self.store.job_by_id(existing.namespace, existing.job_id)
                if job is not None and not job.stopped():
                    seen.add((existing.namespace, existing.job_id))
                    evals.append(Evaluation(
                        namespace=existing.namespace, priority=job.priority,
                        type=job.type, triggered_by="alloc-failure",
                        job_id=existing.job_id, status=EVAL_STATUS_PENDING))
        return evals

    def _revoke_terminal_accessors(self, allocs: List[Allocation]) -> None:
        # revoke vault leases of allocs the client just reported
        # terminal (node_endpoint.go UpdateAlloc -> revokeVaultAccessors);
        # the reaper pass also catches these within its tick
        terminal = {a.id for a in allocs
                    if a.client_status in ("complete", "failed", "lost")}
        if terminal:
            doomed = [va.accessor for aid in terminal
                      for va in self.store.vault_accessors_by_alloc(aid)]
            self.revoke_vault_accessors(doomed)

    def _node_evals(self, node_id: str) -> List[Evaluation]:
        """One eval per job with allocs on the node + each system job
        (node_endpoint.go createNodeEvals:1318)."""
        evals = []
        jobs = set()
        for alloc in self.store.allocs_by_node(node_id):
            key = (alloc.namespace, alloc.job_id)
            if key in jobs:
                continue
            jobs.add(key)
            job = alloc.job or self.store.job_by_id(*key)
            if job is None:
                continue
            evals.append(Evaluation(
                namespace=key[0], priority=job.priority, type=job.type,
                triggered_by=TRIGGER_NODE_UPDATE, job_id=key[1],
                node_id=node_id, status=EVAL_STATUS_PENDING))
        for job in self.store.jobs():
            if job.type == JOB_TYPE_SYSTEM and job.namespaced_id() not in jobs \
                    and not job.stopped():
                evals.append(Evaluation(
                    namespace=job.namespace, priority=job.priority,
                    type=job.type, triggered_by=TRIGGER_NODE_UPDATE,
                    job_id=job.id, node_id=node_id,
                    status=EVAL_STATUS_PENDING))
        return evals

    # -- ACL (nomad/acl_endpoint.go; acl/acl.go engine) ----------------
    def bootstrap_acl(self):
        """One-time management-token mint (acl_endpoint.go Bootstrap).
        Raises if the cluster already has tokens."""
        from ..acl import new_token
        if self.store.acl_tokens():
            raise PermissionError("ACL bootstrap already done")
        token = new_token(name="Bootstrap Token", type_="management",
                          global_=True)
        self.raft_apply("acl_token_upsert", dict(tokens=[token]))
        return token

    def upsert_acl_policies(self, policies) -> int:
        from ..acl import parse_policy_rules
        for p in policies:
            if not p.name:
                raise ValueError("policy name required")
            parse_policy_rules(p.rules)        # validate
        return self.raft_apply("acl_policy_upsert", dict(policies=policies))

    def delete_acl_policies(self, names) -> int:
        return self.raft_apply("acl_policy_delete", dict(names=names))

    def create_acl_token(self, name: str = "", type_: str = "client",
                         policies=None, global_: bool = False):
        from ..acl import new_token
        if type_ not in ("client", "management"):
            raise ValueError(f"invalid token type {type_!r}")
        if type_ == "client" and not policies:
            raise ValueError("client token requires policies")
        token = new_token(name=name, type_=type_, policies=policies,
                          global_=global_)
        self.raft_apply("acl_token_upsert", dict(tokens=[token]))
        return token

    def delete_acl_tokens(self, accessor_ids) -> int:
        return self.raft_apply("acl_token_delete",
                               dict(accessor_ids=accessor_ids))

    def resolve_token(self, secret_id):
        """secret -> compiled ACL (nomad/acl.go ResolveToken). With ACLs
        disabled everything is management; with no token the anonymous
        deny-all ACL applies; unknown secrets are rejected."""
        from ..acl import ACL_MANAGEMENT, compile_acl
        from ..acl.acl import ACL_DENY_ALL
        if not self.config.acl_enabled:
            return ACL_MANAGEMENT
        if not secret_id:
            return ACL_DENY_ALL
        token = self.store.acl_token_by_secret(secret_id)
        if token is None:
            raise PermissionError("ACL token not found")
        if token.type == "management":
            return ACL_MANAGEMENT
        key = (tuple(sorted(token.policies)),
               self.store._root.indexes.get("acl_policies") or 0)
        cached = self._acl_cache.get(key)
        if cached is not None:
            return cached
        policies = [p for name in token.policies
                    if (p := self.store.acl_policy(name)) is not None]
        acl = compile_acl(policies)
        if len(self._acl_cache) > 256:
            self._acl_cache.clear()
        self._acl_cache[key] = acl
        return acl

    @staticmethod
    def _implied_constraints(job: Job) -> None:
        """jobImpliedConstraints (job_endpoint_hooks.go:114): auto-add
        group constraints implied by feature use — vault stanzas need a
        vault-capable node, signal-based change modes need nodes
        advertising those signals."""
        from ..models import Constraint
        for tg in job.task_groups:
            wants_vault = any(t.vault is not None for t in tg.tasks)
            signals = set()
            for t in tg.tasks:
                if t.kill_signal:
                    signals.add(t.kill_signal)
                if t.vault is not None and t.vault.change_signal:
                    signals.add(t.vault.change_signal)
                for tmpl in t.templates:
                    if tmpl.change_signal:
                        signals.add(tmpl.change_signal)
            have = {(c.ltarget, c.operand) for c in tg.constraints}
            if wants_vault and \
                    ("${attr.vault.version}", "is_set") not in have:
                tg.constraints.append(Constraint(
                    ltarget="${attr.vault.version}", rtarget="",
                    operand="is_set"))
            if signals and ("${attr.os.signals}",
                            "set_contains") not in have:
                tg.constraints.append(Constraint(
                    ltarget="${attr.os.signals}",
                    rtarget=",".join(sorted(signals)),
                    operand="set_contains"))

    # -- namespaces (nomad/namespace_endpoint.go) ----------------------
    def upsert_namespaces(self, namespaces: list) -> int:
        errs = []
        for ns in namespaces:
            errs.extend(ns.validate())
        if errs:
            raise ValueError("; ".join(errs))
        return self.raft_apply("namespace_upsert",
                               dict(namespaces=list(namespaces)))

    def delete_namespaces(self, names: list) -> int:
        """DeleteNamespaces:66 — "default" is undeletable and occupied
        namespaces (non-terminal jobs) refuse deletion."""
        from ..models.namespace import DEFAULT_NAMESPACE
        for name in names:
            if name == DEFAULT_NAMESPACE:
                raise ValueError("default namespace can not be deleted")
            if self.store.namespace_by_name(name) is None:
                raise KeyError(f"namespace {name} not found")
            occupied = [j.id for j in self.store.jobs()
                        if j.namespace == name
                        and j.status != "dead"]
            if occupied:
                raise ValueError(
                    f"namespace {name!r} has non-terminal jobs: "
                    f"{sorted(occupied)[:5]}")
        return self.raft_apply("namespace_delete", dict(names=names))

    # -- service registry (built-in catalog) ---------------------------
    def update_service_registrations(self, upserts=None,
                                     delete_alloc_ids=None,
                                     delete_ids=None) -> int:
        """Client-driven catalog sync: register live services, drop the
        rows of stopped allocs (the reference's Consul sync loop,
        command/agent/consul/service_client.go sync)."""
        index = 0
        if upserts:
            index = self.raft_apply("service_registration_upsert",
                                    dict(services=list(upserts)))
        if delete_alloc_ids or delete_ids:
            index = self.raft_apply(
                "service_registration_delete",
                dict(ids=list(delete_ids or []),
                     alloc_ids=list(delete_alloc_ids or [])))
        return index

    def list_services(self, namespace: str = "default") -> list:
        """Per-service summary (nomad service list analog): name, tags,
        live instance count."""
        summary: Dict[str, dict] = {}
        for s in self.store.service_registrations(namespace):
            row = summary.setdefault(
                s.service_name,
                {"ServiceName": s.service_name, "Namespace": s.namespace,
                 "Tags": set(), "Instances": 0})
            row["Tags"].update(s.tags)
            row["Instances"] += 1
        out = []
        for name in sorted(summary):
            row = summary[name]
            row["Tags"] = sorted(row["Tags"])
            out.append(row)
        return out

    def get_service(self, namespace: str, name: str) -> list:
        return self.store.service_by_name(namespace, name)

    # -- CSI volumes (nomad/csi_endpoint.go; volumewatcher/) -----------
    def register_csi_volume(self, volume) -> int:
        if not volume.id or not volume.plugin_id:
            raise ValueError("volume requires id and plugin_id")
        return self.raft_apply("csi_volume_register",
                               dict(volumes=[volume]))

    def deregister_csi_volume(self, namespace: str, volume_id: str,
                              force: bool = False) -> int:
        v = self.store.csi_volume(namespace, volume_id)
        if v is None:
            raise KeyError(f"volume {volume_id} not found")
        if not force and (v.read_allocs or v.write_allocs):
            raise ValueError(
                f"volume {volume_id} has active claims (use force)")
        return self.raft_apply("csi_volume_deregister",
                               dict(namespace=namespace,
                                    volume_id=volume_id))

    def _watch_volumes(self) -> None:
        """Volume watcher (nomad/volumewatcher): release claims held by
        terminal allocations so volumes become schedulable again."""
        while not getattr(self, "_shutdown", False):
            time.sleep(1.0)
            if not self._leader:
                continue
            try:
                for v in self.store.csi_volumes():
                    for aid in (list(v.read_allocs)
                                + list(v.write_allocs)):
                        alloc = self.store.alloc_by_id(aid)
                        if alloc is None or alloc.terminal_status():
                            self.raft_apply(
                                "csi_volume_release",
                                dict(namespace=v.namespace,
                                     volume_id=v.id, alloc_id=aid))
            except Exception:     # pragma: no cover — best effort
                LOG.exception("volume watcher pass failed")
            try:
                self._reap_vault_accessors()
            except Exception:     # pragma: no cover — best effort
                LOG.exception("vault accessor reap failed")

    # -- Vault integration (nomad/vault.go:176 vaultClient) ------------
    def derive_vault_token(self, alloc_id: str, tasks) -> Dict[str, dict]:
        """Token derivation for tasks with a vault stanza
        (node_endpoint.go DeriveVaultToken + vault.go CreateToken).
        The embedded authority mints a TTL'd token + accessor per task
        and tracks the lease in the replicated store, so revocation and
        renewal survive leader failover (see server/vault.py). Returns
        {task: {token, accessor, ttl_s}}."""
        alloc = self.store.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(f"alloc {alloc_id} not found")
        if alloc.terminal_status():
            raise ValueError(f"alloc {alloc_id} is terminal")
        from ..server.vault import VaultAccessor
        from ..utils.codec import to_wire
        from ..utils.ids import generate_uuid
        tg = alloc.job.lookup_task_group(alloc.task_group) \
            if alloc.job else None
        policies: Dict[str, list] = {}
        if tg is not None:
            for t in tg.tasks:
                if t.vault is not None:
                    policies[t.name] = list(t.vault.policies)
        now = time.time()
        ttl = self.config.vault_token_ttl_s
        accessors, out = [], {}
        # node_endpoint.go DeriveVaultToken: reject tasks that don't
        # exist in the alloc's group or carry no vault stanza — a
        # client must not be able to mint tokens for arbitrary names
        unknown = [t for t in tasks if t not in policies]
        if unknown:
            raise ValueError(
                f"tasks {unknown} do not exist in alloc {alloc_id} "
                "or have no vault stanza")
        for task in tasks:
            tok = f"s.{generate_uuid()[:24]}"
            acc = generate_uuid()
            accessors.append(VaultAccessor(
                accessor=acc, token=tok, alloc_id=alloc_id, task=task,
                node_id=alloc.node_id, policies=policies.get(task, []),
                ttl_s=ttl, create_time=now, expire_time=now + ttl))
            out[task] = {"token": tok, "accessor": acc, "ttl_s": ttl}
        self.raft_apply("vault_accessor_upsert",
                        dict(accessors=[to_wire(a) for a in accessors]))
        return out

    def renew_vault_token(self, accessor: str, token: str) -> float:
        """Extend a lease (vault.go RenewToken / client-side renewal
        loop target). Raises on unknown/revoked/expired leases — the
        client must re-derive then."""
        a = self.store.vault_accessor(accessor)
        if a is None or a.token != token:
            raise KeyError("unknown vault accessor")
        now = time.time()
        if a.expired(now):
            # reap lazily; the renewal failure tells the client to
            # re-derive (vaultclient.go renewal error path)
            self.raft_apply("vault_accessor_delete",
                            dict(accessors=[accessor]))
            raise ValueError("vault token lease expired")
        self.raft_apply("vault_accessor_renew",
                        dict(accessor=accessor,
                             expire_time=now + a.ttl_s))
        return a.ttl_s

    def revoke_vault_accessors(self, accessors: List[str]) -> None:
        """vault.go RevokeTokens: the embedded backend simply drops the
        lease rows — a dropped row IS an invalid token here."""
        if accessors:
            from ..utils import metrics
            metrics.incr_counter("nomad.vault.revoked", len(accessors))
            self.raft_apply("vault_accessor_delete",
                            dict(accessors=list(accessors)))

    def lookup_vault_token(self, token: str) -> bool:
        """Is this token currently valid? (vault TokenLookup analog,
        used by tests and operator introspection)."""
        a = self.store.vault_accessor_by_token(token)
        return a is not None and not a.expired()

    def _reap_vault_accessors(self) -> None:
        """Leader-side revocation daemon (vault.go revokeDaemon +
        nomad/node_endpoint.go revoking accessors of terminal allocs):
        drop leases whose alloc is gone/terminal or whose TTL lapsed
        without renewal."""
        now = time.time()
        doomed = []
        for a in self.store.vault_accessors():
            alloc = self.store.alloc_by_id(a.alloc_id)
            if alloc is None or alloc.terminal_status() or a.expired(now):
                doomed.append(a.accessor)
        self.revoke_vault_accessors(doomed)

    # -- heartbeats (nomad/heartbeat.go) -------------------------------
    def reset_heartbeat_timer(self, node_id: str) -> None:
        if self.raft is not None and not self._leader:
            return              # TTL timers are leader-only (heartbeat.go)
        self._heartbeats.reset(node_id, self.config.heartbeat_ttl_s)

    def _invalidate_heartbeat(self, node_id: str) -> None:
        node = self.store.node_by_id(node_id)
        if node is None or node.status == NODE_STATUS_DOWN:
            return
        LOG.warning("node %s missed heartbeat, marking down", node_id[:8])
        self.update_node_status(node_id, NODE_STATUS_DOWN)

    def heartbeat(self, node_id: str,
                  stats: Optional[dict] = None) -> float:
        """Client TTL renewal; returns the TTL. `stats` is the compact
        host-stats summary the client sampler attaches (ISSUE 13) —
        stashed per node for cluster_stats() to fold; O(1) per beat,
        the rollup itself runs at telemetry cadence, not here."""
        node = self.store.node_by_id(node_id)
        if node is None:
            raise KeyError(f"node {node_id} not registered")
        from ..chaos import faults as chaos_faults
        if chaos_faults.ACTIVE and \
                chaos_faults.fire("server.heartbeat", node_id=node_id):
            # chaos hook (ISSUE 15): the beat is dropped in transit —
            # the client believes it renewed, but the TTL timer keeps
            # running toward node-down and the stale-stats clock ages
            # the last payload toward `stale_heartbeats`
            return self.config.heartbeat_ttl_s
        if stats:
            with self._node_stats_l:
                self._node_stats[node_id] = {
                    **stats, "received_at": time.time()}
        if node.status != NODE_STATUS_READY:
            self.update_node_status(node_id, NODE_STATUS_READY)
        self.reset_heartbeat_timer(node_id)
        return self.config.heartbeat_ttl_s

    # -- cluster rollup (ISSUE 13) -------------------------------------
    def _telemetry_extra(self) -> Dict[str, float]:
        """The telemetry collector's extra_fn: device-mirror residency
        plus the cluster.* family, so fleet economics land in the
        retained ring every sample."""
        out: Dict[str, float] = {
            "device.mirror_bytes":
            self.store.table_cache.device_mirror_bytes()}
        for k, v in self.cluster_stats().items():
            out[f"cluster.{k}"] = v
        return out

    def cluster_stats(self) -> Dict[str, float]:
        """Fold per-node heartbeat host-stats into fleet economics:
        nodes up/down, capacity vs ALLOCATED (bin-packed, from the
        resident columnar node table) vs actually USED (host truth,
        from the heartbeat payloads), per-node utilization p50/p99,
        stale-heartbeat count. Pure host reads — O(nodes) numpy sums;
        also mirrors the family into the metrics registry so
        /v1/metrics?format=prometheus exposes nomad.cluster.*."""
        snap = self.store.snapshot()
        nodes = snap.nodes()
        now = time.time()
        with self._node_stats_l:
            # prune payloads for nodes the store no longer knows
            known = {n.id for n in nodes}
            for nid in list(self._node_stats):
                if nid not in known:
                    del self._node_stats[nid]
            stats = dict(self._node_stats)
        out: Dict[str, float] = {
            "nodes_total": float(len(nodes)),
            "nodes_ready": float(sum(1 for n in nodes if n.ready())),
            "nodes_down": float(sum(
                1 for n in nodes if n.status == NODE_STATUS_DOWN)),
            "nodes_reporting": 0.0,
            "stale_heartbeats": 0.0,
        }
        cap_cpu = cap_mem = 0.0
        for n in nodes:
            res = n.comparable_resources()
            cap_cpu += res.cpu_shares
            cap_mem += res.memory_mb
        out["fleet_cpu_capacity_mhz"] = cap_cpu
        out["fleet_mem_capacity_mb"] = cap_mem
        # allocated: the resident node table's live-alloc usage sums
        # (delta-maintained — no per-sample alloc scan). build=False:
        # a rollup must never trigger a cold table build; before the
        # first eval the allocated half reads 0 and catches up with
        # the first scheduled table
        alloc_cpu = alloc_mem = 0.0
        table = snap.node_table(build=False)
        if table is not None and table.n > 0:
            alloc_cpu = float(table.base_used[:, 0].sum())
            alloc_mem = float(table.base_used[:, 1].sum())
        out["fleet_cpu_allocated_mhz"] = alloc_cpu
        out["fleet_mem_allocated_mb"] = alloc_mem
        out["fleet_cpu_allocated_ratio"] = \
            round(alloc_cpu / cap_cpu, 4) if cap_cpu > 0 else 0.0
        out["fleet_mem_allocated_ratio"] = \
            round(alloc_mem / cap_mem, 4) if cap_mem > 0 else 0.0
        # used: host truth from the heartbeat payloads — a node's
        # host-level utilization FRACTION (cpu percent, mem
        # used/total) scaled by its configured capacity, so both used
        # sums stay commensurate with the capacity denominator and a
        # host busier than its schedulable share can't push a fleet
        # ratio past 1.0. Stale payloads drop out of the used sums
        # (their capacity still counts: unreported usage is unknown,
        # not 0)
        used_cpu = used_mem = 0.0
        cpu_pcts: List[float] = []
        mem_ratios: List[float] = []
        stale_after = self.config.stats_stale_after_s
        by_id = {n.id: n for n in nodes}
        for nid, st in stats.items():
            if now - st.get("received_at", 0.0) > stale_after:
                out["stale_heartbeats"] += 1.0
                continue
            node = by_id.get(nid)
            if node is None:
                continue
            out["nodes_reporting"] += 1.0
            res = node.comparable_resources()
            pct = float(st.get("cpu_pct", 0.0))
            used_cpu += pct / 100.0 * res.cpu_shares
            cpu_pcts.append(pct)
            total = float(st.get("mem_total_mb", 0.0))
            if total > 0:
                ratio = min(
                    float(st.get("mem_used_mb", 0.0)) / total, 1.0)
                used_mem += ratio * res.memory_mb
                mem_ratios.append(ratio)
        out["fleet_cpu_used_mhz"] = round(used_cpu, 1)
        out["fleet_mem_used_mb"] = round(used_mem, 1)
        out["fleet_cpu_used_ratio"] = \
            round(used_cpu / cap_cpu, 4) if cap_cpu > 0 else 0.0
        out["fleet_mem_used_ratio"] = \
            round(used_mem / cap_mem, 4) if cap_mem > 0 else 0.0
        if cpu_pcts:
            arr = np.asarray(cpu_pcts)
            out["node_cpu_pct_p50"] = round(
                float(np.percentile(arr, 50)), 3)
            out["node_cpu_pct_p99"] = round(
                float(np.percentile(arr, 99)), 3)
        if mem_ratios:
            arr = np.asarray(mem_ratios)
            out["node_mem_ratio_p50"] = round(
                float(np.percentile(arr, 50)), 4)
            out["node_mem_ratio_p99"] = round(
                float(np.percentile(arr, 99)), 4)
        for k in ("nodes_total", "nodes_ready", "nodes_down",
                  "nodes_reporting", "stale_heartbeats",
                  "fleet_cpu_used_ratio", "fleet_mem_used_ratio",
                  "fleet_cpu_allocated_ratio",
                  "fleet_mem_allocated_ratio"):
            metrics.set_gauge(f"nomad.cluster.{k}", out[k])
        return out
