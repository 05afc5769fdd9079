"""GenericScheduler: service and batch evaluation processing.

Reference semantics: scheduler/generic_sched.go — Process:125 (retry
loop, 5 service / 2 batch attempts), process:216, computeJobAllocs:332,
computePlacements:468, blocked-eval creation:193.

The placement inner loop differs by design: instead of one stack.Select
per missing alloc, placements are grouped per task group and dispatched
to the batched device kernel (PlacementEngine.select_batch) — the
north-star rewrite (SURVEY.md preamble).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from ..models import (
    AllocatedResources, AllocatedSharedResources, Allocation, AllocMetric,
    Evaluation, Job, Plan,
    ALLOC_CLIENT_FAILED, ALLOC_CLIENT_PENDING, ALLOC_DESIRED_RUN,
    EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED,
    TRIGGER_MAX_PLANS,
)
from ..models.alloc import RescheduleEvent, RescheduleTracker, AllocDeploymentStatus
from ..ops import ProposedIndex
from ..utils import stages
from ..utils.ids import generate_uuid
from .context import EvalContext
from .reconcile import AllocReconciler
from .reconcile_columnar import ColumnarAllocReconciler, columnar_enabled
from .stack import PlacementEngine, SelectOptions, tasks_updated_cached
from .util import (adjust_queued_allocations, tainted_nodes,
                   tainted_nodes_columnar, tasks_updated,
                   update_non_terminal_allocs_to_lost,
                   update_non_terminal_allocs_to_lost_columnar)

MAX_SERVICE_ATTEMPTS = 5
MAX_BATCH_ATTEMPTS = 2
# races an eval may lose in all before it fails (process())
MAX_RACES_LOST = 32

BLOCKED_EVAL_MAX_PLAN_DESC = "created due to placement conflicts"
BLOCKED_EVAL_FAILED_PLACEMENTS = "created to place remaining allocations"
ALLOC_IN_PLACE = "alloc updating in-place"


class SetStatusError(Exception):
    def __init__(self, eval_status: str, msg: str):
        super().__init__(msg)
        self.eval_status = eval_status


class GenericScheduler:
    def __init__(self, state, planner, batch: bool):
        self.state = state
        self.planner = planner
        self.batch = batch

        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result = None
        self.ctx: Optional[EvalContext] = None
        self.engine: Optional[PlacementEngine] = None
        self.deployment = None

        self.blocked: Optional[Evaluation] = None
        # True while this eval reconciles columnar: gates the
        # tasks_updated memo so engine-off (env hatch OR
        # ServerConfig.reconcile_columnar=False) measures the raw
        # reference diff cost, not the memoized one
        self._columnar_active = False
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}
        self.followup_evals: List[Evaluation] = []
        # set by the batched worker: routes kernel dispatches through
        # the multi-eval gateway (one select_many per lane barrier)
        self.kernel_dispatch = None
        # set by concurrent workers: (lane, lanes) hash-slice
        # decorrelation for big batch selects (SelectKernel.decorrelate)
        self.kernel_decorrelate = None
        self._job_allocs = None     # (snapshot, the job's allocations)

    # -- entry ---------------------------------------------------------
    def process(self, evaluation: Evaluation) -> None:
        self.eval = evaluation
        limit = MAX_BATCH_ATTEMPTS if self.batch else MAX_SERVICE_ATTEMPTS

        # retryMax + progressMade (scheduler/util.go:277-310): a round
        # that committed ANYTHING resets the attempt budget — under
        # optimistic concurrency a storm of plan conflicts burns rounds
        # while still converging, and only zero-progress rounds may
        # exhaust the limit. A plan that commits whole or not at all
        # (a one-instance eval; every node-coupling ask: _process_once)
        # can show no partial progress, so a round it LOST — refused
        # against a state newer than the one it ranked: another plan
        # took its node first — resets the budget too: two workers
        # that rank the whole fleet choose the same fullest node, and
        # five races lost in a row (a one-instance eval of the 10k-node
        # service cell, PR 27) are no reason to fail an eval the fleet
        # has room for. A refusal against the very state the plan
        # ranked still counts, and MAX_RACES_LOST bounds the races.
        progress = [False, False]       # committed something, lost a race
        attempts = 0
        races = 0
        while True:
            progress[:] = (False, False)
            try:
                done = self._process_once(progress)
            except SetStatusError as e:
                self._set_status(e.eval_status, str(e))
                return
            if done:
                self._set_status(EVAL_STATUS_COMPLETE, "")
                return
            if progress[0]:
                attempts = 0
                continue
            if progress[1] and races < MAX_RACES_LOST:
                races += 1
                attempts = 0
                continue
            attempts += 1
            if attempts >= limit:
                break
        # retries exhausted on placement conflicts: block so the remaining
        # work is retried when capacity frees (generic_sched.go:150-160)
        if self.blocked is None and self.ctx is not None:
            blocked = self.eval.create_blocked_eval(
                dict(self.ctx.eligibility.class_eligibility),
                self.ctx.eligibility.has_escaped(), "")
            blocked.triggered_by = TRIGGER_MAX_PLANS
            blocked.status_description = BLOCKED_EVAL_MAX_PLAN_DESC
            self.planner.create_eval(blocked)
            self.blocked = blocked
        self._set_status(
            EVAL_STATUS_FAILED,
            f"maximum attempts reached ({limit})")

    # -- one attempt ---------------------------------------------------
    def _process_once(self, progress) -> bool:
        ev = self.eval
        snapshot = self.state
        self.job = snapshot.job_by_id(ev.namespace, ev.job_id)

        self.queued_allocs = {tg.name: 0
                              for tg in (self.job.task_groups if self.job else [])}
        self.failed_tg_allocs = {}
        self.followup_evals = []

        self.plan = ev.make_plan(self.job)
        ranked_index = snapshot.latest_index()
        self.blocked = None
        self.ctx = EvalContext(snapshot, ev, self.plan)
        self.engine = PlacementEngine(snapshot)
        if self.kernel_dispatch is not None:
            self.engine.dispatch = self.kernel_dispatch
        if self.kernel_decorrelate is not None:
            self.engine.kernel.decorrelate = self.kernel_decorrelate
        if self.job is not None:
            self.engine.set_job(self.job)
            self.ctx.eligibility.set_job(self.job)

        self.deployment = None
        if self.job is not None:
            self.deployment = snapshot.latest_deployment_by_job(
                ev.namespace, ev.job_id)

        # compute the changes
        self._compute_job_allocs()

        # if the plan is a no-op, we're done
        if self.plan.is_no_op() and not self.followup_evals \
                and not self.failed_tg_allocs:
            return True

        # create follow-up evals for delayed reschedules
        for fev in self.followup_evals:
            self.planner.create_eval(fev)

        # if there were failures, create/adjust a blocked eval
        if self.failed_tg_allocs and self.blocked is None:
            self.blocked = self.eval.create_blocked_eval(
                dict(self.ctx.eligibility.class_eligibility),
                self.ctx.eligibility.has_escaped(), "")
            self.blocked.status_description = BLOCKED_EVAL_FAILED_PLACEMENTS
            self.planner.create_eval(self.blocked)

        if self.plan.is_no_op():
            return True

        # a node-coupling ask (spread, distinct_*) is one greedy
        # sequence: each step was scored on the steps before it having
        # landed. A plan that keeps what the applier accepted of it and
        # re-places the rest holds nodes no ranking of the fleet would
        # choose (a chosen node's coupled score 0.2 below the best
        # node's: a machine class) — so it commits whole or is ranked
        # again, whole, against the state that refused it
        if self.engine.coupled:
            self.plan.all_at_once = True

        # submit the plan
        result = self.planner.submit_plan(self.plan)
        self.plan_result = result
        adjust_queued_allocations(result, self.queued_allocs)

        if result is None:
            return True
        full, expected, actual = result.full_commit(self.plan)
        if not full:
            # partial commit: refresh state and retry
            if result.refresh_index:
                self.state = self.planner.refreshed_state(
                    result.refresh_index) if hasattr(
                        self.planner, "refreshed_state") else self.state
            progress[0] = actual > 0
            progress[1] = result.refresh_index > ranked_index
            return False
        return True

    # -- reconcile + place --------------------------------------------
    def _compute_job_allocs(self) -> None:
        with stages.span("reconcile") as sp:
            results = self._reconcile()
            # the attr rides onto the flight recorder's reconcile span
            # (a slow reconcile means something different on the
            # columnar engine vs the reference fallback)
            sp.note(columnar=self._columnar_active)
        # Compute placements (destructive first to discount resources)
        self._compute_placements(results.destructive_update, results.place)

    def _reconcile(self):
        """The alloc-diff host phase (the `reconcile` stage): alloc
        fetch + tainted split + reconciler + result staging into the
        plan. Returns the reconciler's results."""
        ev = self.eval

        # columnar reconcile engine: the state store's per-job alloc
        # index turns the O(allocs) host phase into mask ops
        # (reconcile_columnar.py); NOMAD_TPU_COLUMNAR_RECONCILE=0 or a
        # detached snapshot falls back to the reference reconciler
        cols = None
        if columnar_enabled():
            getter = getattr(self.state, "job_alloc_columns", None)
            if getter is not None:
                cols = getter(ev.namespace, ev.job_id)
        self._columnar_active = cols is not None

        if cols is not None:
            tainted = tainted_nodes_columnar(self.state, cols)
            update_non_terminal_allocs_to_lost_columnar(
                self.plan, tainted, cols)
        else:
            allocs = self.state.allocs_by_job(ev.namespace, ev.job_id)
            tainted = tainted_nodes(self.state, allocs)
            update_non_terminal_allocs_to_lost(self.plan, tainted,
                                               allocs)

        job = self.job
        if job is None or job.stopped():
            job = job if job is not None else Job(
                id=ev.job_id, namespace=ev.namespace, stop=True,
                task_groups=[])
        if cols is not None:
            reconciler = ColumnarAllocReconciler(
                self._alloc_update_fn, self.batch, ev.job_id, job,
                self.deployment, cols, tainted, ev.id,
                spec_change_fn=self._spec_change_fn)
        else:
            reconciler = AllocReconciler(
                self._alloc_update_fn, self.batch, ev.job_id, job,
                self.deployment, allocs, tainted, ev.id)
        results = reconciler.compute()

        if self.eval.annotate_plan:
            from ..models.plan import PlanAnnotations
            self.plan.annotations = PlanAnnotations(
                desired_tg_updates=results.desired_tg_updates)

        # Add the deployment changes to the plan
        self.plan.deployment = results.deployment
        self.plan.deployment_updates = results.deployment_updates

        # Followup evals (delayed reschedules)
        for evals in results.desired_followup_evals.values():
            self.followup_evals.extend(evals)

        # Update the stored deployment
        if results.deployment is not None:
            self.deployment = results.deployment

        # Handle stops
        for stop in results.stop:
            self.plan.append_stopped_alloc(
                stop.alloc, stop.status_description, stop.client_status,
                stop.followup_eval_id)

        # Handle attribute updates (followup eval ids on allocs)
        for alloc in results.attribute_updates.values():
            self.plan.append_alloc(alloc)

        # Handle in-place updates
        for alloc in results.inplace_update:
            self.plan.append_alloc(alloc)

        # Queued allocations = requested placements per tg, derived
        # from the reconciler's per-tg counts in ONE pass: fresh places
        # + canaries + migrations land in results.place, destructive
        # updates in results.destructive_update, and the old code
        # re-walked both 10k-entry lists after the reconciler had
        # already bucketed them
        for tg_name, du in results.desired_tg_updates.items():
            n = du.place + du.canary + du.migrate + du.destructive_update
            if n:
                self.queued_allocs[tg_name] = \
                    self.queued_allocs.get(tg_name, 0) + n

        return results

    def _spec_change_fn(self, old_job: Job, tg_name: str) -> bool:
        """Destructive-update verdict for the columnar reconciler: one
        memoized deep diff per (old version, new version, tg)."""
        return tasks_updated_cached(self.job, old_job, tg_name)

    # genericAllocUpdateFn (util.go:926)
    def _alloc_update_fn(self, existing: Allocation, new_job: Job, new_tg):
        if existing.job is not None and \
                existing.job.job_modify_index == new_job.job_modify_index:
            return True, False, None
        if existing.job is None:
            return False, True, None
        # memoized with the engine on (one diff per version pair);
        # engine-off — env hatch or reconcile_columnar=False — keeps
        # the raw diff so comparisons measure the true reference cost
        updated = (tasks_updated_cached(new_job, existing.job,
                                        new_tg.name)
                   if self._columnar_active
                   else tasks_updated(new_job, existing.job,
                                      new_tg.name))
        if updated:
            return False, True, None
        if existing.terminal_status():
            return True, False, None
        node = self.state.node_by_id(existing.node_id)
        if node is None:
            return False, True, None

        # Host-side single-node feasibility + fit check: the in-place path
        # touches exactly one node, so a device dispatch per candidate
        # alloc would be pure overhead (genericAllocUpdateFn util.go:926
        # runs the stack on a one-node set for the same reason).
        if not self._node_feasible_for(node, new_tg):
            return False, True, None
        ask = PlacementEngine.group_ask(new_tg)
        cap = node.comparable_resources()
        cap.subtract(node.comparable_reserved_resources())
        used = [0.0, 0.0, 0.0]
        stopped = {a.id for allocs in self.plan.node_update.values()
                   for a in allocs} | {existing.id}
        for a in self.state.allocs_by_node(node.id):
            if a.terminal_status() or a.id in stopped:
                continue
            c = a.comparable_resources()
            if c is not None:
                used[0] += c.cpu_shares
                used[1] += c.memory_mb
                used[2] += c.disk_mb
        for a in self.plan.node_allocation.get(node.id, []):
            c = a.comparable_resources()
            if c is not None:
                used[0] += c.cpu_shares
                used[1] += c.memory_mb
                used[2] += c.disk_mb
        if (used[0] + ask[0] > cap.cpu_shares
                or used[1] + ask[1] > cap.memory_mb
                or used[2] + ask[2] > cap.disk_mb):
            return False, True, None

        # build task resources, restoring network/device offers from the
        # existing allocation (in-place updates keep their ports)
        from ..models.resources import (AllocatedCpuResources,
                                        AllocatedMemoryResources,
                                        AllocatedTaskResources)
        task_resources = {}
        for task in new_tg.tasks:
            tr = AllocatedTaskResources(
                cpu=AllocatedCpuResources(task.resources.cpu),
                memory=AllocatedMemoryResources(task.resources.memory_mb))
            if existing.allocated_resources is not None:
                old = existing.allocated_resources.tasks.get(task.name)
                if old is not None:
                    tr.networks = old.networks
                    tr.devices = old.devices
            task_resources[task.name] = tr
        option = type("_Opt", (), {})()
        option.task_resources = task_resources

        new_alloc = existing.copy_skip_job()
        new_alloc.eval_id = self.eval.id
        new_alloc.job = None
        new_alloc.allocated_resources = AllocatedResources(
            tasks=option.task_resources,
            shared=AllocatedSharedResources(
                disk_mb=new_tg.ephemeral_disk.size_mb
                if new_tg.ephemeral_disk else 0,
                networks=(existing.allocated_resources.shared.networks
                          if existing.allocated_resources else []),
            ))
        new_alloc.metrics = existing.metrics.copy() if existing.metrics \
            else AllocMetric()
        return False, False, new_alloc

    def _node_feasible_for(self, node, tg) -> bool:
        """Static feasibility of one node for a task group (host-side,
        no device dispatch)."""
        from ..ops.tables import NodeTable
        t = NodeTable([node])
        engine = PlacementEngine.__new__(PlacementEngine)
        engine.snapshot = self.state
        engine.config = self.state.scheduler_config()
        engine.job = self.job
        engine.table = t
        engine.by_dc = {node.datacenter: 1}
        engine._base_mask = t.ready.copy()
        engine._mask_cache = {}
        engine._dc_key = None       # private table: no cross-eval cache
        engine._net_cache = {}
        engine._dev_cache = {}
        engine._feas_tokens = {}
        engine._feas_push_s = 0.0
        mask, _counts = engine.feasibility(tg)
        return bool(mask[0])

    # computePlacements (generic_sched.go:468), batched per task group
    def _compute_placements(self, destructive: List, place: List) -> None:
        if self.job is None:
            return
        n = self.engine.set_nodes(self.job.datacenters)
        self._preemption_rounds = {}   # tg name -> PreemptionRound

        deployment_id = ""
        if self.deployment is not None and self.deployment.active():
            deployment_id = self.deployment.id

        now = time.time()

        empty_options = SelectOptions()
        for results in (destructive, place):
            # group placements by (tg, penalty/preferred signature)
            groups: Dict[Tuple, List] = {}
            order: List[Tuple] = []
            for missing in results:
                tg = missing.task_group if not hasattr(missing, "place_task_group") \
                    else missing.place_task_group
                if tg is None:
                    continue
                if missing.previous_alloc is None:
                    # fresh placement: no penalty/preferred signature —
                    # skip per-instance option construction (a 10k-count
                    # job walks this loop 10k times)
                    options = empty_options
                    sig = (tg.name, None, None)
                else:
                    options = self._get_select_options(missing)
                    sig = (tg.name, options.penalty_node_ids,
                           tuple(nd.id for nd in options.preferred_nodes))
                if sig not in groups:
                    groups[sig] = []
                    order.append(sig)
                groups[sig].append((missing, options))

            for sig in order:
                batch = groups[sig]
                tg_name = sig[0]
                tg = self.job.lookup_task_group(tg_name)
                if tg is None:
                    continue
                if tg.name in self.failed_tg_allocs:
                    self.failed_tg_allocs[tg.name].coalesced_failures += len(batch)
                    continue

                # fresh batches (sig carries no penalty/preferred data ⟺
                # every item has previous_alloc None) have no stops to
                # stage and take the bulk append below
                fresh = sig[1] is None and sig[2] is None

                # stage stops for destructive updates first (frees resources)
                if not fresh:
                    for missing, _opts in batch:
                        stop_prev, stop_desc = missing.stop_previous()
                        if stop_prev and missing.previous_alloc is not None:
                            self.plan.append_stopped_alloc(
                                missing.previous_alloc, stop_desc, "", "")

                proposed = self._proposed()
                # room first (upstream selectNextOption): this select
                # carries no preemption; only what it finds no node
                # for goes to a second one that does (_append_general)
                options_list = self.engine.select_batch(
                    tg, len(batch), proposed, batch[0][1])

                if fresh and not batch[0][1].preferred_nodes:
                    # bulk-append the successful fresh placements in one
                    # tight loop (a 10k-count batch spent ~0.3 s in the
                    # general per-item body below — round-5 profile);
                    # leftovers (no fit, preemption winners, canaries)
                    # fall through to the general loop
                    with stages.span("plan_build",
                                     placements=len(batch)):
                        leftover = self._append_fresh_bulk(
                            batch, options_list, tg, deployment_id)
                    if not leftover:
                        continue
                    pairs = leftover
                else:
                    pairs = list(zip(batch, options_list))

                # the general per-item loop is plan_build too: one
                # report a batch. The fallback select and the
                # preemption search of an item that found no node
                # (stages of their own) run inside its interval
                with stages.span("plan_build", placements=len(pairs)):
                    self._append_general(pairs, batch, tg, deployment_id,
                                         now)

        # record class eligibility for the blocked eval — only over nodes
        # in the iteration set (ready & in-DC): a down node's class must
        # stay UNKNOWN so BlockedEvals wakes the eval when it recovers
        # (the resident table holds all nodes; feasible.go's iterator
        # never saw non-ready ones)
        if self.failed_tg_allocs and self.engine.table is not None:
            base = self.engine._base_mask
            for tg_name in self.failed_tg_allocs:
                tg = self.job.lookup_task_group(tg_name)
                if tg is None:
                    continue
                mask, _counts = self.engine.feasibility(tg)
                for i, node in enumerate(self.engine.table.nodes):
                    if node.computed_class and bool(base[i]):
                        prev = self.ctx.eligibility.class_eligibility.get(
                            node.computed_class, False)
                        self.ctx.eligibility.set_class_eligibility(
                            node.computed_class, prev or bool(mask[i]))

    def _append_general(self, pairs, batch, tg, deployment_id: str,
                        now) -> None:
        """Append (item, option) pairs to the plan one by one: what
        _append_fresh_bulk left over, and every batch it cannot take.
        The items no select found a node for are then ranked ONCE more,
        together, with preemption switched on (_select_evicting)."""
        unplaced = []
        for (missing, _opts), (option, metrics) in pairs:
            # preferred-node miss falls back to the full node set
            if option is None and batch[0][1].preferred_nodes:
                fallback = self.engine.select_batch(
                    tg, 1, self._proposed(),
                    SelectOptions(
                        penalty_node_ids=batch[0][1].penalty_node_ids))
                option, metrics = fallback[0] if fallback else (None, metrics)
            if option is not None:
                self._append_placement(missing, tg, option,
                                       deployment_id, now)
            else:
                unplaced.append((missing, metrics))
        if not unplaced:
            return
        # no fit anywhere: try preemption before failing
        # (BinPackIterator evict path, rank.go:415-448)
        evicting = self._select_evicting(
            tg, len(unplaced), batch[0][1].penalty_node_ids)
        for (missing, metrics), (option, _m) in zip(unplaced, evicting):
            if option is not None:
                self._append_placement(missing, tg, option,
                                       deployment_id, now)
                continue
            if tg.name in self.failed_tg_allocs:
                # coalesce later failures of the same group
                self.failed_tg_allocs[tg.name].coalesced_failures += 1
            else:
                # private copy: `metrics` may be the batch's
                # shared flyweight, and coalesced_failures
                # mutates on later failures
                self.failed_tg_allocs[tg.name] = metrics.copy()
            # back out the staged stop: a failed placement must not
            # leave its previous alloc stopping with no replacement
            stop_prev, _ = missing.stop_previous()
            if stop_prev and missing.previous_alloc is not None:
                self.plan.remove_update(missing.previous_alloc)

    def _proposed(self) -> ProposedIndex:
        """The job's proposed allocations over the plan as it stands.
        The job's own allocations are listed once a snapshot: a second
        select, or a batch job of 20,000 allocations, asks again."""
        hit = self._job_allocs
        if hit is None or hit[0] is not self.state:
            hit = self._job_allocs = (self.state, self.state.allocs_by_job(
                self.job.namespace, self.job.id))
        return ProposedIndex(self.engine.table, self.job, hit[1],
                             self.plan)

    def _select_evicting(self, tg, count: int, penalty_node_ids):
        """The second select, for `count` instances that found no node
        as the fleet stands: the same ranking over the plan as it is
        now, with the nodes that would fit after evicting lower-priority
        allocations (priority delta >= 10) competing. Only here is a
        PreemptionRound built, one per (eval, task group): an eval that
        found room pays none. Returns select_batch's pairs, or `count`
        misses when preemption is off for this scheduler type."""
        from .preemption import PreemptionRound, preemption_enabled
        if not preemption_enabled(self.state.scheduler_config(),
                                  "batch" if self.batch else "service"):
            return [(None, None)] * count
        round_ = self._preemption_rounds.get(tg.name)
        if round_ is None or round_.plan is not self.plan:
            mask, _counts = self.engine.feasibility(tg)
            round_ = PreemptionRound(
                self.state, self.engine.table, mask,
                self.engine.group_ask(tg), self.job, self.plan, tg=tg)
            self._preemption_rounds[tg.name] = round_
        return self.engine.select_batch(
            tg, count, self._proposed(),
            SelectOptions(penalty_node_ids=penalty_node_ids),
            preemption_round=round_)

    def _append_fresh_bulk(self, batch, options_list, tg,
                           deployment_id: str):
        """Append fresh placements (no previous alloc) to the plan via a
        prototype-copy loop: one Allocation template per batch, per-item
        work limited to id/name/node/resources. Safe because the shared
        default fields (desired_transition, task_states,
        preempted_allocations) are replaced, never mutated, downstream.
        Returns the (item, option) pairs needing the general path:
        failures, preemption winners, canaries."""
        from os import urandom

        proto = Allocation(
            namespace=self.job.namespace, eval_id=self.eval.id,
            job_id=self.job.id, task_group=tg.name,
            deployment_id=deployment_id,
            desired_status=ALLOC_DESIRED_RUN,
            client_status=ALLOC_CLIENT_PENDING)
        base = proto.__dict__
        disk_mb = tg.ephemeral_disk.size_mb if tg.ephemeral_disk else 0
        res_fly: Dict[Tuple[int, int], AllocatedResources] = {}
        node_alloc = self.plan.node_allocation
        deployment_active = (self.deployment is not None
                             and self.deployment.active())
        leftover = []
        for item, (option, metrics) in zip(batch, options_list):
            missing = item[0]
            if option is None or option.preempted_allocs or \
                    (missing.canary and deployment_active):
                leftover.append((item, (option, metrics)))
                continue
            tr = option.task_resources
            ar = option.alloc_resources
            key = (id(tr), id(ar))
            resources = res_fly.get(key)
            if resources is None:
                resources = AllocatedResources(
                    tasks=tr, shared=ar or AllocatedSharedResources(
                        disk_mb=disk_mb))
                res_fly[key] = resources
            a = object.__new__(Allocation)
            d = a.__dict__
            d.update(base)
            h = urandom(16).hex()
            d["id"] = f"{h[:8]}-{h[8:12]}-4{h[13:16]}-{h[16:20]}-{h[20:]}"
            node = option.node
            d["name"] = missing.name
            d["node_id"] = node.id
            d["node_name"] = node.name
            d["allocated_resources"] = resources
            d["metrics"] = option.metrics
            lst = node_alloc.get(node.id)
            if lst is None:
                node_alloc[node.id] = [a]
            else:
                lst.append(a)
        return leftover

    @staticmethod
    def _get_select_options(missing) -> SelectOptions:
        prev = missing.previous_alloc
        penalty = set()
        if prev is not None:
            if prev.client_status == ALLOC_CLIENT_FAILED:
                penalty.add(prev.node_id)
            if prev.reschedule_tracker is not None:
                for ev in prev.reschedule_tracker.events:
                    if ev.prev_node_id:
                        penalty.add(ev.prev_node_id)
        return SelectOptions(penalty_node_ids=frozenset(penalty))

    def _append_placement(self, missing, tg, option, deployment_id: str,
                          now: float) -> None:
        # flyweight-aware: winners of one batch share task_resources
        # when no ports/devices are at stake (stack.py select_batch), so
        # the wrapping AllocatedResources can be shared too — these are
        # read-only downstream (in-place updates build fresh objects)
        cached = getattr(self, "_res_fly", None)
        if cached is not None and cached[0] is option.task_resources \
                and cached[1] is option.alloc_resources:
            resources = cached[2]
        else:
            resources = AllocatedResources(
                tasks=option.task_resources,
                shared=option.alloc_resources or AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb
                    if tg.ephemeral_disk else 0))
            self._res_fly = (option.task_resources,
                             option.alloc_resources, resources)
        alloc = Allocation(
            id=generate_uuid(),
            namespace=self.job.namespace,
            eval_id=self.eval.id,
            name=missing.name,
            job_id=self.job.id,
            task_group=tg.name,
            metrics=option.metrics,
            node_id=option.node.id,
            node_name=option.node.name,
            deployment_id=deployment_id,
            allocated_resources=resources,
            desired_status=ALLOC_DESIRED_RUN,
            client_status=ALLOC_CLIENT_PENDING,
        )
        prev = missing.previous_alloc
        if prev is not None:
            alloc.previous_allocation = prev.id
            if missing.reschedule:
                self._update_reschedule_tracker(alloc, prev, now)
        if missing.canary and self.deployment is not None:
            alloc.deployment_status = AllocDeploymentStatus(canary=True)
        if option.preempted_allocs:
            from .preemption import link_preemptions
            link_preemptions(self.plan, alloc, option.preempted_allocs)
        self.plan.append_alloc(alloc)

    @staticmethod
    def _update_reschedule_tracker(alloc: Allocation, prev: Allocation,
                                   now: float) -> None:
        events: List[RescheduleEvent] = []
        if prev.reschedule_tracker is not None:
            events.extend(prev.reschedule_tracker.events)
        events.append(RescheduleEvent(
            reschedule_time=now, prev_alloc_id=prev.id,
            prev_node_id=prev.node_id,
            delay_s=prev._next_delay(prev.reschedule_policy())
            if prev.reschedule_policy() else 0.0))
        alloc.reschedule_tracker = RescheduleTracker(events=events)

    # -- status --------------------------------------------------------
    def _set_status(self, status: str, desc: str) -> None:
        new_eval = self.eval.copy()
        new_eval.status = status
        new_eval.status_description = desc
        if self.blocked is not None:
            new_eval.blocked_eval = self.blocked.id
        if self.failed_tg_allocs:
            new_eval.failed_tg_allocs = dict(self.failed_tg_allocs)
        if self.queued_allocs is not None:
            new_eval.queued_allocations = dict(self.queued_allocs)
        if self.deployment is not None and self.deployment.active():
            new_eval.deployment_id = self.deployment.id
        self.planner.update_eval(new_eval)
