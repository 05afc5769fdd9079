"""In-process scheduler test harness.

Reference semantics: scheduler/testing.go — Harness:43 wraps a real
state store, implements Planner by applying plans directly, and records
Plans/Evals for assertions.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from ..models import Evaluation, Plan, PlanResult
from ..state import StateStore
from .scheduler import new_scheduler
from ..utils.locks import make_lock


class RejectPlan:
    """Planner that rejects everything (testing.go:18) — exercises the
    scheduler's retry path."""

    def __init__(self, harness: "Harness"):
        self.h = harness

    def submit_plan(self, plan: Plan) -> PlanResult:
        result = PlanResult(refresh_index=self.h.store.latest_index())
        return result

    def update_eval(self, evaluation: Evaluation) -> None:
        pass

    def create_eval(self, evaluation: Evaluation) -> None:
        pass

    def reblock_eval(self, evaluation: Evaluation) -> None:
        pass


class Harness:
    # recorded plans/evals are assertion material for tests, but a
    # long soak loop drives hundreds of thousands of
    # evals through one harness — unbounded recording was one of the
    # round-5 soak's RSS leaks (each plan pins its placed allocs and
    # job). Tests never come close to this bound.
    MAX_HISTORY = 4096

    def __init__(self, store: Optional[StateStore] = None):
        self.store = store or StateStore()
        self.planner = None
        self.plans: List[Plan] = []
        self.evals: List[Evaluation] = []
        self.create_evals: List[Evaluation] = []
        self.reblock_evals: List[Evaluation] = []
        self._lock = make_lock()
        self._next_index = 1000

    def _trim(self, lst: List) -> None:
        if len(lst) > self.MAX_HISTORY:
            del lst[:len(lst) - self.MAX_HISTORY]

    def next_index(self) -> int:
        with self._lock:
            self._next_index += 1
            return self._next_index

    # -- Planner interface --------------------------------------------
    def submit_plan(self, plan: Plan) -> PlanResult:
        with self._lock:
            self.plans.append(plan)
            self._trim(self.plans)
        if self.planner is not None:
            return self.planner.submit_plan(plan)

        # apply the plan directly to the state store (testing.go:83)
        index = self.next_index()
        stopped = [a for allocs in plan.node_update.values() for a in allocs]
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        preempted = [a for allocs in plan.node_preemptions.values()
                     for a in allocs]
        for a in placed:
            if a.job is None:
                a.job = plan.job
        self.store.upsert_plan_results(
            index,
            allocs_stopped=stopped,
            allocs_placed=placed,
            allocs_preempted=preempted,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
        )
        return PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
            node_preemptions=plan.node_preemptions,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
            alloc_index=index,
        )

    def update_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.evals.append(evaluation)
            self._trim(self.evals)

    def create_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.create_evals.append(evaluation)
            self._trim(self.create_evals)

    def reblock_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.reblock_evals.append(evaluation)
            self._trim(self.reblock_evals)

    # -- driving -------------------------------------------------------
    def process(self, scheduler_name: str, evaluation: Evaluation) -> None:
        snapshot = self.store.snapshot()
        sched = new_scheduler(scheduler_name, snapshot, self)
        sched.process(evaluation)

    def assert_eval_status(self, testcase, status: str) -> None:
        assert len(self.evals) > 0
        assert self.evals[-1].status == status
