"""Optimistic concurrency on small node-coupling evals (ISSUE 27): two
workers that rank the whole fleet choose the same fullest node, and the
applier refuses the second plan. What holds the answer exact there: a
plan of a node-coupling ask commits whole or not at all (its refused
node invalidates the greedy sequence after it) and is then ranked again
against the state that refused it; and a race lost is not an attempt
spent, so no eval the fleet has room for fails on the fifth."""
import pytest

from nomad_tpu import mock
from nomad_tpu.models import (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED, Plan,
                              PlanResult, Spread)
from nomad_tpu.models.evaluation import TRIGGER_MAX_PLANS, Evaluation
from nomad_tpu.scheduler import Harness, generic
from nomad_tpu.server.core import Server, ServerConfig
from nomad_tpu.utils.ids import generate_uuid


def ev_for(job):
    return Evaluation(
        id=generate_uuid(), namespace=job.namespace, priority=job.priority,
        type=job.type, triggered_by="job-register", job_id=job.id,
        status="pending")


def _fleet(h, n=10):
    for _ in range(n):
        h.store.upsert_node(h.next_index(), mock.node())


class _LosesRaces:
    """A planner that refuses the first `lost` plans whole, each against
    a state newer than the one the plan ranked (another plan got there
    first), then applies what it is given."""

    def __init__(self, h, lost):
        self.h, self.lost, self.seen = h, lost, 0

    def submit_plan(self, plan):
        self.seen += 1
        if self.seen <= self.lost:
            self.h.store.upsert_node(self.h.next_index(), mock.node())
            return PlanResult(refresh_index=self.h.store.latest_index())
        self.h.planner = None
        try:
            return self.h.submit_plan(plan)
        finally:
            self.h.planner = self


def test_a_race_lost_is_not_an_attempt_spent():
    h = Harness()
    _fleet(h)
    job = mock.job()
    job.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), job)
    h.planner = _LosesRaces(h, lost=generic.MAX_SERVICE_ATTEMPTS + 2)
    h.process("service", ev_for(job))
    assert h.planner.seen == generic.MAX_SERVICE_ATTEMPTS + 3
    assert h.evals[-1].status == EVAL_STATUS_COMPLETE
    assert len(h.store.allocs_by_job(job.namespace, job.id)) == 1
    assert not h.create_evals


def test_the_races_an_eval_may_lose_are_bounded():
    h = Harness()
    _fleet(h)
    job = mock.job()
    job.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), job)
    h.planner = _LosesRaces(h, lost=10 ** 6)
    h.process("service", ev_for(job))
    assert h.planner.seen == \
        generic.MAX_RACES_LOST + generic.MAX_SERVICE_ATTEMPTS
    assert h.evals[-1].status == EVAL_STATUS_FAILED
    assert [e.triggered_by for e in h.create_evals] == [TRIGGER_MAX_PLANS]


def test_a_refusal_against_the_state_it_ranked_still_counts():
    h = Harness()
    _fleet(h)
    job = mock.job()
    h.store.upsert_job(h.next_index(), job)

    class Refuses:
        seen = 0

        def submit_plan(self, plan):
            self.seen += 1
            return PlanResult(refresh_index=h.store.latest_index())

    h.planner = Refuses()
    h.process("service", ev_for(job))
    assert h.planner.seen == generic.MAX_SERVICE_ATTEMPTS
    assert h.evals[-1].status == EVAL_STATUS_FAILED


@pytest.mark.parametrize("coupled", [True, False])
def test_a_node_coupling_ask_plans_all_at_once(coupled):
    h = Harness()
    _fleet(h)
    job = mock.job()
    job.task_groups[0].count = 4
    if coupled:
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    h.store.upsert_job(h.next_index(), job)
    h.process("service", ev_for(job))
    plan, = h.plans
    assert plan.all_at_once is coupled
    assert h.evals[-1].status == EVAL_STATUS_COMPLETE


@pytest.mark.parametrize("all_at_once", [True, False])
def test_one_refused_node_refuses_an_all_at_once_plan(all_at_once):
    """plan_apply.go evaluatePlan: AllAtOnce and a node that does not
    fit leave nothing of the plan; without it the rest commits."""
    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=60.0))
    roomy, full = mock.node(), mock.node()
    srv.store.upsert_node(100, roomy)
    srv.store.upsert_node(101, full)
    holder = mock.alloc()               # holds reserved port 5000 there
    holder.node_id = full.id
    holder.client_status = "running"
    srv.store.upsert_allocs(102, [holder])

    fits, clash = mock.alloc(), mock.alloc()
    fits.node_id = roomy.id
    clash.node_id = full.id             # the same reserved port: refused
    plan = Plan(priority=50, all_at_once=all_at_once)
    plan.job = fits.job
    plan.node_allocation = {roomy.id: [fits], full.id: [clash]}
    plan.snapshot_index = srv.store.latest_index()

    result = srv.plan_applier.apply_sync(plan)
    full_commit, expected, actual = result.full_commit(plan)
    assert not full_commit and expected == 2
    assert result.refresh_index > 0
    assert actual == (0 if all_at_once else 1)
    assert (srv.store.alloc_by_id(fits.id) is None) is all_at_once
    assert srv.store.alloc_by_id(clash.id) is None
