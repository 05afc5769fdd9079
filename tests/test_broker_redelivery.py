"""Fault 3's readings (PR 34): the broker counts the deliveries that RAN
OUT — the nack timer fired while a worker still held the eval — apart
from the nacks a worker sends itself, and the `sched_host` span says how
long an eval's delivery had to last: dequeue to its last plan's
answer."""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.models import (EVAL_STATUS_PENDING, TRIGGER_JOB_REGISTER,
                              Evaluation)
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.utils.ids import generate_uuid


def _eval(job_id="j1"):
    return Evaluation(id=generate_uuid(), namespace="default", priority=50,
                      triggered_by=TRIGGER_JOB_REGISTER, job_id=job_id,
                      status=EVAL_STATUS_PENDING, type="service")


def _wait(cond, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


@pytest.fixture
def broker():
    b = EvalBroker(nack_timeout_s=0.05, initial_nack_delay_s=0.0,
                   subsequent_nack_delay_s=0.0)
    b.set_enabled(True)
    yield b
    b.set_enabled(False)


def test_an_eval_that_outlives_its_nack_timer_is_counted_and_redelivered(
        broker):
    ev = _eval()
    broker.enqueue(ev)
    got, token = broker.dequeue(["service"], timeout_s=1.0)
    assert got.id == ev.id and broker.stats.total_redelivered == 0
    assert got._dequeued_t <= time.monotonic()
    # the holder sits on it past the (shortened) timer
    assert _wait(lambda: broker.stats.total_redelivered == 1)
    assert broker.stats.as_dict()["redelivered"] == 1
    # the delivery is gone: the holder's token no longer stands ...
    assert broker.outstanding(ev.id) != token
    with pytest.raises((KeyError, ValueError)):
        broker.ack(ev.id, token)
    # ... and the eval comes to the next dequeue under a new one
    again, token2 = broker.dequeue(["service"], timeout_s=1.0)
    assert again.id == ev.id and token2 != token
    broker.ack(ev.id, token2)
    time.sleep(0.12)
    assert broker.stats.total_redelivered == 1      # acked in time


def test_a_nack_the_worker_sends_itself_is_no_redelivery(broker):
    ev = _eval("j2")
    broker.enqueue(ev)
    _got, token = broker.dequeue(["service"], timeout_s=1.0)
    broker.nack(ev.id, token)
    again, token2 = broker.dequeue(["service"], timeout_s=1.0)
    broker.ack(again.id, token2)
    time.sleep(0.12)
    assert broker.stats.total_redelivered == 0


def test_sched_host_says_how_long_the_delivery_had_to_last():
    """One served eval: the span's `delivery_s` runs from the broker's
    dequeue to the last plan's answer, inside the eval's whole time and
    far inside the 60 s timer; the governor lists the counter."""
    from nomad_tpu.server.core import Server, ServerConfig
    from nomad_tpu.utils import stages

    srv = Server(ServerConfig(num_schedulers=1))
    seen = []
    prev, prev_on = stages._trace_hook, stages._trace_on
    try:
        srv.start()
        stages.set_trace_hook(
            lambda stage, seconds, attrs=None:
            stage == "sched_host" and seen.append((seconds, attrs)))
        srv.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        t0 = time.monotonic()
        srv.register_job(job)
        assert _wait(lambda: len(srv.store.snapshot().allocs_by_job(
            job.namespace, job.id)) == 2 and seen, 60.0)
        took = time.monotonic() - t0
        seconds, attrs = seen[0]
        assert 0.0 < attrs["delivery_s"] <= took
        assert attrs["delivery_s"] < srv.eval_broker.nack_timeout_s
        srv.governor.sample_once()
        names = {g["name"] for g in srv.governor.status()["gauges"]}
        assert "broker.redelivered" in names
        assert srv.eval_broker.stats.total_redelivered == 0
    finally:
        stages.set_trace_hook(prev, prev_on)
        srv.shutdown()


# -- follow-up evals of one job: only the latest waiting one runs ------

def _followup(job_id, trigger="preemption", index=0):
    ev = _eval(job_id)
    ev.type = "batch"
    ev.triggered_by = trigger
    ev.create_index = index
    return ev


def test_an_ack_sheds_all_but_the_latest_waiting_preemption_eval():
    """While one eval of a job is out, the follow-ups a run of
    evictions makes for it wait behind it; as it is acked only the
    latest of them goes on, the others leave the broker to be marked
    canceled — what the latest finds when a worker takes it includes
    everything the earlier ones were made for."""
    b = EvalBroker(nack_timeout_s=30.0)
    b.set_enabled(True)
    try:
        first = _followup("backfill", index=10)
        later = [_followup("backfill", index=11 + k) for k in range(5)]
        other = _followup("another-job", index=12)
        manual = _followup("backfill", trigger="job-register", index=13)
        for ev in [first, *later, other, manual]:
            b.enqueue(ev)
        assert b.stats.total_blocked == 6 and b.stats.total_ready == 2
        got, token = b.dequeue(["batch"], timeout_s=1.0)
        assert got.id == first.id and b.take_cancelable() == []
        handed = []
        b.on_superseded = handed.extend             # the server's hook
        b.ack(got.id, token)
        shed = list(handed)
        assert {e.id for e in shed} == {e.id for e in later[:-1]}
        assert b.take_cancelable() == []            # handed out once
        # what is left of the job: the register eval and the LATEST
        # follow-up, one ready and one waiting; the other job untouched
        assert b.stats.total_blocked == 1
        seen = []
        for _ in range(3):
            got, token = b.dequeue(["batch"], timeout_s=1.0)
            seen.append(got.id)
            b.ack(got.id, token)
        assert sorted(seen) == sorted([other.id, manual.id, later[-1].id])
        assert len(handed) == 4 and b.take_cancelable() == []
        assert b.stats.as_dict()["blocked"] == 0
        # a shed eval is gone from the broker: it can be enqueued anew
        assert all(e.id not in b._evals for e in shed)
    finally:
        b.set_enabled(False)


def test_the_server_writes_the_shed_evals_back_canceled():
    from nomad_tpu.models import EVAL_STATUS_CANCELED
    from nomad_tpu.server.core import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=0))
    try:
        job = mock.job()
        srv.store.upsert_job(srv._raft_index + 1, job)
        evs = [_followup(job.id, index=k) for k in range(3)]
        for ev in evs:
            ev.namespace = job.namespace
        srv.store.upsert_evals(srv._raft_index + 2, evs)
        srv.cancel_evals(evs[:2])
        snap = srv.store.snapshot()
        assert [snap.eval_by_id(e.id).status for e in evs] == \
            [EVAL_STATUS_CANCELED, EVAL_STATUS_CANCELED, "pending"]
        assert "later preemption eval" in \
            snap.eval_by_id(evs[0].id).status_description
        assert srv.eval_broker.stats.total_ready == 0   # not re-queued
    finally:
        srv.shutdown()
