"""Tier-1 mini-soak: a few thousand evals through a dev agent with the
governor sampling on a tight cadence; asserts the registered gauges
hold inside their watermarks and the process RSS delta stays bounded —
the fast regression guard for the steady-state properties the full
soak (bench/soak.py, SOAK_r06.json) certifies at C2M scale."""

import gc
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client import Client, ClientConfig
from nomad_tpu.server import Server, ServerConfig

# each job wave generates ~4-5 evals (register, deregister, client
# alloc updates, job-status reconciles) — ~1.2k evals through the
# real worker/broker path in well under a minute
N_JOBS = 250


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def _wait_for(pred, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


@pytest.fixture(scope="module")
def cluster():
    server = Server(ServerConfig(num_schedulers=2,
                                 heartbeat_ttl_s=60.0,
                                 governor_interval_s=0.1))
    server.start()
    client = Client(server, ClientConfig(node_name="gov-soak"))
    client.start()
    yield server, client
    client.shutdown()
    server.shutdown()


def test_mini_soak_gauges_hold_and_rss_bounded(cluster):
    server, _client = cluster
    gov = server.governor
    assert gov is not None

    gc.collect()
    rss_before = _rss_mb()
    processed_before = sum(w.stats["processed"]
                           for w in server.workers)

    # churn: waves of short service jobs register, place, and stop —
    # the substrate must hold steady state, not accrete
    wave = 40
    for i in range(N_JOBS):
        job = mock.job()
        job.id = f"gov-soak-{i}"
        job.task_groups[0].count = 1
        job.task_groups[0].tasks[0].config = {"run_for": "0s"}
        for t in job.task_groups[0].tasks:
            t.resources.networks = []
        job.task_groups[0].networks = []
        server.register_job(job)
        if i >= wave:
            server.deregister_job("default", f"gov-soak-{i - wave}",
                                  purge=True)

    # drain: every register/deregister eval processed
    want = processed_before + N_JOBS
    assert _wait_for(lambda: sum(w.stats["processed"]
                                 for w in server.workers) >= want,
                     timeout=120.0), "broker failed to drain"
    assert _wait_for(
        lambda: server.eval_broker.stats.total_ready == 0
        and server.eval_broker.stats.total_unacked == 0,
        90.0), "ready queue failed to drain"

    # the governor samples throughout (0.1 s cadence): how many samples
    # fit in the churn depends on how fast the churn ran (read 10 once),
    # so wait for the count instead of racing it
    assert _wait_for(lambda: gov._samples > 10, timeout=30.0)
    assert gov.latency_samples() > 0

    # every watermarked gauge is back inside its bound at steady state
    gov.sample_once()
    for row in gov.registry.rows():
        if "high" not in row:
            continue
        assert row["value"] <= row["high"], \
            f"{row['name']} over watermark after drain: {row}"
        assert row["status"] == "ok", row
    assert not gov.backpressure()

    # bounded structures actually bounded
    assert server.events.buffered_events() <= 4096
    assert server.store.version_debt() <= 100_000

    # RSS delta over ~800 evals of churn stays small; a leak on the
    # eval path shows up here as tens of MB
    gc.collect()
    rss_delta = _rss_mb() - rss_before
    assert rss_delta < 120.0, f"RSS grew {rss_delta:.1f} MB"


def test_governor_events_surface_reclaims(cluster):
    """Force a watermark breach and observe the structured event +
    reclaim land in the governor's log (the drift/ops surface the
    operator reads via `operator governor`)."""
    server, _client = cluster
    gov = server.governor
    reg = gov.registry.get("event_broker.bytes")
    old_high, old_low = reg.watermark.high, reg.watermark.low
    reg.watermark.high = 1.0
    reg.watermark.low = 0.5
    try:
        # publish enough events to sit over the tiny watermark
        from nomad_tpu.server.event_broker import Event
        server.events.publish([Event(topic="Job", type="T", key="k",
                                     index=10_000 + i)
                               for i in range(8)])
        gov.sample_once()
        kinds = [e["kind"] for e in gov.events()]
        assert "watermark" in kinds
        assert "reclaim" in kinds or reg.reclaims > 0
    finally:
        reg.watermark.high, reg.watermark.low = old_high, old_low
        reg.status = "ok"
