"""Telemetry: metrics registry, instrumentation, /v1/metrics,
agent monitor stream, pprof analogs (reference: armon/go-metrics via
setupTelemetry, worker.go:162-282 measure points, agent_endpoint.go
monitor/pprof) — plus the ISSUE 11 retained-telemetry core: histogram
buckets + Prometheus exposition round-trip, InmemSink parity
(interval-anchored Timestamp, explicit empty-sample Min), the
struct-of-arrays history ring's bounding, the flatness verdict and
the live route's parity with it, /v1/operator/telemetry + /v1/operator/
flatness + ?format=prometheus surface, `operator top`, the
NOMAD_TPU_TELEMETRY kill switch, what one sample costs as counts, and
(slow, by hand on a quiet machine) the paired collector-overhead
smoke.
"""

import calendar
import json
import logging
import threading
import time
import urllib.request

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.api import HTTPApiServer
from nomad_tpu.api.client import ApiClient
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.telemetry import MAX_SERIES, TelemetryCollector
from nomad_tpu.telemetry import collector as telemetry_collector
from nomad_tpu.telemetry.collector import flatness_verdict
from nomad_tpu.utils.metrics import (HIST_BUCKETS_MS, INTERVAL_S,
                                     Histogram, MetricsRegistry,
                                     prom_name)
from nomad_tpu.utils.monitor import MonitorBuffer


def _wait_for(pred, timeout=15.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_registry_counters_gauges_samples():
    r = MetricsRegistry()
    r.set_gauge("g", 3.5)
    r.incr_counter("c")
    r.incr_counter("c", 2)
    r.add_sample_ms("s", 10.0)
    r.add_sample_ms("s", 30.0)
    snap = r.snapshot()
    assert snap["Gauges"] == [{"Name": "g", "Value": 3.5}]
    c = snap["Counters"][0]
    assert c["Name"] == "c" and c["Count"] == 2 and c["Sum"] == 3
    s = snap["Samples"][0]
    assert s["Count"] == 2 and s["Min"] == 10.0 and s["Max"] == 30.0 \
        and s["Mean"] == 20.0


# -- ISSUE 11 satellite: InmemSink parity -------------------------------

def test_timestamp_is_interval_anchored():
    """The reference InmemSink aggregates into fixed intervals and
    DisplayMetrics reports the interval boundary, not call time: two
    scrapes inside one interval agree on their window."""
    r = MetricsRegistry()
    ts = r.snapshot()["Timestamp"]
    epoch = calendar.timegm(
        time.strptime(ts, "%Y-%m-%d %H:%M:%S +0000"))
    assert epoch % int(INTERVAL_S) == 0
    # anchored to the CURRENT interval (within one interval of now)
    assert 0 <= time.time() - epoch < 2 * INTERVAL_S


def test_empty_sample_min_explicit():
    """A sample set with no ingests reports Min 0.0 because Count is
    0 — never an inf sentinel leaking out of the raw aggregate."""
    from nomad_tpu.utils.metrics import _Sample
    s = _Sample()
    assert s.min is None            # distinct no-samples state
    r = MetricsRegistry()
    with r._l:
        r._samples["never"] = _Sample()
    row = [x for x in r.snapshot()["Samples"] if x["Name"] == "never"][0]
    assert row["Count"] == 0 and row["Min"] == 0.0 and row["Mean"] == 0.0
    assert row["Min"] != float("inf")
    s.add(5.0)
    s.add(9.0)
    assert s.min == 5.0


# -- ISSUE 11: histogram buckets + quantile math ------------------------

def test_histogram_quantiles_vs_numpy():
    """Bucket-interpolated quantiles track numpy percentiles to within
    the containing bucket's width (that is the histogram contract —
    Prometheus histogram_quantile has exactly this resolution)."""
    rng = np.random.RandomState(7)
    vals = np.concatenate([rng.uniform(0.5, 40.0, 1500),
                           rng.uniform(100.0, 900.0, 500)])
    h = Histogram()
    for v in vals:
        h.add(float(v))
    assert h.count == len(vals)
    assert abs(h.sum - float(vals.sum())) < 1e-6
    bounds = (0.0,) + HIST_BUCKETS_MS
    for q in (10, 50, 90, 99):
        est = h.quantile(q / 100.0)
        ref = float(np.percentile(vals, q))
        # tolerance: the width of the bucket holding the true quantile
        i = next(k for k in range(1, len(bounds))
                 if ref <= bounds[k])
        width = bounds[i] - bounds[i - 1]
        assert abs(est - ref) <= width, (q, est, ref, width)
    # degenerate cases
    assert Histogram().quantile(0.5) == 0.0
    h2 = Histogram()
    h2.add(50000.0)                 # beyond the last bound -> +Inf
    assert h2.counts[-1] == 1
    assert h2.quantile(0.99) == HIST_BUCKETS_MS[-1]


def _parse_prometheus(text):
    """Minimal exposition parser: {name_with_labels: value} + types."""
    values, types = {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _h, _t, name, kind = line.split()
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        values[key] = float(val)
    return values, types


def test_prometheus_exposition_roundtrip():
    """Render -> parse -> compare against the JSON snapshot: every
    gauge/counter value survives, histogram buckets are cumulative and
    monotone, _count/_sum agree with the sample aggregate."""
    r = MetricsRegistry()
    r.set_gauge("nomad.broker.total_ready", 7.0)
    r.incr_counter("nomad.plan.apply", 3)
    r.incr_counter("nomad.plan.apply", 2)
    for v in (0.3, 4.0, 4.5, 80.0, 2000.0):
        r.add_sample_ms("nomad.worker.invoke", v)
    values, types = _parse_prometheus(r.prometheus())
    snap = r.snapshot()
    g = snap["Gauges"][0]
    assert values[prom_name(g["Name"])] == g["Value"]
    assert types[prom_name(g["Name"])] == "gauge"
    c = snap["Counters"][0]
    assert values[prom_name(c["Name"]) + "_total"] == c["Sum"] == 5.0
    assert types[prom_name(c["Name"]) + "_total"] == "counter"
    s = snap["Samples"][0]
    pn = prom_name(s["Name"])
    assert types[pn] == "histogram"
    assert values[pn + "_count"] == s["Count"] == 5
    assert values[pn + "_sum"] == pytest.approx(s["Sum"])
    buckets = [(k, v) for k, v in values.items()
               if k.startswith(pn + "_bucket")]
    assert len(buckets) == len(HIST_BUCKETS_MS) + 1
    cum = [v for _k, v in buckets]
    assert cum == sorted(cum)           # cumulative => monotone
    assert values[f'{pn}_bucket{{le="+Inf"}}'] == 5
    # le="5" holds 0.3, 4.0, 4.5
    assert values[f'{pn}_bucket{{le="5"}}'] == 3


# -- ISSUE 11: history ring bounding ------------------------------------

def test_ring_slots_and_bytes_bounded_under_churn():
    """A gauge-name churn storm must not grow the ring: series are
    capped at MAX_SERIES (drops counted), slots wrap (oldest
    overwritten), and the byte ceiling is slots x series x 8."""
    tick = {"n": 0}

    def churny_gauges():
        tick["n"] += 1
        # 40 fresh names every sample: blows past MAX_SERIES fast
        return {f"churn.{tick['n']}.{i}": float(i) for i in range(40)}

    tc = TelemetryCollector(interval_s=1.0, slots=32,
                            gauges_fn=churny_gauges, device_fn=None)
    for _ in range(20):
        tc.sample_once()
    st = tc.status()
    assert st["samples"] == 20
    assert st["series_count"] <= MAX_SERIES
    assert st["series_dropped"] > 0
    assert st["ring_bytes"] <= (MAX_SERIES + 1) * 32 * 8
    hist = tc.history()
    assert len(hist["t"]) == 20         # under slot capacity: no wrap
    for _ in range(20):
        tc.sample_once()
    hist = tc.history()
    assert len(hist["t"]) == 32         # wrapped: ring depth, not 40
    assert hist["samples"] == 40
    # chronological after wrap
    ts = hist["t"]
    assert ts == sorted(ts)
    # a series that stopped reporting reads None (NaN-cleared), not a
    # stale wrapped-over value
    first_series = "churn.1.0"
    vals = hist["series"].get(first_series)
    if vals is not None:
        assert all(v is None for v in vals)


def test_ring_history_limit_and_rates():
    """`last` limits history; cumulative counter series expose derived
    per-second rates (delta over dt), NaN where undefined."""
    from nomad_tpu.utils import metrics as gm
    name = f"test.ring.rate.{time.monotonic_ns()}"
    tc = TelemetryCollector(interval_s=1.0, slots=64, device_fn=None)
    for i in range(6):
        gm.incr_counter(name, 10)
        tc.sample_once(now=1000.0 + i)      # dt == 1s exactly
    hist = tc.history(last=4)
    assert len(hist["t"]) == 4
    key = f"counter.{name}"
    assert key in hist["series"]
    rates = hist["rates"][key]
    assert rates[-1] == pytest.approx(10.0)
    full = tc.history()
    assert full["rates"][key][0] is None    # no left neighbor
    assert all(r == pytest.approx(10.0)
               for r in full["rates"][key][1:])


def test_monitor_buffer_levels_and_blocking():
    buf = MonitorBuffer()
    log = logging.getLogger("nomad_tpu.test-monitor")
    log.addHandler(buf)
    log.setLevel(logging.DEBUG)
    log.info("hello-info")
    log.debug("hello-debug")
    seq, lines = buf.read_since(0, logging.INFO, timeout_s=1.0)
    assert any("hello-info" in ln for ln in lines)
    assert not any("hello-debug" in ln for ln in lines)
    # blocking read wakes on a new record
    got = []

    def reader():
        _s, ls = buf.read_since(seq, logging.INFO, timeout_s=5.0)
        got.extend(ls)

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.1)
    log.warning("wake-up")
    t.join(timeout=5)
    assert any("wake-up" in ln for ln in got)


# -- ISSUE 11: live flatness verdict parity -----------------------------

class TestFlatnessVerdict:
    def test_flat_windows_pass(self):
        windows = [{"t_min": i, "p99_ms": 50.0 + (i % 2),
                    "rss_mb": 1000.0 + i} for i in range(10)]
        v = flatness_verdict(windows)
        assert v["pass"] is True
        assert v["p99_drift_ratio"] < 1.1
        assert v["rss_slope_mb_per_hour"] == 60.0  # 1 MB/min fit

    def test_p99_drift_fails(self):
        windows = [{"t_min": i, "p99_ms": 50.0 * (1 + i),
                    "rss_mb": 1000.0} for i in range(10)]
        v = flatness_verdict(windows)
        assert v["pass"] is False
        assert "p99 drift" in v["reason"]

    def test_rss_slope_fails(self):
        windows = [{"t_min": i, "p99_ms": 50.0,
                    "rss_mb": 1000.0 + 10.0 * i} for i in range(10)]
        v = flatness_verdict(windows)
        assert v["pass"] is False
        assert "rss slope" in v["reason"]

    def test_too_few_windows(self):
        assert flatness_verdict([])["pass"] is False


def _scripted_collector(monkeypatch, p99s, rsss):
    """A collector whose windows are fully scripted: latency_fn and
    rss_mb return the given series step by step, one sample per
    window, 1 minute apart."""
    idx = {"i": -1}

    def lat(pct):
        return p99s[idx["i"]] if pct == 99 else p99s[idx["i"]] / 2

    monkeypatch.setattr(telemetry_collector, "rss_mb",
                        lambda: rsss[idx["i"]])
    tc = TelemetryCollector(interval_s=60.0, slots=64,
                            latency_fn=lat, device_fn=None)
    for i in range(len(p99s)):
        idx["i"] = i
        tc.sample_once(now=1_000_000.0 + i * 60.0)
    return tc


def test_flatness_verdict_parity_with_soak(monkeypatch):
    """/v1/operator/flatness reuses flatness_verdict: over
    identical synthetic windows the live verdict and the function's
    own are the SAME dict (same drift ratios, slopes,
    pass bit, reasons) — for a flat window set and a drifting one."""
    flat_p99 = [50.0, 52.0, 49.0, 51.0, 50.0, 52.0, 50.0, 51.0]
    flat_rss = [500.0, 501.0, 500.5, 501.0, 500.8, 501.2, 500.9, 501.0]
    drift_p99 = [50.0, 52.0, 60.0, 75.0, 90.0, 120.0, 150.0, 180.0]
    drift_rss = [500.0, 520.0, 545.0, 570.0, 600.0, 625.0, 650.0, 680.0]

    for p99s, rsss, want_pass in ((flat_p99, flat_rss, True),
                                  (drift_p99, drift_rss, False)):
        tc = _scripted_collector(monkeypatch, p99s, rsss)
        windows = tc.windows()
        # the collector's windows carry exactly the scripted series
        assert [w["p99_ms"] for w in windows] == p99s
        assert [w["rss_mb"] for w in windows] == rsss
        live = tc.flatness()
        ref = flatness_verdict(windows)
        for k, v in ref.items():
            assert live[k] == v, (k, live[k], v)
        assert live["pass"] is want_pass
        assert live["windows_measured"] == len(p99s)


def test_flatness_route_matches_soak_verdict(monkeypatch):
    """The HTTP route serves the same verdict flatness_verdict
    computes over the server collector's windows (background sampling
    disabled: interval pinned high, samples driven by hand)."""
    server = Server(ServerConfig(num_schedulers=0,
                                 telemetry_sample_interval_s=3600.0))
    api = HTTPApiServer(server, port=0)
    api.start()
    try:
        tc = server.telemetry
        assert tc is not None
        monkeypatch.setattr(telemetry_collector, "rss_mb", lambda: 512.0)
        for i in range(6):
            tc.sample_once(now=2_000_000.0 + i * 60.0)
        ref = flatness_verdict(tc.windows())
        c = ApiClient(f"http://127.0.0.1:{api.port}")
        live = c.flatness()
        assert live["enabled"] is True
        for k, v in ref.items():
            assert live[k] == v, (k, live[k], v)
    finally:
        api.shutdown()
        server.shutdown()


def test_flatness_insufficient_history_and_warmup_scaling(monkeypatch):
    """The live verdict rescales the soak's 60s-window calibration to
    the ring cadence: warmup exclusion covers ~60s of wall clock, and
    until 120s of post-warmup history exists the verdict is pass=None
    ('insufficient history') — a slope fit over seconds is noise, not
    a steady-state failure."""
    monkeypatch.setattr(telemetry_collector, "rss_mb", lambda: 100.0)
    tc = TelemetryCollector(interval_s=1.0, slots=256,
                            latency_fn=lambda p: 10.0, device_fn=None)
    for i in range(10):
        tc.sample_once(now=5_000_000.0 + i)
    out = tc.flatness()
    assert out["pass"] is None
    assert "insufficient history" in out["reason"]
    for i in range(10, 200):
        tc.sample_once(now=5_000_000.0 + i)
    out = tc.flatness()
    # 1s cadence -> 60 warmup slots excluded (the soak's one 60s
    # window), and 139s of flat post-warmup history => a real verdict
    assert out["warmup_windows_excluded"] == 60
    assert out["span_s"] >= 120.0
    assert out["pass"] is True


# -- ISSUE 11: kill switch ---------------------------------------------

def test_telemetry_kill_switch(monkeypatch):
    """NOMAD_TPU_TELEMETRY=0 degenerates to today's snapshot-only
    behavior: no collector object on the server, telemetry/flatness
    routes report disabled, /v1/metrics still serves both formats."""
    monkeypatch.setenv("NOMAD_TPU_TELEMETRY", "0")
    server = Server(ServerConfig(num_schedulers=0))
    api = HTTPApiServer(server, port=0)
    api.start()
    try:
        assert server.telemetry is None
        c = ApiClient(f"http://127.0.0.1:{api.port}")
        assert c.telemetry() == {"enabled": False}
        flat = c.flatness()
        assert flat["enabled"] is False and flat["pass"] is None
        snap = c.metrics()
        assert "Gauges" in snap
        assert "# TYPE" in c.metrics(format="prometheus")
    finally:
        api.shutdown()
        server.shutdown()
    # interval=0 is the config-level equivalent
    monkeypatch.delenv("NOMAD_TPU_TELEMETRY")
    server2 = Server(ServerConfig(num_schedulers=0,
                                  telemetry_sample_interval_s=0.0))
    try:
        assert server2.telemetry is None
    finally:
        server2.shutdown()


# -- ISSUE 11: HTTP surface + operator top ------------------------------

def test_telemetry_history_route_and_operator_top(monkeypatch):
    """/v1/operator/telemetry serves the chronological ring (series +
    derived rates, JSON-safe), and `nomad operator top` renders rates,
    trends, device economics, and the flatness verdict from it."""
    import contextlib
    import io
    from nomad_tpu.cli.main import main as cli_main
    from nomad_tpu.utils import metrics as gm
    server = Server(ServerConfig(num_schedulers=0,
                                 telemetry_sample_interval_s=3600.0))
    api = HTTPApiServer(server, port=0)
    api.start()
    try:
        tc = server.telemetry
        for i in range(5):
            gm.incr_counter("nomad.worker.eval_processed", 5)
            gm.incr_counter("nomad.plan.placements", 50)
            tc.sample_once(now=3_000_000.0 + i)
        c = ApiClient(f"http://127.0.0.1:{api.port}")
        tel = c.telemetry(last=4)
        assert len(tel["t"]) == 4
        assert tel["samples"] == 5
        assert "process.rss_mb" in tel["series"]
        # governor gauges ride along under their registry names
        assert "broker.ready" in tel["series"]
        # the device.* family is sampled
        assert "device.kernel_cache_entries" in tel["series"]
        assert "device.mirror_bytes" in tel["series"]
        assert "device.pad_waste_ratio" in tel["series"]
        # counter series expose derived rates
        key = "counter.nomad.worker.eval_processed"
        assert key in tel["rates"]
        assert tel["rates"][key][-1] == pytest.approx(5.0)
        assert tel["rates"]["counter.nomad.plan.placements"][-1] == \
            pytest.approx(50.0)
        # JSON round-trip already proved NaN-cleanliness (urllib +
        # json.loads with default parse_constant accepts NaN, but the
        # cleaner turns gaps into None); spot-check types
        for vals in tel["series"].values():
            assert all(v is None or isinstance(v, (int, float))
                       for v in vals)

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main(["-address", f"http://127.0.0.1:{api.port}",
                           "operator", "top", "-n", "16"])
        assert rc == 0
        text = out.getvalue()
        assert "Evals/s" in text
        assert "Placements/s" in text
        assert "Device economics" in text
        assert "Flatness" in text
        # the CPU ledger (ISSUE 36) reads as cores: the process's line,
        # the roles beside it where a thread's clock can be read
        assert "process.cpu_s" in tel["rates"]
        cpu_line = next(line for line in text.splitlines()
                        if line.startswith("CPU "))
        assert "cores" in cpu_line
        if "thread_cpu.workers_s" in tel["rates"]:
            assert all(role in cpu_line for role in
                       ("workers", "applier", "http", "other"))
    finally:
        api.shutdown()
        server.shutdown()


def test_operator_top_reads_the_change_logs_trims():
    """The store's change-log trims ride the governor's gauge into the
    ring, and `operator top` prints them: one trim a publish past the
    cap."""
    import contextlib
    import io
    from nomad_tpu import mock
    from nomad_tpu.cli.main import main as cli_main
    server = Server(ServerConfig(num_schedulers=0,
                                 telemetry_sample_interval_s=3600.0))
    api = HTTPApiServer(server, port=0)
    api.start()
    try:
        store = server.store
        store.CHANGELOG_MAX = 3
        for i in range(5):
            store.upsert_node(10_000 + i, mock.node())
        assert store.changelog_stats()["trims"] == 2
        server.governor.sample_once()
        server.telemetry.sample_once()
        tel = ApiClient(f"http://127.0.0.1:{api.port}").telemetry(last=4)
        assert tel["series"]["state.changelog_trims"][-1] == 2
        assert store.changelog_stats()["dropped"] == 2
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main(["-address", f"http://127.0.0.1:{api.port}",
                           "operator", "top", "-n", "16"])
        assert rc == 0
        line = next(ln for ln in out.getvalue().splitlines()
                    if ln.startswith("Change log"))
        assert "3 entries, 2 trims" in line
    finally:
        api.shutdown()
        server.shutdown()


def test_operator_top_puts_a_spans_cpu_beside_its_wall(monkeypatch):
    """`operator top`'s stage table: a span's CPU companion is a column
    of its stage's row, never a row of its own; `operator trace` prints
    a span's `cpu_ms` among its attrs where the span read the clock."""
    import contextlib
    import io
    from nomad_tpu import trace
    from nomad_tpu.cli.main import main as cli_main
    from nomad_tpu.utils import stages
    server = Server(ServerConfig(num_schedulers=0,
                                 telemetry_sample_interval_s=3600.0))
    api = HTTPApiServer(server, port=0)
    api.start()
    try:
        trace.tracer.force_threshold_ms = 0.0   # every trace an exemplar

        class Ev:
            id, job_id, namespace, type = "ev-top", "job-top", "default", \
                "service"
            queue_wait_s = 0.0
        tr = trace.begin(Ev(), track="worker-0")
        with trace.use(tr):
            with stages.span("plan_build", placements=1):
                sum(range(20000))
            stages.add("sched_host_self", 0.001)
        trace.finish(tr)
        server.telemetry.sample_once(now=3_000_000.0)
        def cli(*sub):
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                assert cli_main(["-address",
                                 f"http://127.0.0.1:{api.port}",
                                 "operator", *sub]) == 0
            return out.getvalue().splitlines()

        lines = cli("top", "-n", "4")
        head = next(l for l in lines if l.startswith("Stage")
                    and "cpu p50 ms" in l)
        assert head.split()[-1] == "Samples"
        row = next(l for l in lines if l.startswith("plan_build ")
                   and len(l.split()) == 5)
        assert float(row.split()[3]) <= float(row.split()[1]) + 0.01
        wait = next(l for l in lines if l.startswith("sched_host_self ")
                    and len(l.split()) == 5)
        assert wait.split()[3] == "-"
        assert not any(l.startswith("plan_build_cpu") and
                       len(l.split()) == 5 for l in lines)
        # `operator trace`: the reservoirs' table lists the companion
        # as what it is, a reservoir; the exemplar's tree says cpu_ms
        lines = cli("trace")
        assert any(l.startswith("plan_build_cpu ") for l in lines)
        span = next(l for l in lines if " plan_build " in l and "[" in l)
        assert '"placements": 1' in span and '"cpu_ms": ' in span
        bare = next(l for l in lines if " sched_host_self " in l
                    and "[" in l)
        assert "cpu_ms" not in bare
    finally:
        api.shutdown()
        server.shutdown()
        trace.tracer.reset()


def test_prometheus_route_reflects_registry():
    """?format=prometheus on a live agent: text/plain exposition whose
    gauge values match the JSON snapshot scraped back-to-back."""
    from nomad_tpu.utils import metrics as gm
    server = Server(ServerConfig(num_schedulers=0))
    api = HTTPApiServer(server, port=0)
    api.start()
    gm.set_gauge("nomad.test.prom_probe", 41.5)
    try:
        url = f"http://127.0.0.1:{api.port}/v1/metrics?format=prometheus"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        values, types = _parse_prometheus(text)
        assert values["nomad_test_prom_probe"] == 41.5
        c = ApiClient(f"http://127.0.0.1:{api.port}")
        snap = c.metrics()
        # the probe gauge agrees across the two formats
        probe = [g for g in snap["Gauges"]
                 if g["Name"] == "nomad.test.prom_probe"]
        assert probe and probe[0]["Value"] == 41.5
        # every histogram family is structurally complete
        for name, kind in types.items():
            if kind == "histogram":
                assert name + "_count" in values
                assert name + "_sum" in values
                assert f'{name}_bucket{{le="+Inf"}}' in values
    finally:
        api.shutdown()
        server.shutdown()


# -- what one sample does, as counts -----------------------------------

def test_sample_once_reads_each_source_once_and_allocates_nothing_new():
    """The collector's cost per sample, in what repeats exactly: every
    source is read once (the latency reservoir twice, p50 and p99),
    every key of the row is written to one slot of its own series, and
    from the second sample on nothing is allocated that the ring's
    length could scale: the same arrays, the same bytes, across a
    wrap. (The wall-clock twin below is marked slow.)"""
    calls = {"gauges": 0, "latency": [], "stage": 0, "device": 0,
             "extra": 0}

    def gauges():
        calls["gauges"] += 1
        return {"g.a": 1.0, "g.b": 2.0}

    def latency(p):
        calls["latency"].append(p)
        return float(p)

    def stage():
        calls["stage"] += 1
        return {"kernel": {"p50_ms": 1.0, "p99_ms": 2.0, "count": 3}}

    def device():
        calls["device"] += 1
        return {"device.packs": 4.0}

    def extra():
        calls["extra"] += 1
        return {"cluster.nodes_total": 5.0}

    slots = 8
    tc = TelemetryCollector(interval_s=60.0, slots=slots,
                            gauges_fn=gauges, latency_fn=latency,
                            stage_fn=stage, device_fn=device,
                            extra_fn=extra)
    assert tc.sample_once(now=1000.0) == 1
    counters = {n for n in tc._series if n.startswith("counter.")}
    # the CPU ledger (ISSUE 36): the process's clock and, where another
    # thread's clock can be read, the four roles'
    cpu = {"process.cpu_s"} | {n for n in tc._series
                               if n.startswith("thread_cpu.")}
    assert len(cpu) in (1, 5)
    want = {"process.rss_mb", "g.a", "g.b", "latency.p50_ms",
            "latency.p99_ms", "stage.kernel.p50_ms",
            "stage.kernel.p99_ms", "stage_count.kernel", "device.packs",
            "cluster.nodes_total"} | counters | cpu
    assert set(tc._series) == want
    arrays = {n: id(a) for n, a in tc._series.items()}
    t_id, ring_bytes = id(tc._t), tc.status()["ring_bytes"]
    assert ring_bytes == (1 + len(want)) * slots * 8

    n = 3 * slots + 1                   # wraps the ring three times
    for i in range(1, n):
        assert tc.sample_once(now=1000.0 + 60.0 * i) == i + 1
    assert calls["gauges"] == calls["stage"] == calls["device"] \
        == calls["extra"] == n
    assert calls["latency"] == [50, 99] * n
    # the same arrays hold the ring: nothing was re-made or grown
    assert {m: id(a) for m, a in tc._series.items()} == arrays
    assert id(tc._t) == t_id
    assert tc.status()["ring_bytes"] == ring_bytes
    assert tc.status()["series_dropped"] == 0
    # one value a series a slot: every slot of every series is written
    for name in want - counters:
        assert not np.isnan(tc._series[name]).any(), name
    assert len(tc.history()["t"]) == slots


# -- ISSUE 11 acceptance: paired collector-overhead smoke ---------------

@pytest.mark.slow
def test_collector_overhead_within_5pct(monkeypatch):
    """Two overhead bounds (r13 paired methodology, split): (a)
    collector-on MODE keeps e2e eval latency within 5% of
    collector-off — modes alternate eval-by-eval so workload
    non-stationarity hits both classes identically, medians are
    outlier-robust, bounded retries absorb CI noise; (b) a full
    sample_once() (run every 4th on-eval so it's exercised under the
    live workload) stays under a 5% duty cycle at the production 1 s
    cadence — the bound the background sampler thread actually
    imposes."""
    from nomad_tpu.bench.ladder import _eval_for, _seed_nodes
    from nomad_tpu.scheduler.harness import Harness
    from nomad_tpu.utils import gcsafe

    h = Harness()
    # capacity must survive the retry budget (the r16 test_trace fix,
    # same arithmetic): mock nodes hold 7 allocs each and warm + three
    # measured phases place up to 1480 — 200 nodes (cap 1400) run dry
    # mid-second-retry exactly when full-suite load makes the retries
    # trigger. 256 keeps the same _pad_n bucket (256) so the measured
    # kernel shape is unchanged while the ceiling rises to 1792
    _seed_nodes(h, 256, dcs=1)

    tc = TelemetryCollector(interval_s=1.0, slots=128)

    def mk_job(tag, i):
        job = mock.job()
        job.id = f"tovh-{tag}-{i}"
        job.datacenters = ["dc1"]
        tg = job.task_groups[0]
        tg.count = 10
        for t in tg.tasks:
            t.resources.networks = []
        tg.networks = []
        return job

    def run_paired(tag, n_pairs=24):
        times = {True: [], False: []}
        sample_times = []
        with gcsafe.safepoints():
            for i in range(2 * n_pairs):
                on = (i % 2 == 0)
                job = mk_job(tag, i)
                h.store.upsert_job(h.next_index(), job)
                ev = _eval_for(job)
                t0 = time.perf_counter()
                h.process("service", ev)
                t1 = time.perf_counter()
                if on and i % 8 == 0:
                    tc.sample_once()
                    sample_times.append(time.perf_counter() - t1)
                times[on].append(t1 - t0)
                gcsafe.safepoint()

        def median(v):
            v = sorted(v)
            return v[len(v) // 2]

        # the sample is timed SEPARATELY from its host eval: in-eval
        # timing compared the on-median (the ~67th percentile of the
        # 18 unsampled evals, the 6 sampled ones occupying the top
        # ranks) against the off-median (a true 50th) — a bias
        # proportional to eval-time variance, which full-suite heap
        # state inflates past 5%. Mode overhead and sampling cost get
        # their own bounds below
        return (median(times[True]), median(times[False]),
                median(sample_times) if sample_times else 0.0)

    run_paired("warm", n_pairs=2)           # compile + caches
    on, off, sample = run_paired("m0")
    for attempt in range(2):
        if on <= off / 0.95:
            break
        on2, off2, sample2 = run_paired(f"m{attempt + 1}")  # noise retry
        on, off = min(on, on2), min(off, off2)
        sample = min(sample, sample2)
    assert on <= off / 0.95, (
        f"collector-on median {on * 1e3:.2f} ms/eval vs off "
        f"{off * 1e3:.2f} ms/eval")
    # (b) the sample itself: registry + reservoir + ring writes must
    # stay under a 5% duty cycle at the production cadence
    assert sample <= 0.05 * 1.0, (
        f"sample_once median {sample * 1e3:.2f} ms exceeds a 5% duty "
        f"cycle at the 1 s production interval")
    assert tc.status()["samples"] > 0


@pytest.fixture
def api_cluster():
    from nomad_tpu.client import Client, ClientConfig
    server = Server(ServerConfig(num_schedulers=2, heartbeat_ttl_s=30.0))
    server.start()
    client = Client(server, ClientConfig(node_name="telemetry"))
    client.start()
    api = HTTPApiServer(server, port=0)
    api.start()
    yield server, api
    api.shutdown()
    client.shutdown()
    server.shutdown()


@pytest.mark.slow
def test_metrics_endpoint_reflects_scheduling(api_cluster):
    server, api = api_cluster
    job = mock.batch_job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].config = {"run_for": "50ms"}
    server.register_job(job)
    assert _wait_for(lambda: len(
        server.store.allocs_by_job("default", job.id)) == 2)

    c = ApiClient(f"http://127.0.0.1:{api.port}")
    assert _wait_for(lambda: any(
        s["Name"].startswith("nomad.worker.invoke_scheduler")
        for s in c.metrics()["Samples"]), timeout=10)
    snap = c.metrics()
    names = {s["Name"] for s in snap["Samples"]}
    assert "nomad.worker.submit_plan" in names
    assert "nomad.plan.evaluate" in names
    assert _wait_for(lambda: any(
        g["Name"] == "nomad.state.latest_index" and g["Value"] > 0
        for g in c.metrics()["Gauges"]), timeout=5)


@pytest.mark.slow
def test_monitor_stream_and_pprof(api_cluster):
    server, api = api_cluster
    c = ApiClient(f"http://127.0.0.1:{api.port}")

    # pprof analogs
    threads = c.agent_threads()["threads"]
    assert any("plan-applier" in name for name in threads)
    prof = c.agent_profile(seconds=0.2)
    assert "profile" in prof

    # monitor: start streaming, then emit a log line and see it arrive
    url = f"http://127.0.0.1:{api.port}/v1/agent/monitor?log_level=info"
    resp = urllib.request.urlopen(url, timeout=10)
    logging.getLogger("nomad_tpu.server").warning("monitor-probe-123")
    found = False
    deadline = time.time() + 10
    while time.time() < deadline:
        line = resp.readline()
        if not line:
            break
        text = line.decode().strip()
        if "monitor-probe-123" in text:
            found = True
            break
    resp.close()
    assert found
