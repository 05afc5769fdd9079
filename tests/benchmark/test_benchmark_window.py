"""Window arithmetic: rates over the whole window, tails of all requests."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import window  # noqa: E402
from benchmark.readers import (client_quantile, counter_delta,  # noqa: E402
                               counter_per_work, counter_share_max,
                               gc_pause, programs_met, rate, routing_share,
                               stage_per_eval, stage_quantile, stage_share,
                               trace_field)


@pytest.mark.parametrize("q,want", [(0.5, 5), (0.99, 10), (0.1, 1),
                                    (0.95, 10), (0.0, 1), (1.0, 10)])
def test_quantile_is_nearest_rank(q, want):
    assert window.quantile(list(range(1, 11)), q) == want


def test_quantile_of_nothing_is_nothing():
    assert window.quantile([], 0.5) is None


def test_rate_counts_only_the_window_over_the_whole_window():
    done = [(-1.0, 1000), (0.0, 1000), (9.99, 1000), (10.0, 1000)]
    assert window.rate_per_s(done, 10.0) == 200.0


def _serve(due, service_s, stall=None):
    """A single server: each request starts when due and the server is
    free; `stall` = (at, length) freezes it once."""
    free, done = 0.0, []
    for t in due:
        start = max(t, free)
        if stall and start >= stall[0] and free <= stall[0] + stall[1]:
            start = max(start, stall[0] + stall[1])
        free = start + service_s
        done.append(free)
    return done


def test_a_stall_inside_the_window_raises_the_tail_and_lowers_the_rate():
    due = [i * 0.1 for i in range(100)]
    calm = _serve(due, 0.05)
    stalled = _serve(due, 0.05, stall=(5.0, 1.0))
    lat_c, _ = window.latencies_ms(due, calm, 20.0)
    lat_s, _ = window.latencies_ms(due, stalled, 20.0)
    assert window.quantile(lat_s, 0.99) > 10 * window.quantile(lat_c, 0.99)
    assert window.quantile(lat_s, 0.5) >= window.quantile(lat_c, 0.5)
    # a stall the window's end cuts through leaves its work undone
    late = _serve(due, 0.05, stall=(9.2, 1.0))
    rate_c = window.rate_per_s([(t, 1) for t in calm], 10.0)
    rate_s = window.rate_per_s([(t, 1) for t in late], 10.0)
    assert rate_s < rate_c


def test_latency_runs_from_due_not_from_sent():
    lat, failed = window.latencies_ms([1.0], [1.5], 99.0)
    assert lat == [500.0] and failed == 0


def test_a_request_that_never_completes_waits_to_the_end_and_fails():
    lat, failed = window.latencies_ms([1.0, 2.0], [1.1, None], 62.0)
    assert failed == 1 and lat[1] == 60000.0


OBS = {
    "seconds": 10.0,
    "series": {"eval_ms": [(-1.0, 999.0), (0.5, 10.0), (1.0, 30.0),
                           (9.0, 20.0), (10.5, 999.0)],
               "placements": [(-0.1, 1000), (2.0, 1000), (9.9, 500)]},
    "stages": [("kernel", -0.5, 1.0), ("kernel", 1.0, 0.002),
               ("kernel", 2.0, 0.004), ("table_build", 3.0, 0.010),
               ("kernel", 11.0, 1.0),
               # a write the window's open cuts, one its close cuts (a
               # sibling writer's wait inside it), one outside
               ("snapshot_write", 1.0, 3.0), ("snapshot_write", 11.0, 2.0),
               ("snapshot_write", 9.5, 0.4), ("snapshot_write", 14.0, 2.0)],
    "evals_done": 4,
    "gc_pauses": [(-2.0, 0.7, 2), (1.0, 0.2, 2), (5.0, 0.05, 1),
                  (12.0, 0.9, 2)],
    "programs_met": [(-3.0, "program", 1.0), (4.0, "program", 0.1)],
    "counters": {"before": {"persistence.background_snapshots": 1.0,
                            "persistence.wal_bytes": 1.0e6},
                 "after": {"persistence.background_snapshots": 2.0,
                           "persistence.wal_bytes": 4.9e6,
                           "persistence.wal_bytes_since_snapshot": 2.0e6,
                           "persistence.snapshot_wal_bytes": 8.0e6,
                           "persistence.wal_entries_since_snapshot": 30.0,
                           "persistence.snapshot_every": 40.0}},
    "routing": {"before": {"scan": 10, "scan@cpu": 1},
                "after": {"scan": 40, "scan@cpu": 11, "kway": 0}},
    "device": {"kind": "TPU v5 lite"},
    "trace": {"busy_s": 0.5, "window_s": 10.0, "idle_share": 0.95,
              "kernel_s": 0.4},
    "traced_evals": 100, "traced_floor_bytes": 819e9 * 0.004,
}


DUE = [["persistence.wal_bytes_since_snapshot",
        "persistence.snapshot_wal_bytes"],
       ["persistence.wal_entries_since_snapshot",
        "persistence.snapshot_every"]]


@pytest.mark.parametrize("reader,args,want", [
    (client_quantile, {"series": "eval_ms", "q": 0.5}, 20.0),
    (client_quantile, {"series": "eval_ms", "q": 0.99}, 30.0),
    (rate, {"series": "placements"}, 150.0),
    (stage_quantile, {"stage": "kernel", "q": 0.5}, 2.0),
    (stage_per_eval, {"stage": "table_build"}, 2.5),
    (stage_per_eval, {"stage": "restore"}, 0.0),
    (gc_pause, {"stat": "max_ms"}, 200.0),
    (gc_pause, {"stat": "share_pct"}, 2.5),
    (programs_met, {}, 1.0),
    (counter_delta, {"key": "persistence.background_snapshots"}, 1.0),
    (stage_share, {"stage": "snapshot_write"}, 20.0),
    (stage_share, {"stage": "restore"}, 0.0),
    # the entry count is nearer its trigger (75%) than the bytes (25%)
    (counter_share_max, {"pairs": DUE}, 75.0),
    (counter_share_max, {"pairs": DUE[:1]}, 25.0),
    # 3.9 MB over the 1,500 placements completed inside the window
    (counter_per_work, {"key": "persistence.wal_bytes",
                        "series": "placements", "per": 1000.0}, 2.6),
    (routing_share, {}, 75.0),
    (trace_field, {"field": "idle_share_pct"}, 95.0),
    (trace_field, {"field": "kernel_ms_per_eval"}, 4.0),
    (trace_field, {"field": "roofline_pct"}, 1.0),
])
def test_reader_reads_the_window_only(reader, args, want):
    assert reader.read(OBS, **args) == pytest.approx(want)


@pytest.mark.parametrize("reader,args", [
    (client_quantile, {"series": "generator_late_ms", "q": 0.5}),
    (rate, {"series": "nothing"}),
    (stage_quantile, {"stage": "preempt", "q": 0.5}),
    (trace_field, {"field": "roofline_pct"}),
    (gc_pause, {"stat": "max_ms"}),
    (programs_met, {}),
    (counter_delta, {"key": "persistence.background_snapshots"}),
    (counter_share_max, {"pairs": DUE}),
    (counter_per_work, {"key": "persistence.wal_bytes",
                        "series": "placements", "per": 1000.0}),
    (routing_share, {}),
])
def test_reader_with_nothing_to_read_returns_nothing(reader, args):
    empty = {"seconds": 10.0, "series": {}, "stages": [],
             "trace": {"busy_s": 0.0, "kernel_s": 0.0}}
    assert reader.read(empty, **args) is None


def test_readers_of_counters_and_spans_without_their_source():
    # an untraced run has no tap: no share, rather than a 0
    assert stage_share.read({"seconds": 10.0}, "snapshot_write") is None
    # counters of a program without a WAL position, work without any
    quiet = dict(OBS, counters={"before": {}, "after": {}})
    assert counter_share_max.read(quiet, DUE) is None
    args = {"key": "persistence.wal_bytes", "series": "placements",
            "per": 1000.0}
    assert counter_per_work.read(quiet, **args) is None
    idle = dict(OBS, series={"placements": [(-1.0, 1000), (10.0, 1000)]})
    assert counter_per_work.read(idle, **args) is None
