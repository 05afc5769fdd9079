"""The reduction from a profiler trace to device metrics, on a small
hand-made trace and on a trace recorded on the chip (recorded_trace.json,
a slice of a prod-10k_service-stream window on a TPU v5 lite, PR 24)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import tracered as tr  # noqa: E402

DEV = "/device:TPU:0"


def op(start, dur, name="%fusion = f32[8]{0} fusion(...)", plane=DEV):
    return {"plane": plane, "line": tr.OPS_LINE, "name": name,
            "start_s": start, "dur_s": dur}


def module(start, dur, name, plane=DEV):
    return {"plane": plane, "line": tr.MODULES_LINE, "name": name,
            "start_s": start, "dur_s": dur}


SMALL = [
    module(1.0, 0.30, "jit__select_scan_fn(123)"),
    op(1.00, 0.10), op(1.05, 0.10, "%while.8 = (s32[]) while(...)"),
    op(1.20, 0.10),
    module(2.0, 0.10, "jit_fn(7)"),                 # a table scatter
    op(2.00, 0.10, "%copy = f32[16384,4]{1,0} copy(...)"),
    module(3.0, 0.20, "jit_fn(9)"),                 # a vmapped kernel
    op(3.00, 0.20, "%while.2 = (s32[]) while(...)"),
    op(9.0, 0.5),                                   # outside the window
    {"plane": "/host:CPU", "line": "python", "name": tr.MARK,
     "start_s": 0.5, "dur_s": 0.002},
    {"plane": "/host:CPU", "line": tr.OPS_LINE, "name": "host op",
     "start_s": 0.0, "dur_s": 5.0},
]


def test_busy_is_the_union_not_the_sum():
    # 1.00-1.15 and 1.20-1.30, 2.0-2.1, 3.0-3.2
    assert tr.busy_s(SMALL, 0.0, 5.0) == pytest.approx(0.15 + 0.10 + 0.10
                                                       + 0.20)


def test_busy_is_clipped_to_the_window():
    assert tr.busy_s(SMALL, 1.1, 2.05) == pytest.approx(0.05 + 0.10 + 0.05)


def test_host_planes_do_not_count_as_device():
    assert not tr.is_device_plane("/host:CPU")
    assert tr.is_device_plane("/device:TPU:3")
    assert tr.busy_s([e for e in SMALL if e["plane"] != DEV], 0, 5) == 0.0


def test_idle_share():
    assert tr.idle_share(0.55, 5.0) == pytest.approx(0.89)


def test_busy_averages_over_chips():
    two = SMALL + [op(1.0, 0.05, plane="/device:TPU:1")]
    assert tr.busy_s(two, 0.0, 5.0) == pytest.approx((0.55 + 0.05) / 2)


def test_kernel_time_names_the_placement_programs_only():
    seconds, runs = tr.kernel_s(SMALL, 0.0, 5.0, tr.kernel_patterns())
    # the scan by name, the vmapped kernel by the loop inside it; the
    # scatter, which is also called jit_fn, has no loop and stays out
    assert (seconds, runs) == (pytest.approx(0.30 + 0.20), 2)


def test_kernel_time_takes_runs_that_start_in_the_window():
    seconds, runs = tr.kernel_s(SMALL, 2.5, 5.0, tr.kernel_patterns())
    assert (seconds, runs) == (pytest.approx(0.20), 1)


def test_top_ops_are_named_by_program_and_op():
    top = tr.top_ops(SMALL, 0.0, 5.0, n=3)
    assert top[0] == ["jit__select_scan_fn/fusion", pytest.approx(0.20)]
    assert top[1] == ["jit_fn/while.2", pytest.approx(0.20)]
    assert len(top) == 3


def test_idle_gaps_cover_what_busy_leaves():
    gaps = tr.idle_gaps(SMALL, 0.0, 5.0)
    assert sum(b - a for a, b in gaps) == pytest.approx(5.0 - 0.55)
    assert gaps[0] == (0.0, 1.0) and gaps[-1] == (pytest.approx(3.2), 5.0)


@pytest.mark.parametrize("gap,want", [
    ((1.30, 2.00), "plan_verify"),      # the child names the gap
    ((2.10, 3.00), "sched_host"),       # only the wrapper covers it
    ((3.20, 5.00), "idle"),             # nothing does
    ((0.00, 1.00), "queue_wait"),
])
def test_gap_is_named_by_the_stage_the_host_was_in(gap, want):
    spans = [("queue_wait", 0.1, 0.9), ("sched_host", 1.0, 3.0),
             ("plan_verify", 1.4, 1.9), ("kernel", 1.0, 1.31)]
    assert tr.name_gap(gap, spans) == want


@pytest.mark.parametrize("gap,want", [
    # the evals that wait cover all of it, a collection a tenth: the
    # host was collecting, the evals were only waiting for it
    ((4.0, 5.0), "gc_full"),
    # the worker works inside its own eval's gateway park
    ((5.0, 6.0), "kernel_pack"),
    # only the wrapper and a wait: the wrapper names it
    ((6.0, 7.0), "sched_host"),
    # waits alone: the longest of them still names the gap
    ((7.0, 8.0), "plan_queue_wait"),
])
def test_a_wait_names_a_gap_only_where_nothing_else_covers_it(gap, want):
    spans = [("queue_wait", 3.0, 6.5), ("queue_wait", 3.5, 6.9),
             ("gc_full", 4.4, 4.5),
             ("gateway_wait", 5.0, 6.0), ("kernel_pack", 5.2, 5.3),
             ("sched_host", 6.0, 7.0), ("fence_wait", 6.0, 6.9),
             ("plan_queue_wait", 7.0, 7.9), ("queue_wait", 7.2, 7.9)]
    assert tr.name_gap(gap, spans) == want
    assert set(tr.WAITS) == {"queue_wait", "gateway_wait", "fence_wait",
                             "plan_queue_wait"}


def test_longest_gaps_first_and_at_most_n():
    spans = [("sched_host", 1.0, 3.0)]
    gaps = tr.longest_gaps(SMALL, 0.0, 5.0, spans, n=2)
    assert gaps == [["idle", pytest.approx(1.8)], ["idle", pytest.approx(1.0)]]


def test_clock_offset_comes_from_the_mark():
    assert tr.clock_offset(SMALL, 100.5) == pytest.approx(100.0)
    assert tr.clock_offset([e for e in SMALL if e["name"] != tr.MARK],
                           100.5) is None


def test_reduce_puts_it_together():
    red = tr.reduce(SMALL, 0.0, 5.0)
    assert red["window_s"] == 5.0
    assert red["busy_s"] == pytest.approx(0.55)
    assert red["idle_share"] == pytest.approx(0.89)
    assert red["kernel_s"] == pytest.approx(0.5) and red["kernel_runs"] == 2
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_union_merges_touching_and_nested():
    assert tr.union([(0, 1), (1, 2), (0.5, 0.6), (3, 4)]) == [(0, 2), (3, 4)]


# -- the recorded trace ----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_what_was_read_by_hand(recorded):
    red = tr.reduce(recorded["events"], recorded["t0"], recorded["t1"],
                    [tuple(s) for s in recorded["spans"]])
    want = recorded["by_hand"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert red["kernel_s"] == pytest.approx(want["kernel_s"], rel=1e-6)
    assert red["kernel_runs"] == want["kernel_runs"]
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["kernel_s"] <= red["busy_s"] * 1.0001
    assert red["device_ops"][0][0] == want["top_op"]


def test_recorded_trace_gaps_and_busy_make_the_window(recorded):
    ev, t0, t1 = recorded["events"], recorded["t0"], recorded["t1"]
    gaps = tr.idle_gaps(ev, t0, t1)
    assert sum(b - a for a, b in gaps) + tr.busy_s(ev, t0, t1) == \
        pytest.approx(t1 - t0, rel=1e-9)
    named = tr.longest_gaps(ev, t0, t1,
                            [tuple(s) for s in recorded["spans"]])
    assert len(named) <= 10 and all(sec > 0 for _n, sec in named)
    assert {n for n, _s in named} - {"idle"}      # some gap has a stage


def test_recorded_trace_names_no_gap_by_a_wait_a_stage_covers(recorded):
    spans = [tuple(s) for s in recorded["spans"]]
    for gap in tr.idle_gaps(recorded["events"], recorded["t0"],
                            recorded["t1"]):
        name = tr.name_gap(gap, spans)
        covering = {s for s, a, b in spans
                    if min(b, gap[1]) > max(a, gap[0])}
        if covering - set(tr.WAITS):
            assert name not in tr.WAITS, (gap, covering)
