"""The span tree's metrics (ISSUE 25) in a traced toy rehearsal: every
one of them is in the line, and the stages the program's own parent
map puts directly under sched_host add up to it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchrun_helper import MANIFEST, rehearse     # noqa: E402

CELL = "prod-10k_batch-fill"
SPAN_METRICS = [
    "job_register_ms_p50", "sched_host_ms_per_eval",
    "sched_host_self_ms_per_eval", "reconcile_ms_per_eval",
    "select_prep_ms_per_eval", "select_finish_ms_per_eval",
    "plan_build_ms_per_eval", "plan_submit_ms_per_eval",
    "gateway_wait_ms_per_eval", "feasibility_ms_per_eval",
    "sched_host_self_ms_p50", "plan_submit_ms_p50",
    "plan_submits_per_eval", "plan_queue_wait_ms_p50",
    "plan_queue_wait_ms_p95", "table_build_private_ms_per_eval",
    "table_builds_private_per_eval", "kernel_ms_per_eval",
    "kernel_pack_ms_per_eval", "kernel_expand_ms_per_eval",
    "h2d_ms_per_eval", "d2h_ms_per_eval", "kernel_dispatches_per_eval"]


@pytest.fixture(scope="module")
def traced():
    line, _err = rehearse(CELL, "--trace", "1")
    assert line["correct"] and line["failed"] == 0
    return {name: m["value"] for name, m in line["metrics"].items()}


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_is_declared_and_in_the_traced_line(traced, metric):
    name = metric + ".batch"
    assert name in {m["name"] for m in MANIFEST["per_layer"]}
    assert name in traced and traced[name] >= 0.0


def test_children_of_sched_host_and_self_sum_to_it(traced):
    """The list comes from the program's map, not from here; a child
    that never reports in the cell (preempt) has no metric and counts
    0."""
    from nomad_tpu.trace import STAGE_PARENTS
    children = [s for s, parent in STAGE_PARENTS.items()
                if parent == "sched_host"]
    assert "sched_host_self" in children and "plan_submit" in children
    total = sum(traced.get(f"{s}_ms_per_eval.batch", 0.0)
                for s in children)
    assert total == pytest.approx(traced["sched_host_ms_per_eval.batch"],
                                  rel=0.05)
    # one kernel dispatch and one plan an eval, but for the retries of
    # partly committed plans. Not `1.0 <=`: a plan_submit that ends just
    # before the window opens belongs to an eval that completes inside
    # it, so a 3 s toy window reads a little under one (0.976 once in
    # sixteen runs; the chip's 51 s window reads 0.99664: ledger, PR 30)
    assert 0.9 <= traced["plan_submits_per_eval.batch"] < 2.0
    assert traced["kernel_dispatches_per_eval.batch"] >= 0.9


def test_the_wal_and_the_snapshot_are_in_the_traced_line(traced):
    """ISSUE 31's four: Agent.counters() found the WAL's position, what
    it took since the last snapshot and the two triggers in the program,
    and the tap heard no snapshot and no whole walk in a 3 s toy. The
    WAL's bytes a placement are read, whatever their size: a leaner
    frame is no fault."""
    assert traced["wal_kb_per_placement.batch"] > 0.0
    assert 0.0 < traced["snapshot_due_share.batch"] < 100.0
    assert traced["snapshot_write_share.batch"] == 0.0
    assert traced["gc_whole_walks_per_eval.batch"] == 0.0
    assert traced["snapshots_in_window.batch"] == 0.0


def test_stage_count_per_eval_on_a_hand_made_window():
    from benchmark.readers import stage_count_per_eval as reader
    obs = {"seconds": 10.0, "evals_done": 4,
           "stages": [("plan_submit", 1.0, 0.2), ("plan_submit", 2.0, 0.2),
                      ("plan_submit", 9.99, 0.1), ("kernel", 3.0, 0.05),
                      ("plan_submit", -0.5, 0.3),      # the rehearsal's
                      ("plan_submit", 10.0, 0.3)]}     # after the close
    assert reader.read(obs, "plan_submit") == 0.75
    assert reader.read(obs, "kernel") == 0.25
    assert reader.read(obs, "table_build_private") == 0.0
    assert reader.read(dict(obs, stages=None), "kernel") is None
    assert reader.read({"seconds": 10.0}, "kernel") is None
    assert reader.read(dict(obs, evals_done=0), "kernel") is None
