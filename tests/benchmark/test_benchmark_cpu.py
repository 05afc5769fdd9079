"""ISSUE 36's thirteen metrics in a traced toy rehearsal: the CPU column
beside the walls (a span's thread CPU, the companion `<stage>_cpu`) and
what plan_commit waits for; and what the new reports do to the names of
the device's idle gaps (lib/tracered.name_gap draws every report the
tap heard as the interval that ends as it arrived, a companion as its
span ended): nothing, but that a gap may read `<stage>_cpu` where it
read `<stage>`. The toy runs XLA's
CPU backend, so its numbers say nothing about a chip: only what must
hold on any machine is held."""
import ast
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import tracered as tr                   # noqa: E402
from benchrun_helper import CELLS, MANIFEST, rehearse     # noqa: E402
from nomad_tpu.trace import STAGE_PARENTS                  # noqa: E402
from nomad_tpu.utils import stages                         # noqa: E402

CELL = "prod-10k_batch-fill"
# a span's CPU an eval, and the stage whose wall an eval it lies under
SPAN_CPU = {"plan_build_cpu_ms_per_eval": "plan_build",
            "select_prep_cpu_ms_per_eval": "select_prep",
            "kernel_cpu_ms_per_eval": "kernel",
            "kernel_pack_cpu_ms_per_eval": "kernel_pack",
            "table_build_cpu_ms_per_eval": "table_build",
            "plan_verify_cpu_ms_per_eval": "plan_verify",
            "plan_commit_cpu_ms_per_eval": "plan_commit",
            "fsm_apply_cpu_ms_per_eval": "fsm_apply",
            "job_register_cpu_ms_per_eval": "job_register"}
COMMIT_WAITS = {"raft_lock_wait_ms_per_eval": "raft_lock_wait",
                "wal_write_ms_per_eval": "wal_write",
                "fsm_apply_ms_per_eval": "fsm_apply",
                "event_publish_ms_per_eval": "event_publish"}
NEW = list(SPAN_CPU) + list(COMMIT_WAITS)
COMPANIONS = {s + stages.CPU_SUFFIX for s in stages.CPU_STAGES}


def _stage_of(name):
    """The stage a gap's name stands for: a companion's is its span's."""
    return name[:-len(stages.CPU_SUFFIX)] if name in COMPANIONS else name


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("slice")
    line, err = rehearse(CELL, "--trace", "1", "--out", str(out))
    assert line["correct"] and line["failed"] == 0
    # what the tap heard inside the window, by stage: (count, seconds)
    heard = re.search(r"stage reports that ended in the window "
                      r"\(count, seconds\): (\{.*\})", err)
    (name,) = [f for f in os.listdir(out) if f.startswith("trace_")]
    with open(os.path.join(out, name)) as f:
        piece = json.load(f)
    return {"metrics": {name: m["value"]
                        for name, m in line["metrics"].items()},
            "units": {name: m["unit"]
                      for name, m in line["metrics"].items()},
            "heard": ast.literal_eval(heard.group(1)),
            "gaps": line["breakdown"]["idle_gaps"], "slice": piece}


def test_there_are_thirteen_and_every_cell_reports_them():
    assert len(NEW) == len(set(NEW)) == 13
    assert set(SPAN_CPU.values()) == stages.CPU_STAGES
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    for metric in NEW:
        m = declared[metric + ".batch"]
        assert "workloads" not in m and m["moves"] == "placements_per_s"
        assert m["better"] == "lower" and m["source"] == "program_span"
    assert len(CELLS) == 3


@pytest.mark.parametrize("metric", NEW)
def test_cpu_metric_is_declared_filed_and_in_the_traced_line(traced, metric):
    name = metric + ".batch"
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name and spec["kind"] == "per_layer"
    for key in ("unit", "source", "layer", "moves"):
        assert spec[key] == declared[key]
    # read by a reader the benchmark had: this PR brings data alone
    assert spec["reader"] == "stage_per_eval"
    assert traced["metrics"][name] >= 0.0
    assert traced["units"][name] == declared["unit"]


def test_a_spans_cpu_is_no_more_than_its_wall(traced):
    """Per stage over the window, as the tap heard them: the companion
    came once a report and its seconds are no more than the wall's (the
    clocks' grain a report, at most)."""
    for metric, stage in SPAN_CPU.items():
        n, wall = traced["heard"][stage]
        n_cpu, cpu = traced["heard"][stage + "_cpu"]
        assert n_cpu == n > 0, stage
        assert cpu <= wall + 0.0005 * n, (stage, cpu, wall)
        # and where the wall has a metric an eval, the two agree
        twin = traced["metrics"].get(f"{stage}_ms_per_eval.batch")
        if twin is not None:
            assert traced["metrics"][metric + ".batch"] <= twin + 0.5
    # the scheduler's thread waits for the device through kernel: on
    # any machine part of its wall is off the core
    m = traced["metrics"]
    assert m["kernel_cpu_ms_per_eval.batch"] < m["kernel_ms_per_eval.batch"]


def test_what_plan_commit_waits_for_fits_inside_it(traced):
    heard = traced["heard"]
    n_commits, commit_s = heard["plan_commit"]
    kids = ["wal_encode"] + list(COMMIT_WAITS.values())
    # once a commit each (but for a commit the window's edge cuts: its
    # children end before it does)
    assert all(abs(heard[k][0] - n_commits) <= 1 for k in kids)
    assert sum(heard[k][1] for k in kids) \
        <= commit_s * (1.0 + 2.0 / n_commits)
    # a wait has no CPU clock
    assert "raft_lock_wait_cpu" not in heard
    assert heard["fsm_apply_cpu"][1] <= heard["plan_commit_cpu"][1]


# -- the idle gaps' names ------------------------------------------------

def test_the_tap_hears_intervals_and_companions_and_nothing_else(traced):
    """The agent's telemetry sampler ran through the window (1 s), and
    its CPU ledger is an amount over a sample: had it gone out on the
    hook the tap would have drawn it as an interval of two seconds and
    more ending at every sample, and name_gap would have read it into
    every gap it overlaps."""
    heard = set(traced["heard"])
    assert heard - set(stages.STAGES) == COMPANIONS
    assert not any(name.startswith("cpu_") for name in heard)
    assert "sched_host_cpu" not in heard
    # the line's ten longest gaps: a host stage of the tree, or the
    # companion of one, or nothing
    assert traced["gaps"]
    for name, seconds in traced["gaps"]:
        assert _stage_of(name) in set(STAGE_PARENTS) | {"idle"}, name
        assert seconds > 0.0


def test_a_companion_names_no_gap_its_own_stage_would_not(traced):
    """name_gap over two seconds of the tapped window, every report
    drawn as the harness draws it: with the companions left out each
    gap reads the same stage (a companion is the tail of its own span,
    stamped with the span's end however late it reached the tap, so it
    stands where its stage stood and covers no more of a gap)."""
    piece = traced["slice"]
    spans = [tuple(s) for s in piece["spans"]]
    assert {s for s, _a, _b in spans} & COMPANIONS
    plain = [s for s in spans if s[0] not in COMPANIONS]
    # XLA's CPU backend leaves no device events to be idle between:
    # gaps of 1, 7 and 40 ms laid over the slice, 600 in all
    gaps = [(piece["t0"] + i * 0.01, piece["t0"] + i * 0.01 + length)
            for length in (0.001, 0.007, 0.04) for i in range(200)]
    moved = []
    for gap in gaps:
        name = tr.name_gap(gap, spans)
        assert not name.startswith("cpu_")
        if _stage_of(name) != tr.name_gap(gap, plain):
            moved.append((gap, name, tr.name_gap(gap, plain)))
    assert not moved, moved[:5]
    # drawn so, a companion lies inside its span and ends with it
    by_end = {}
    for stage, a, b in plain:
        by_end.setdefault(stage, []).append((a, b))
    for stage, a, b in spans:
        if stage in COMPANIONS:
            mine = [(sa, sb) for sa, sb in by_end[_stage_of(stage)]
                    if sb <= b]
            sa, sb = max(mine, key=lambda iv: iv[1])
            assert b == sb and a >= sa - 0.0005, (stage, a, b, sa, sb)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "recorded_trace.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("on_core", [0.05, 0.5, 0.999])
def test_recorded_gaps_keep_their_names_under_companions(recorded,
                                                         on_core):
    """The chip's own gaps (recorded_trace.json), with a companion
    drawn after every span of a CPU stage as the tap would have drawn
    it - the last `on_core` of the span, ending 5 us after it."""
    spans = [tuple(s) for s in recorded["spans"]]
    with_cpu = list(spans)
    for stage, a, b in spans:
        if stage in stages.CPU_STAGES:
            with_cpu.append((stage + "_cpu",
                             b + 5e-6 - on_core * (b - a), b + 5e-6))
    with_cpu.sort(key=lambda s: s[2])       # the tap's order: by end
    assert len(with_cpu) > len(spans)
    gaps = tr.idle_gaps(recorded["events"], recorded["t0"], recorded["t1"])
    for gap in gaps:
        assert _stage_of(tr.name_gap(gap, with_cpu)) \
            == tr.name_gap(gap, spans), gap


def test_an_amount_over_a_sample_would_name_every_gap(recorded):
    """Why the sampler's CPU ledger stays off the hook: one report a
    second that carries two seconds of CPU, drawn as an interval, covers
    every gap more than any stage does."""
    spans = [tuple(s) for s in recorded["spans"]]
    t = recorded["t0"]
    ledger = []
    while t < recorded["t1"] + 1.0:
        ledger.append(("cpu_process", t - 2.0, t))
        t += 1.0
    gaps = tr.idle_gaps(recorded["events"], recorded["t0"], recorded["t1"])
    named = [tr.name_gap(g, spans + ledger) for g in gaps]
    assert named.count("cpu_process") > len(gaps) // 2
    assert "cpu_process" not in stages.STAGES
