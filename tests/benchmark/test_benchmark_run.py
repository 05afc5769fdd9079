"""run.py end to end, in processes of its own: no accelerator means no
result, and a toy CPU rehearsal of every cell (counts only — nothing a
CPU run times is a device number)."""
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchrun_helper import CELLS, ROOT, RUN, STREAM, env, rehearse  # noqa: E402


def test_no_accelerator_no_result():
    p = subprocess.run([sys.executable, RUN, "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode not in (0, 2, 3)
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_unknown_cell_is_refused():
    p = subprocess.run([sys.executable, RUN, "--workload", "no-such-cell",
                        "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS + [STREAM])
def test_cpu_rehearsal_counts(cell):
    line, err = rehearse(cell, "--trace", "0")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert line["arms"] and all(n > 0 for n in line["arms"].values())
    # the compared numbers close standard error, each beside its limit
    tail = [ln for ln in err.strip().splitlines() if ln][-len(
        line["compared"]):]
    assert all(ln.startswith("compared ") and " limit " in ln for ln in tail)


def test_cpu_rehearsal_traced_reports_the_layers():
    line, err = rehearse(CELLS[0], "--trace", "1")
    assert line["correct"] is True, err[-3000:]
    got = set(line["metrics"])
    assert {"queue_wait_ms_p50.batch", "sched_host_ms_p50.batch",
            "plan_commit_ms_p50.batch", "gc_pause_share.batch",
            "compiles_in_window.batch", "register_ms_p50.batch"} <= got
    # no device plane on the CPU: the trace readers return nothing
    # rather than a 0 for a share of a roofline
    assert not {m for m in got if "roofline" in m or "device" in m}
    assert "busy_s" in line["device"] and "window_s" in line["device"]
