"""run.py with the timed path broken underneath, and with the plain
reference in the program's place: what has to come out as not correct."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchrun_helper import STREAM, rehearse  # noqa: E402

BATCH = "prod-10k_batch-fill"

HALF = """
from nomad_tpu.state.store import StateStore
_orig = StateStore._plan_results_root
def _half(self, root, index, *, allocs_placed, **kw):
    return _orig(self, root, index, allocs_placed=allocs_placed[::2], **kw)
StateStore._plan_results_root = _half
"""

# every select arm's answer (K-way, chunked, scan) altered where the
# kernel's wrapper hands it to the scheduler: row r becomes row r + 7
SHIFTED = """
import numpy as np
import nomad_tpu.ops.select as sel
_orig = sel.SelectKernel._select
def _shifted(self, req):
    res = _orig(self, req)
    n = len(req.feasible)
    res.node_idx = np.where(res.node_idx >= 0, (res.node_idx + 7) % n,
                            res.node_idx)
    return res
sel.SelectKernel._select = _shifted
"""

ONE_NODE = """
import dataclasses
from nomad_tpu.state.store import StateStore
_orig = StateStore._plan_results_root
_first = []
def _one(self, root, index, *, allocs_placed, **kw):
    if allocs_placed and not _first:
        _first.append(allocs_placed[0].node_id)
    moved = [dataclasses.replace(a, node_id=_first[0]) for a in allocs_placed]
    return _orig(self, root, index, allocs_placed=moved, **kw)
StateStore._plan_results_root = _one
"""


# 6,000 nodes make the toy's jobs 600 instances: over 512 the program
# takes the K-way arm, and over 256 each scheduler ranks its own share
@pytest.mark.parametrize("cell,patch,number,nodes", [
    (STREAM, HALF, "lost_or_duplicated", "640"),
    (STREAM, SHIFTED, "infeasible", "640"),
    (BATCH, HALF, "lost_or_duplicated", "640"),
    (BATCH, SHIFTED, "rank_gap", "640"),
    (BATCH, SHIFTED, "rank_gap", "6000"),
    (BATCH, ONE_NODE, "stacked", "640"),
], ids=["stream-half-left-out", "stream-answer-altered",
        "batch-half-left-out", "batch-chunked-rows-shifted",
        "batch-kway-rows-shifted", "batch-plan-on-one-node"])
def test_broken_timed_path_is_not_correct(cell, patch, number, nodes):
    line, err = rehearse(cell, "--trace", "0", patch=patch, seconds="2",
                         nodes=nodes)
    assert line["correct"] is False, err[-3000:]
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]
    if nodes == "6000":
        assert set(line["arms"]) == {"kway"}
        # a shifted row is in its share or not, never a near miss: the
        # share's bound reads it as far off as the whole fleet's did
        assert c["value"] >= 0.4


def test_sound_kway_run_ranks_as_the_reference():
    line, err = rehearse(BATCH, "--trace", "0", seconds="2", nodes="6000")
    assert line["correct"] is True, err[-3000:]
    assert set(line["arms"]) == {"kway"}
    assert line["compared"]["rank_gap"]["value"] <= 1e-5


@pytest.mark.parametrize("control,number", [
    ("capacity", "over_capacity"), ("lose", "lost_or_duplicated"),
    ("firstfit", "stacked"), ("norank", "rank_gap")])
def test_control_in_the_programs_place_is_not_correct(control, number):
    # 110 jobs: the toy's fullest nodes have room for 95 instances
    line, _err = rehearse(BATCH, "--control", control,
                          "--control-jobs", "110")
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", [BATCH, STREAM])
def test_reference_in_the_programs_place_is_correct(cell):
    line, _err = rehearse(cell, "--control", "none")
    assert line["correct"] is True
