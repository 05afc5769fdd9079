"""The toy preempting deployment through run.py, on the program as it
stands (PR 32): files that are all new and the tests' own — the
`toy-evict` mix and two tiered configurations under tests/benchmark/ —
go through `run.py --rehearse-cpu --nodes 640 --manifest <the tests'
own>`: the
scheduler configuration is PUT and read back, the tiers load and the
probe agrees, evictions are read back over HTTP and judged. The next
`model_config` PR repeats this at 10,000 nodes with data files alone.
Counts only: nothing a CPU run times is a device number."""
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchrun_helper import EVICT, EVICT_ROOM, rehearse  # noqa: E402

FOUR = ["evicted_wrongly", "evicted_needlessly", "evicted_with_room",
        "residents_stopped"]
ELEVEN = ["never_completed", "unplaced_evals", "lost_or_duplicated",
          "unread", "over_capacity", "infeasible", "port_conflicts",
          "spread_over_target", "stacked", "rank_gap", "harness_problems"]


def read_back(err):
    """(resident allocs read, evicted, placed since the load) from the
    harness's log line."""
    m = re.search(r"read back (\d+) resident allocs of (\d+) tier jobs in "
                  r"[\d.]+s: (\d+) evicted, (\d+) placed since", err)
    assert m, err[-3000:]
    return int(m.group(1)), int(m.group(3)), int(m.group(4))


@pytest.fixture(scope="module")
def program():
    return rehearse(EVICT, "--trace", "0")


def test_the_program_evicts_and_is_judged_correct(program):
    line, err = program
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["arms"]) and all(n > 0 for n in line["arms"].values())
    assert list(line["compared"]) == ELEVEN[:-1] + FOUR + ELEVEN[-1:]
    assert all(c["value"] == 0 and c["limit"] == 0
               for k, c in line["compared"].items() if k in FOUR)
    # each number compared closes standard error beside its limit
    tail = [ln for ln in err.strip().splitlines() if ln][-15:]
    assert [ln.split()[1] for ln in tail] == list(line["compared"])
    assert all(ln.startswith("compared ") and " limit " in ln for ln in tail)


def test_the_scheduler_configuration_is_put_and_read_back(program):
    _line, err = program
    m = re.search(r"scheduler configuration PUT and read back: (\{.*\})", err)
    assert m and "'service_scheduler_enabled': True" in m.group(1)
    assert "'batch_scheduler_enabled': False" in m.group(1)
    # before the fleet loads
    assert err.index("scheduler configuration PUT") < err.index("load_nodes")


def test_the_tiers_load_and_the_probe_agrees(program):
    _line, err = program
    m = re.search(r"backlog on node-\d+: \[(\d+), (\d+), (\d+)\] allocs by "
                  r"tier, as made", err)
    assert m, err[-3000:]
    low, mid, peer = map(int, m.groups())
    assert low // 12 == mid // 8 == peer // 5 in (1, 2, 4)


def test_evictions_are_read_back_and_the_rest_still_run(program):
    line, err = program
    read, evicted, fresh = read_back(err)
    assert evicted >= 1
    # 640 nodes hold 25,600 residents; what was read is those and the
    # replacements, under the loader's ids (a resident that is gone,
    # moved or stopped would count in residents_stopped)
    assert read == 25 * 1024 + fresh and fresh <= evicted
    assert line["compared"]["residents_stopped"]["value"] == 0
    assert re.search(r"broker idle after [\d.]+s", err)


@pytest.mark.parametrize("control,cell,number", [
    ("none", EVICT, None),
    ("none", EVICT_ROOM, None),
    ("noevict", EVICT, "over_capacity"),
    ("evictpeer", EVICT, "evicted_wrongly"),
    ("evictall", EVICT, "evicted_needlessly"),
    ("evictearly", EVICT_ROOM, "evicted_with_room"),
])
def test_controls_in_the_programs_place(control, cell, number):
    line, err = rehearse(cell, "--control", control)
    assert list(line["compared"]) == ELEVEN[:-1] + FOUR + ELEVEN[-1:]
    if number is None:
        assert line["correct"] is True, err[-3000:]
        return
    assert line["correct"] is False
    broke = [k for k, c in line["compared"].items()
             if c["value"] > c["limit"]]
    assert broke == [number], line["compared"]


# the timed path broken underneath: what the program commits as evicted
# is altered where it is produced
NOTHING_EVICTED = """
from nomad_tpu.state.store import StateStore
_orig = StateStore._plan_results_root
def _none(self, root, index, *, allocs_preempted, **kw):
    return _orig(self, root, index, allocs_preempted=[], **kw)
StateStore._plan_results_root = _none
"""

# the victims a preemption round hands the stack for a node, altered
# where they are produced: as many, but of the tiers within 10 of the
# job's priority ...
PEERS_INSTEAD = """
import nomad_tpu.scheduler.preemption as pre
_orig = pre.PreemptionRound.victims_for
def _peers(self, idx):
    victims = _orig(self, idx)
    if not victims:
        return victims
    peers = [a for a in self.snapshot.allocs_by_node(self.table.nodes[idx].id)
             if a.job is not None and not a.terminal_status()
             and 0 < self.job.priority - a.job.priority < pre.PRIORITY_DELTA]
    return peers[:len(victims)] or victims
pre.PreemptionRound.victims_for = _peers
"""

# ... and every eligible allocation of the node
ALL_OF_THEM = """
import nomad_tpu.scheduler.preemption as pre
_orig = pre.PreemptionRound.victims_for
def _all(self, idx):
    victims = _orig(self, idx)
    if not victims:
        return victims
    return [a for a in self.snapshot.allocs_by_node(self.table.nodes[idx].id)
            if a.job is not None and not a.terminal_status()
            and self.job.priority - a.job.priority >= pre.PRIORITY_DELTA]
pre.PreemptionRound.victims_for = _all
"""


@pytest.mark.parametrize("patch,number", [
    (NOTHING_EVICTED, "over_capacity"),
    (PEERS_INSTEAD, "evicted_wrongly"),
    (ALL_OF_THEM, "evicted_needlessly"),
], ids=["victims-left-running", "peers-evicted", "whole-node-evicted"])
def test_broken_preemption_underneath_is_not_correct(patch, number):
    line, err = rehearse(EVICT, "--trace", "0", patch=patch, seconds="2")
    assert line["correct"] is False, err[-3000:]
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]


# REVIEW 32: a broker that is still busy when the wait runs out is not
# judged on a store that still moves (the harness's own wait, told to
# answer "not calm"; the wait itself: test_benchmark_evict.py)
NEVER_CALM = """
from benchmark.lib import agent
agent.Agent.quiesce = lambda self, timeout_s: (False, 0.0)
"""


def test_a_broker_still_busy_at_the_read_back_is_a_harness_problem():
    line, err = rehearse(EVICT, "--trace", "0", patch=NEVER_CALM,
                         seconds="2")
    assert "broker STILL BUSY after" in err
    assert line["correct"] is False
    broke = [k for k, c in line["compared"].items()
             if c["value"] > c["limit"]]
    assert broke == ["harness_problems"], line["compared"]


def test_the_program_fills_room_before_it_evicts():
    """Upstream's selectNextOption tries preemption only after a select
    without it found no node: on a toy whose fleet still has room, no
    placement evicts."""
    line, err = rehearse(EVICT_ROOM, "--trace", "0")
    _read, evicted, _fresh = read_back(err)
    assert line["compared"]["evicted_with_room"]["value"] == 0, \
        (evicted, line["compared"])
    assert line["correct"] is True
