"""GC-safepoint regime: collector state, gen-2 budget, freeze.

VERDICT r4 item 7: the young-gen-only safepoint policy deferred full
collections indefinitely, so nothing bounded cyclic garbage over a long
run. The regime now runs a FULL collection on a time budget at
safepoints, and the steady-state substrate can be frozen out of every
pass (utils/gcsafe.py)."""

import gc
import time
import weakref

import pytest

from nomad_tpu.utils import gcsafe


class _Cyclic:
    def __init__(self):
        self.me = self


def test_enter_exit_restores_collector_state():
    was = gc.isenabled()
    gcsafe.enter()
    try:
        assert not gc.isenabled()
        gcsafe.enter()          # nested participant
        gcsafe.exit_()
        assert not gc.isenabled(), "still one participant registered"
    finally:
        gcsafe.exit_()
    assert gc.isenabled() == was


def test_full_collect_budget_reclaims_cycles(monkeypatch):
    """Cyclic garbage created under the regime is reclaimed once the
    gen-2 budget elapses — the unbounded-growth failure mode of the
    young-gen-only policy."""
    monkeypatch.setattr(gcsafe, "FULL_COLLECT_INTERVAL_S", 0.0)
    monkeypatch.setattr(gcsafe, "MIN_COLLECT_INTERVAL_S", 0.0)
    with gcsafe.safepoints():
        # age a cycle into gen-2 (two young collects promote it), then
        # orphan it; with only young-gen collects it would never die
        c = _Cyclic()
        ref = weakref.ref(c)
        gc.collect()
        gc.collect()
        del c
        gcsafe._last_collect = 0.0
        gcsafe._last_full_collect = 0.0
        gcsafe.safepoint()
        assert ref() is None, "gen-2 cycle survived the full-collect budget"


def test_soak_heap_stays_bounded(monkeypatch):
    """Mini-soak: churn cyclic garbage through repeated safepoints for
    a couple of seconds; tracked-object count must stay flat instead of
    growing with iterations."""
    monkeypatch.setattr(gcsafe, "FULL_COLLECT_INTERVAL_S", 0.2)
    monkeypatch.setattr(gcsafe, "MIN_COLLECT_INTERVAL_S", 0.0)
    with gcsafe.safepoints():
        gc.collect()
        baseline = len(gc.get_objects())
        deadline = time.time() + 2.0
        i = 0
        while time.time() < deadline:
            junk = [_Cyclic() for _ in range(200)]
            for j in junk:
                j.friend = junk      # bigger cycle through the list
            del junk
            gcsafe._last_collect = 0.0
            gcsafe.safepoint()
            i += 1
        gcsafe._last_collect = 0.0
        gcsafe._last_full_collect = 0.0
        gcsafe.safepoint()
        grown = len(gc.get_objects()) - baseline
    if i <= 10:
        # the loop is wall-clock-bound (2 s): on a loaded shared box
        # the iterations collapse and the flatness verdict means
        # nothing — skip instead of failing on scheduler starvation
        # (the CHANGES.md r17 box flake)
        pytest.skip(f"box under load: soak loop ran only {i} "
                    f"iterations in its 2 s window")
    assert grown < 5000, f"tracked objects grew by {grown} over the soak"


def test_freeze_and_unfreeze_steady_state():
    substrate = [_Cyclic() for _ in range(100)]
    before = gc.get_freeze_count()
    gcsafe.freeze_steady_state()
    try:
        assert gc.get_freeze_count() > before
        # frozen objects are excluded from collection: a full collect
        # right after freezing is near-instant even with the substrate
        t0 = time.perf_counter()
        gc.collect()
        assert time.perf_counter() - t0 < 1.0
    finally:
        gcsafe.unfreeze_steady_state()
    assert gc.get_freeze_count() == 0
    assert substrate[0].me is substrate[0]


# -- generations over the resident state (ISSUE 28) --------------------
# what survives a full pass is frozen, so the next one walks what was
# allocated since; a whole walk (unfreeze first) comes round once the
# permanent generation has grown by WHOLE_WALK_GROWTH

@pytest.fixture
def regime(monkeypatch):
    """Inside the regime with every safepoint due, and the stage
    reports of the collector as (stage, attrs)."""
    from nomad_tpu.utils import stages
    monkeypatch.setattr(gcsafe, "MIN_COLLECT_INTERVAL_S", 0.0)
    reports = []
    prev, prev_on = stages._trace_hook, stages._trace_on
    stages.set_trace_hook(
        lambda stage, seconds, attrs=None:
        stage.startswith("gc_") and reports.append((stage, attrs)))
    assert gc.get_freeze_count() == 0 and gcsafe._participants == 0
    gcsafe.enter()
    try:
        yield reports
    finally:
        gcsafe.exit_()
        stages.set_trace_hook(prev, on=prev_on)


# objects a process makes and drops beside the test's own (a gc
# callback's, another thread's): counts agree to within this
SLACK = 500


def _full_pass():
    gcsafe._last_collect = 0.0
    gcsafe._last_full_collect = 0.0
    gcsafe.safepoint()


def _young_pass():
    gcsafe._last_collect = 0.0
    gcsafe.safepoint()


def _of(reports, stage):
    return [attrs for name, attrs in reports if name == stage]


def test_full_pass_freezes_its_survivors_and_the_next_walks_the_new(regime):
    base = [_Cyclic() for _ in range(50_000)]
    _full_pass()
    first, = _of(regime, "gc_full")
    assert first["walked"] >= 50_000
    frozen = gc.get_freeze_count()
    # summed as frozen, not read back: off by what died or was made
    # (a gc callback's own objects) between the count and the freeze
    assert frozen >= 50_000 and abs(first["frozen"] - frozen) < SLACK
    new = [_Cyclic() for _ in range(1_000)]
    _full_pass()
    _first, second = _of(regime, "gc_full")
    # of the order of what was allocated since, not of the base
    assert 1_000 <= second["walked"] < 5_000
    assert abs(second["frozen"] - first["frozen"] - second["walked"]) < SLACK
    assert abs(second["frozen"] - gc.get_freeze_count()) < SLACK
    assert len(_of(regime, "gc_whole_walk")) == 1    # the first: all of it
    assert base[0].me is base[0] and new[0].me is new[0]


def test_a_cycle_frozen_and_then_orphaned_waits_for_the_whole_walk(regime):
    _full_pass()                        # the regime's first: a whole walk
    c = _Cyclic()
    ref = weakref.ref(c)
    _full_pass()                        # c survives, and is frozen
    del c
    _full_pass()
    assert ref() is not None, "a full pass reached a frozen object"
    # as if the last whole walk had found a quarter of what is frozen
    # now: the permanent generation has more than doubled since
    gcsafe._whole_walk_base //= 4
    _full_pass()
    assert ref() is None, "the whole walk left a frozen cycle behind"
    whole = _of(regime, "gc_whole_walk")
    assert len(whole) == 2 and whole[1]["reclaimed"] >= 1
    assert whole[1]["walked"] >= gc.get_freeze_count() - SLACK
    assert gcsafe._whole_walk_base == gcsafe._frozen
    assert abs(gcsafe._frozen - gc.get_freeze_count()) < SLACK


@pytest.mark.parametrize("grown_by, whole", [(0.09, False), (0.11, True)])
def test_the_whole_walk_is_due_by_growth_and_not_before(
        regime, monkeypatch, grown_by, whole):
    # a tenth more, so that the test need not double its process's heap
    monkeypatch.setattr(gcsafe, "WHOLE_WALK_GROWTH", 1.1)
    _full_pass()
    base = gc.get_freeze_count()
    _full_pass()                # nothing since: not due
    _young_pass()               # a young collect never is a whole walk
    assert len(_of(regime, "gc_whole_walk")) == 1
    filler = [_Cyclic() for _ in range(int(base * grown_by))]
    _full_pass()                # the pass that would freeze the filler
    assert len(_of(regime, "gc_whole_walk")) == (2 if whole else 1)
    last = _of(regime, "gc_full")[-1]
    assert (last["walked"] >= base) == whole
    assert len(_of(regime, "gc_full")) == 3
    assert filler[0].me is filler[0]


def test_what_refcounts_freed_since_does_not_bring_the_whole_walk(regime):
    """The sum of what was frozen never shrinks; the permanent
    generation does, as frozen objects die by their reference counts.
    Churn that leaves no garbage behind doubles the sum and not the
    generation: the count is read back, and no whole walk is paid."""
    _full_pass()
    base = gc.get_freeze_count()
    for _ in range(12):
        batch = [[i] for i in range(base // 10)]    # no cycles
        _full_pass()            # frozen alive, freed by refcount after
        del batch
    assert len(_of(regime, "gc_whole_walk")) == 1
    assert gcsafe._frozen < 1.5 * base and gc.get_freeze_count() < 1.5 * base


def test_churn_with_freezing_live_stays_bounded(regime):
    """test_soak_heap_stays_bounded's shape with the leak freezing can
    make: every full pass freezes a batch that is alive and a cycle,
    and orphaned right after. Only whole walks reclaim those, so
    tracked + frozen stays under WHOLE_WALK_GROWTH times the live heap
    (plus the batches in flight) however long the churn."""
    _full_pass()
    live = gc.get_freeze_count()
    batch = live // 20
    peak, held = 0, None
    for _ in range(80):                         # 4x the live heap in all
        junk = [_Cyclic() for _ in range(batch)]
        for j in junk[:100]:
            j.friend = junk
        held = junk                             # alive across the pass
        del junk
        _full_pass()
        peak = max(peak, len(gc.get_objects()) + gc.get_freeze_count())
    # unreclaimed, 80 batches would stand at 5x
    assert len(_of(regime, "gc_whole_walk")) >= 3
    assert peak < (gcsafe.WHOLE_WALK_GROWTH + 0.5) * live, (peak, live)
    assert held is not None


def test_last_exit_leaves_nothing_frozen_and_nested_exits_do_not():
    assert gc.get_freeze_count() == 0
    was = gc.isenabled()
    keep = [_Cyclic() for _ in range(100)]
    gcsafe.enter()
    gcsafe.enter()                              # nested participant
    try:
        _full_pass()
        frozen = gc.get_freeze_count()
        assert frozen >= 100
    finally:
        gcsafe.exit_()
    try:
        assert abs(gc.get_freeze_count() - frozen) < SLACK
        assert not gc.isenabled()
    finally:
        gcsafe.exit_()
    assert gc.get_freeze_count() == 0 and gc.isenabled() == was
    assert gcsafe._frozen == 0 and gcsafe._whole_walk_base == 0
    assert keep[0].me is keep[0]


def test_bench_freeze_composes_with_the_regime(regime):
    """bench/soak.py and bench/ladder.py freeze by hand inside the
    regime and unfreeze at teardown: a second freeze is harmless, and
    after their unfreeze the next full pass walks everything again."""
    gcsafe.freeze_steady_state()
    frozen = gc.get_freeze_count()
    gcsafe.freeze_steady_state()
    assert abs(gc.get_freeze_count() - frozen) < SLACK
    _full_pass()                                # what came since, alone
    assert _of(regime, "gc_full")[-1]["walked"] < frozen // 2
    gcsafe.unfreeze_steady_state()
    assert gc.get_freeze_count() == 0
    _full_pass()
    last = _of(regime, "gc_full")[-1]
    assert last["walked"] >= frozen - SLACK
    assert len(_of(regime, "gc_whole_walk")) == 3


@pytest.mark.parametrize("stage", ["gc_full", "gc_whole_walk"])
def test_collector_stages_are_no_eval_stages(stage):
    from nomad_tpu.trace import AMBIENT_STAGES, STAGE_PARENTS
    from nomad_tpu.utils import stages
    assert stage in stages.STAGES
    assert STAGE_PARENTS[stage] is None and stage not in AMBIENT_STAGES


def test_span_map_is_the_tree_stages_py_draws():
    """trace.STAGE_PARENTS against the tree in utils/stages.py's
    docstring, stage by stage: a name's indentation is its depth."""
    import re
    from nomad_tpu.trace import STAGE_PARENTS
    from nomad_tpu.utils import stages
    tree = stages.__doc__.split("  (no eval)\n", 1)[1].split("\n\n", 1)[0]
    drawn, path = {}, {2: None}
    for line in tree.splitlines():
        m = re.match(r"^( {2,10})([a-z][a-z_0-9]*)(?: |$)", line)
        if m is None:
            continue                            # a description's line
        depth, name = len(m.group(1)), m.group(2)
        if depth == 2:
            assert name == "eval"               # the root of the rest
            path = {2: "eval"}
            continue
        drawn[name] = path[depth - 2]
        path[depth] = name
    assert drawn == STAGE_PARENTS
    assert set(drawn) == set(stages.STAGES)
