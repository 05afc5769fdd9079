"""Chunked placement kernel == one-instance-per-step scan.

The chunked kernel (ops/select.py _select_chunked) exploits node-local
scoring to place whole chunks per step; these tests assert it is
*exactly* equivalent to the reference scan on placements and (within
float32 tolerance) on scores, across randomized fixtures covering
binpack/spread algorithms, penalties, affinities, pre-existing
collisions, dynamic-port budgets, partial feasibility, infeasible
tails, and the max-steps continuation path.
"""

import numpy as np
import pytest

import nomad_tpu.ops.select as sel


def _random_request(rng, n, count, algorithm):
    capacity = rng.uniform(500, 4000, size=(n, 4)).astype(np.float32)
    capacity[:, 2] *= 20
    capacity[:, 3] = 1000.0
    used = (capacity * rng.uniform(0, 0.5, size=(n, 4))).astype(np.float32)
    ask = np.array([rng.uniform(50, 400), rng.uniform(50, 400),
                    rng.uniform(1, 50), 0], np.float32)
    aff = (rng.uniform(-1, 1, n) * (rng.rand(n) > 0.5)).astype(np.float32)
    return sel.SelectRequest(
        ask=ask, count=count,
        feasible=rng.rand(n) > 0.2,
        capacity=capacity, used=used,
        desired_count=float(count),
        tg_collisions=rng.randint(0, 3, n).astype(np.int32),
        job_count=np.zeros(n, np.int32),
        penalty=rng.rand(n) > 0.8,
        affinity=aff, affinity_sum_weights=1.0,
        algorithm=algorithm,
        port_need=float(rng.randint(0, 3)),
        free_ports=rng.uniform(0, 20, n).astype(np.float32),
    )


def _scan_reference(req):
    n_pad = sel._pad_n(len(req.feasible))
    k = sel._bucket_k(max(req.count, 1))
    args, statics = sel.pack_request(req, n_pad)
    _carry, outs = sel._select_scan(**args, k_steps=k, **statics)
    return sel.unpack_result(req, outs)


@pytest.mark.parametrize("seed", range(6))
def test_chunked_matches_scan_randomized(seed):
    rng = np.random.RandomState(seed)
    n = rng.randint(5, 200)
    count = rng.randint(1, 60)
    algorithm = "spread" if seed % 3 == 0 else "binpack"
    req1 = _random_request(rng, n, count, algorithm)
    req2 = sel.SelectRequest(**{f.name: getattr(req1, f.name)
                                for f in req1.__dataclass_fields__.values()})
    chunked = sel.SelectKernel().select(req1)
    scan = _scan_reference(req2)
    assert np.array_equal(chunked.node_idx, scan.node_idx)
    assert chunked.placed == scan.placed
    assert np.allclose(chunked.final_score, scan.final_score,
                       rtol=1e-4, atol=1e-5)
    for name in chunked.scores:
        assert np.allclose(chunked.scores[name], scan.scores[name],
                           rtol=1e-4, atol=1e-5), name


def _assert_equivalent(kway, scan):
    """K-way equivalence to the scan: the greedy multiset of placements
    and the pointwise score trajectory. Near-ties (device and host f32
    differing by 1 ulp) may swap the order of two equal-score instances,
    which changes nothing the scheduler consumes — instances of a task
    group are fungible; a REAL chunking bug changes the multiset or the
    score trajectory and fails these assertions."""
    assert kway.placed == scan.placed
    import collections
    assert collections.Counter(kway.node_idx.tolist()) == \
        collections.Counter(scan.node_idx.tolist())
    assert np.allclose(kway.final_score, scan.final_score,
                       rtol=1e-4, atol=1e-5)
    # where the order differs, the swapped instances must carry
    # near-identical scores (the tie that allowed the swap)
    diff = kway.node_idx != scan.node_idx
    if diff.any():
        assert np.allclose(kway.final_score[diff], scan.final_score[diff],
                           rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", range(4))
def test_kway_matches_scan_randomized(seed):
    """The K-way phase kernel (count > 512 routing) must reproduce the
    scan's greedy placements across random tables."""
    rng = np.random.RandomState(100 + seed)
    n = rng.randint(20, 300)
    count = rng.randint(513, 1400)
    algorithm = "spread" if seed % 2 == 0 else "binpack"
    req1 = _random_request(rng, n, count, algorithm)
    req2 = sel.SelectRequest(**{f.name: getattr(req1, f.name)
                                for f in req1.__dataclass_fields__.values()})
    kway = sel.SelectKernel().select(req1)
    scan = _scan_reference(req2)
    _assert_equivalent(kway, scan)
    for name in kway.scores:
        assert np.allclose(kway.scores[name], scan.scores[name],
                           rtol=1e-4, atol=1e-5), name


def test_kway_matches_scan_identical_nodes_ties():
    """Worst case for tie rules: hundreds of IDENTICAL nodes, where
    every phase is a wall of equal scores and the lowest-index argmax
    rule decides everything."""
    n = 256
    count = 1000
    capacity = np.tile(np.array([[4000.0, 8192.0, 102400.0, 1000.0]],
                                np.float32), (n, 1))
    used = np.zeros((n, 4), np.float32)
    req = sel.SelectRequest(
        ask=np.array([100.0, 100.0, 10.0, 0.0], np.float32), count=count,
        feasible=np.ones(n, bool), capacity=capacity, used=used.copy(),
        desired_count=float(count),
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
    )
    req2 = sel.SelectRequest(**{f.name: getattr(req, f.name)
                                for f in req.__dataclass_fields__.values()})
    kway = sel.SelectKernel().select(req)
    scan = _scan_reference(req2)
    assert np.array_equal(kway.node_idx, scan.node_idx)
    assert kway.placed == scan.placed == count


def test_kway_adaptive_w_matches_scan_large_table():
    """Tables past 4096 padded rows route to a wider K-way phase
    (_kway_w) — the waterline/exactness argument is W-agnostic, and
    this pins it at the wide-W shape the C2M path uses."""
    rng = np.random.RandomState(7)
    n = 5000                      # n_pad 8192 -> w=128
    count = 700
    req1 = _random_request(rng, n, count, "binpack")
    assert sel._kway_w(sel._pad_n(n)) > sel.KWAY_W
    req2 = sel.SelectRequest(**{f.name: getattr(req1, f.name)
                                for f in req1.__dataclass_fields__.values()})
    kway = sel.SelectKernel().select(req1)
    scan = _scan_reference(req2)
    _assert_equivalent(kway, scan)


@pytest.mark.parametrize("seed", range(3))
def test_select_many_matches_individual(seed):
    """Multi-eval batching: one vmapped dispatch over B requests must
    equal B sequential select() calls exactly."""
    rng = np.random.RandomState(200 + seed)
    n = rng.randint(40, 200)
    base = _random_request(rng, n, 1, "binpack")
    reqs = []
    for b in range(5):      # pads to a bucket of 8 internally
        r = sel.SelectRequest(**{f.name: getattr(base, f.name)
                                 for f in base.__dataclass_fields__.values()})
        r.count = int(rng.randint(1, 900))
        r.used = base.used + rng.uniform(0, 50, base.used.shape
                                         ).astype(np.float32)
        r.ask = np.array([rng.uniform(50, 300), rng.uniform(50, 300),
                          1.0, 0.0], np.float32)
        r.desired_count = float(r.count)
        reqs.append(r)
    kernel = sel.SelectKernel()
    batched = kernel.select_many(reqs)
    for r, got in zip(reqs, batched):
        solo = kernel.select(sel.SelectRequest(
            **{f.name: getattr(r, f.name)
               for f in r.__dataclass_fields__.values()}))
        _assert_equivalent(got, solo)


def test_kway_infeasible_tail():
    """count > 512 routing with a saturating table: the tail fails with
    metrics, exactly like the 2-way path."""
    n = 64
    capacity = np.full((n, 4), 1000.0, np.float32)
    req = sel.SelectRequest(
        ask=np.array([600.0, 0.0, 0.0, 0.0], np.float32), count=600,
        feasible=np.ones(n, bool), capacity=capacity,
        used=np.zeros((n, 4), np.float32),
        desired_count=600.0,
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
    )
    res = sel.SelectKernel().select(req)
    assert res.placed == 64
    assert (res.node_idx[64:] == -1).all()
    assert res.exhausted_dim[64:].sum() > 0


def test_chunked_continuation_over_max_steps():
    """More distinct chunk steps than one dispatch allows: every node
    fits exactly one instance, so each step places chunk=1 and the
    kernel must continue across dispatches (max_steps=64 bucket)."""
    n = 100
    count = 90
    capacity = np.full((n, 4), 1000.0, np.float32)
    used = np.full((n, 4), 500.0, np.float32)
    # per-node headroom fits exactly one 400-cpu instance
    req = sel.SelectRequest(
        ask=np.array([400.0, 100.0, 0.0, 0.0], np.float32), count=count,
        feasible=np.ones(n, bool), capacity=capacity, used=used,
        desired_count=float(count),
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
    )
    res = sel.SelectKernel().select(req)
    assert res.placed == count
    # one instance per node -> all chosen nodes distinct
    assert len(set(res.node_idx.tolist())) == count


def test_chunked_infeasible_tail_metrics():
    n = 10
    capacity = np.full((n, 4), 1000.0, np.float32)
    req = sel.SelectRequest(
        ask=np.array([600.0, 0.0, 0.0, 0.0], np.float32), count=5,
        feasible=np.ones(n, bool), capacity=capacity,
        used=np.zeros((n, 4), np.float32),
        desired_count=5.0,
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
    )
    res = sel.SelectKernel().select(req)
    # each node fits exactly one 600-cpu instance; 5 <= 10 so all place
    assert res.placed == 5
    # now saturate: only 3 nodes feasible
    req2 = sel.SelectRequest(
        ask=np.array([600.0, 0.0, 0.0, 0.0], np.float32), count=5,
        feasible=np.arange(n) < 3, capacity=capacity,
        used=np.zeros((n, 4), np.float32),
        desired_count=5.0,
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
    )
    res2 = sel.SelectKernel().select(req2)
    assert res2.placed == 3
    assert (res2.node_idx[3:] == -1).all()
    # the failing instances carry exhaustion metrics from the last probe
    assert res2.exhausted_dim[3:].sum() > 0


def test_n_considered_metrics():
    n = 8
    req = sel.SelectRequest(
        ask=np.array([10.0, 10.0, 0.0, 0.0], np.float32), count=2,
        feasible=np.array([True, True, False, False] + [False] * 4),
        capacity=np.full((n, 4), 1000.0, np.float32),
        used=np.zeros((n, 4), np.float32),
        desired_count=2.0,
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32),
        n_considered=4,
    )
    res = sel.SelectKernel().select(req)
    assert res.nodes_evaluated == 4
    assert res.nodes_filtered == 2


def _heap_merge(fin_m, nodes_v, len_v, limit):
    """The K-way arm's order, plainly: pop the stream whose CURRENT
    head score is largest (ties to the lowest node index), advance that
    stream. The host ran this phase by phase until the program took the
    order on itself (_kway_sequence); kept as its oracle."""
    import heapq
    heap = []
    for k in range(len(nodes_v)):
        if len_v[k] > 0:
            heapq.heappush(heap, (-float(fin_m[k, 0]),
                                  int(nodes_v[k]), k, 0))
    ok, oj = [], []
    while heap and len(ok) < limit:
        _negs, node, k, j = heapq.heappop(heap)
        ok.append(k)
        oj.append(j)
        if j + 1 < len_v[k]:
            heapq.heappush(heap, (-float(fin_m[k, j + 1]), node,
                                  k, j + 1))
    return np.asarray(ok, np.int32), np.asarray(oj, np.int32)


@pytest.mark.parametrize("seed", range(8))
def test_running_minimum_order_is_the_heap_merge(seed):
    """_kway_sequence's lemma: the merge pops element j of stream k in
    the order of (-running minimum, node, j) — on non-monotonic streams
    of unequal lengths, with exact ties in and across streams, under a
    limit. The running minimum is the program's own (_segment_cummin
    over the streams laid end to end, as the program lays them)."""
    import jax
    cummin = jax.jit(sel._segment_cummin)
    rng = np.random.RandomState(300 + seed)
    for trial in range(40):
        w = rng.randint(1, 9)
        max_m = rng.randint(1, 13)
        # a handful of score values: ties abound
        fin = rng.randint(0, 4, size=(w, max_m)).astype(np.float32)
        if trial % 4 == 0:
            fin = rng.uniform(0, 1, size=(w, max_m)).astype(np.float32)
        nodes = rng.permutation(1000)[:w].astype(np.int32)
        lens = rng.randint(0, max_m + 1, size=w)
        limit = int(rng.randint(1, int(lens.sum()) + 2))
        want_k, want_j = _heap_merge(fin, nodes, lens, limit)

        k_flat = np.repeat(np.arange(w), lens)
        j_flat = np.concatenate([np.arange(m) for m in lens]
                                + [np.zeros(0, int)])
        if not len(k_flat):
            assert len(want_k) == 0
            continue
        # one shape, so one compile: what lies past the streams begins
        # a stream of its own each
        pad = 128 - len(k_flat)
        run_min = np.asarray(cummin(
            np.concatenate([j_flat == 0, np.ones(pad, bool)]),
            np.concatenate([fin[k_flat, j_flat],
                            np.zeros(pad, np.float32)])))[:len(k_flat)]
        order = np.lexsort((j_flat, nodes[k_flat], -run_min))[:limit]
        assert np.array_equal(k_flat[order], want_k), (seed, trial)
        assert np.array_equal(j_flat[order], want_j), (seed, trial)


class _StageTap:
    """Every stage report, kept, and passed on to the recorder."""

    def __init__(self):
        from nomad_tpu.utils import stages
        self.stages = stages
        self.reports = []               # (stage, seconds, attrs)
        self._prev, self._prev_on = stages._trace_hook, stages._trace_on
        stages.set_trace_hook(self._on, on=True)

    def _on(self, stage, seconds, attrs=None):
        self.reports.append((stage, seconds, attrs))
        if self._prev is not None and self._prev_on:
            self._prev(stage, seconds, attrs)

    def close(self):
        self.stages.set_trace_hook(self._prev, on=self._prev_on)

    def attrs_of(self, stage):
        return [r[2] for r in self.reports if r[0] == stage]


@pytest.fixture
def stage_tap():
    tap = _StageTap()
    try:
        yield tap
    finally:
        tap.close()


def _batch_cell_request(rng, n=5000, count=1000):
    """One worker's share of `prod-10k_batch-fill`: the mock node in
    three classes 1x/2x/4x at 60/30/10%, 40 resident allocs of cpu 50 /
    64 MB on each, an ask of cpu 20 / 32 MB with job anti-affinity."""
    scale = rng.choice([1.0, 2.0, 4.0], size=n, p=[0.6, 0.3, 0.1])
    capacity = (scale[:, None] * np.array(
        [[3900.0, 7936.0, 98304.0, 1000.0]])).astype(np.float32)
    used = np.tile(np.array([[2000.0, 2560.0, 0.0, 0.0]], np.float32),
                   (n, 1))
    return sel.SelectRequest(
        ask=np.array([20.0, 32.0, 0.0, 0.0], np.float32), count=count,
        feasible=np.ones(n, bool), capacity=capacity, used=used,
        desired_count=float(count),
        tg_collisions=np.zeros(n, np.int32),
        job_count=np.zeros(n, np.int32))


def _copy(req):
    return sel.SelectRequest(**{f.name: getattr(req, f.name)
                                for f in req.__dataclass_fields__.values()})


def test_kway_batch_cell_shape_matches_scan(stage_tap):
    """The batch cell's eval: anti-affinity holds every winner's chunk
    to 1, so seven phases of 128 place 896 and the overshoot rule walks
    the last 104 one phase each — the sequence is the scan's all the
    same, and the kernel_expand span says how it came about."""
    req = _batch_cell_request(np.random.RandomState(5))
    kway = sel.SelectKernel().select(_copy(req))
    scan = _scan_reference(_copy(req))
    _assert_equivalent(kway, scan)
    assert np.array_equal(kway.top_idx, scan.top_idx)
    assert np.array_equal(kway.exhausted_dim, scan.exhausted_dim)
    assert stage_tap.attrs_of("kernel_expand")[0] == {
        "phases": 111, "tail_phases": 104, "placed": 1000}


@pytest.mark.parametrize("algorithm", ["binpack", "spread"])
def test_kway_continuation_over_max_steps(monkeypatch, stage_tap,
                                          algorithm):
    """A dispatch that runs out of its phase budget continues from the
    device-resident carry; the rounds' sequences join in order and a
    node's later streams start where its earlier ones ended."""
    monkeypatch.setattr(sel, "_kway_steps", lambda w: 4)
    rng = np.random.RandomState(17)
    req1 = _random_request(rng, 150, 700, algorithm)
    req1.port_need = 0.0
    kway = sel.SelectKernel().select(_copy(req1))
    scan = _scan_reference(_copy(req1))
    _assert_equivalent(kway, scan)
    for name in kway.scores:
        assert np.allclose(kway.scores[name], scan.scores[name],
                           rtol=1e-4, atol=1e-5), name
    got = stage_tap.attrs_of("kernel_expand")[0]
    assert got["phases"] > 4 and got["placed"] == kway.placed


@pytest.mark.parametrize("seed", range(3))
def test_kway_batched_lanes_match_solo(seed):
    """Lanes of one vmapped K-way dispatch, of unequal counts under one
    sequence bucket, each equal to the lane dispatched alone."""
    rng = np.random.RandomState(400 + seed)
    n = rng.randint(60, 250)
    base = _random_request(rng, n, 1, "spread" if seed == 1 else "binpack")
    reqs = []
    for count in (rng.randint(513, 1300), rng.randint(300, 513),
                  rng.randint(1, 40)):
        r = _copy(base)
        r.count = int(count)
        r.desired_count = float(count)
        r.used = base.used + rng.uniform(0, 50, base.used.shape
                                         ).astype(np.float32)
        reqs.append(r)
    before = sel.device_stats_snapshot()["dispatches"].get(
        "kway_batched", 0)
    kernel = sel.SelectKernel()
    batched = kernel.select_many([_copy(r) for r in reqs])
    assert sel.device_stats_snapshot()["dispatches"]["kway_batched"] \
        == before + 1
    for r, got in zip(reqs, batched):
        solo = kernel.select(_copy(r))
        _assert_equivalent(got, solo)
        assert np.array_equal(got.top_idx, solo.top_idx)
        assert np.array_equal(got.exhausted_dim, solo.exhausted_dim)


def test_kway_host_half_walks_no_phases(stage_tap):
    """What is left of kernel_expand on the K-way arm is a slice, a
    clamp and the result's guard: the calls it makes (Python and C
    alike, counted by the profile hook) are as many for 111 phases as
    for 6, and few."""
    import sys
    kernel = sel.SelectKernel()

    def fetched(req):
        n_pad = sel._pad_n(len(req.feasible))
        cargs, spread_alg, w = kernel._pack_kway(req, n_pad, None)
        pending = sel._select_kway(
            **cargs, max_steps=sel._kway_steps(w), spread_alg=spread_alg,
            w=w, k_out=sel._bucket_k(req.count))
        return kernel._finish_kway_rounds(req, cargs, spread_alg,
                                          pending, w=w)

    def calls_of(req, rounds):
        calls = [0]

        def hook(_frame, event, _arg):
            if event in ("call", "c_call"):
                calls[0] += 1
        sys.setprofile(hook)
        try:
            res = sel._expand_kway(req, rounds)
        finally:
            sys.setprofile(None)
        return res, calls[0]

    rng = np.random.RandomState(5)
    long_req = _batch_cell_request(rng)
    # the job on every node already and its anti-affinity next to
    # nothing: a node's score rises as it fills, so chunks are long
    # and the phases a handful
    short_req = _batch_cell_request(rng)
    short_req.desired_count = 1e9
    short_req.tg_collisions = np.ones(len(short_req.feasible), np.int32)
    long_rounds, short_rounds = fetched(long_req), fetched(short_req)
    assert len(long_rounds) == len(short_rounds) == 1
    phases = [int(r[0][0][-1, sel.KWAY_PHASES])
              for r in (long_rounds, short_rounds)]
    assert phases[0] >= 100 and phases[1] <= 20, phases
    sel._expand_kway(short_req, short_rounds)   # first-use imports
    (long_res, long_calls), (short_res, short_calls) = \
        calls_of(long_req, long_rounds), calls_of(short_req, short_rounds)
    assert long_res.placed == short_res.placed == 1000
    assert long_calls == short_calls, (long_calls, short_calls)
    assert long_calls <= 60, long_calls
