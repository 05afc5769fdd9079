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


def test_native_kway_merge_matches_python():
    """native/kway.cpp merge == the python heap merge on random
    non-monotonic streams (incl. score ties across streams)."""
    from nomad_tpu.native import load_kway
    from nomad_tpu.ops.select import _kway_merge_py

    import shutil
    if shutil.which("g++") is None:
        import pytest
        pytest.skip("no g++: the python heap merge")
    mod = load_kway()
    assert mod is not None, "g++ is here and load_kway() gave None"
    rng = np.random.RandomState(7)
    for trial in range(20):
        w = rng.randint(1, 33)
        max_m = rng.randint(1, 65)
        fin = rng.uniform(0, 1, size=(w, max_m)).astype(np.float32)
        # force ties sometimes
        if trial % 3 == 0:
            fin = np.round(fin * 4) / 4
        nodes = rng.permutation(1000)[:w].astype(np.int32)
        lens = rng.randint(0, max_m + 1, size=w).astype(np.int64)
        limit = int(rng.randint(1, int(lens.sum()) + 2))
        ok_py, oj_py = _kway_merge_py(fin, nodes, lens, limit)
        out = mod.merge(np.ascontiguousarray(fin).tobytes(),
                        nodes.tobytes(),
                        lens.astype(np.int32).tobytes(), max_m, limit)
        pairs = np.frombuffer(out, np.int32)
        p = len(pairs) // 2
        ok_c, oj_c = pairs[:p], pairs[p:]
        assert np.array_equal(ok_py, ok_c), (trial, ok_py, ok_c)
        assert np.array_equal(oj_py, oj_c), trial


def test_batch_scores_match_scalar():
    """_node_local_scores_batch is bit-identical to the per-winner
    _node_local_scores_np (the scan kernels' host-side score math)."""
    from nomad_tpu.ops.select import (_node_local_scores_batch,
                                      _node_local_scores_np)
    rng = np.random.RandomState(11)
    n = 64
    for trial in range(10):
        cap = np.tile(np.array([[4000.0, 8192.0, 102400.0, 1000.0]],
                               np.float32), (n, 1))
        req = sel.SelectRequest(
            ask=np.array([100.0, 150.0, 10.0, 0.0], np.float32),
            count=100,
            feasible=np.ones(n, bool), capacity=cap,
            used=(cap * rng.uniform(0, 0.5, (n, 4))).astype(np.float32),
            desired_count=float(rng.randint(1, 200)),
            tg_collisions=rng.randint(0, 3, n).astype(np.int32),
            job_count=np.zeros(n, np.int32),
            penalty=(rng.rand(n) < 0.3),
            algorithm="spread" if trial % 2 else "binpack")
        w = rng.randint(1, 9)
        cs = rng.permutation(n)[:w]
        starts = rng.randint(0, 5, w)
        ms = rng.randint(1, 12, w)
        fin_m, bin_m, anti_m, pen_v, aff_v, dev_v, pre_v = \
            _node_local_scores_batch(req, cs, starts, ms)
        for k in range(w):
            fin, binp, anti, pen, aff, dev, pre = _node_local_scores_np(
                req, int(cs[k]), int(starts[k]), int(ms[k]))
            m = ms[k]
            assert np.array_equal(fin_m[k, :m], fin), trial
            assert np.array_equal(bin_m[k, :m], binp)
            assert np.array_equal(anti_m[k, :m], anti)
            assert pen_v[k] == pen and aff_v[k] == aff
            assert dev_v[k] == dev and pre_v[k] == pre
