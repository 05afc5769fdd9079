"""The byte-count function against hand-worked shapes, and the peaks."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import kernelcost as kc  # noqa: E402


@pytest.mark.parametrize("n,want", [(1, 8), (8, 8), (9, 16), (10000, 16384),
                                    (50000, 65536), (65536, 65536)])
def test_pad_n_is_the_kernels_power_of_two(n, want):
    assert kc.pad_n(n) == want


def test_batch_eval_at_10k_nodes_by_hand():
    # capacity + used: 2 x 16384 x 4 x 4 B; mask 16384 B; two collision
    # columns 2 x 16384 x 4 B; ask + count 20 B; 1000 x (row + score)
    want = 2 * 16384 * 16 + 16384 + 2 * 16384 * 4 + 20 + 1000 * 8
    assert kc.select_floor_bytes(10000, 4, 1000) == want == 679_764


def test_service_eval_at_10k_nodes_by_hand():
    # the batch columns, plus affinity f32, one spread's value ids i32
    # and the free-port column i32, each 16384 x 4 B; 10 placements
    want = 2 * 16384 * 16 + 16384 + 2 * 16384 * 4 + 3 * 16384 * 4 \
        + 20 + 10 * 8
    assert kc.select_floor_bytes(10000, 4, 10, spreads=1, affinities=1,
                                 ports=2) == want == 868_452


def test_bytes_follow_the_pad_not_the_fleet():
    assert kc.select_floor_bytes(8193, 4, 1) == \
        kc.select_floor_bytes(16384, 4, 1)
    assert kc.select_floor_bytes(50000, 4, 1000) > \
        3.9 * kc.select_floor_bytes(10000, 4, 1000)


def test_roofline_share_by_hand():
    # 819 MB at 819 GB/s is 1 ms; kernels that took 100 ms sit at 1%
    assert kc.roofline_share_pct(819e6, 0.1, "TPU v5 lite") == \
        pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        kc.peaks("TPU v99")


def test_peaks_carry_their_source():
    p = kc.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and "v5e" in p["source"]
