"""work.py's pass counts against hand-worked shapes, and the peaks."""
import pytest

import bench_helpers  # noqa: F401
from chipbench import work

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_dense_pass_counts():
    st = {"kind": "dense", "n": 4, "nnz": 16}
    # A: 16 * 4 B; lanes in and out: 2 * 2 lanes * 4 * 4 B
    assert work.pass_bytes(st, 2) == 64 + 64
    assert work.pass_flops(st, 2) == 2 * 16 * 2


def test_sparse_pass_counts_ignore_layout_padding():
    st = {"kind": "sparse", "n": 10, "nnz": 30}
    # 30 * (4 B value + 4 B index) + 10 * 4 B diagonal + 2 * 1 * 10 * 4 B
    assert work.pass_bytes(st, 1) == 240 + 40 + 80
    assert work.pass_flops(st, 1) == 60


def test_elevators_pass_is_hbm_bound():
    n = 16599
    st = {"kind": "dense", "n": n, "nnz": n * n}
    t, bound = work.least_time(st, 64, V5E)
    assert bound == "hbm"
    assert t == pytest.approx((n * n * 4 + 2 * 64 * n * 4) / 819e9)


def test_flop_bound_when_lanes_are_many():
    st = {"kind": "dense", "n": 8192, "nnz": 8192 ** 2}
    t, bound = work.least_time(st, 4096, V5E)
    assert bound == "flops"
    assert t == pytest.approx(2 * 8192 ** 2 * 4096 / 197e12)


def test_peaks_of_the_v5e_and_unknown_device_raises():
    p = work.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99 imaginary")
    with pytest.raises(ValueError):
        work.pass_bytes({"kind": "tensor", "n": 1, "nnz": 1}, 1)
