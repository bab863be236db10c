import math

import pytest

import work


@pytest.mark.parametrize("n", [256, 1024])
def test_c2c_work(n):
    got = work.transform_work("c2c", (n, n))
    assert got["flops"] == 5 * n * n * math.log2(n * n)
    assert got["bytes"] == 16 * n * n          # two fp32 planes in, two out


@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("kind", ["r2c", "c2r"])
def test_real_work_is_half_the_operations(n, kind):
    got = work.transform_work(kind, (n, n))
    assert got["flops"] == 2.5 * n * n * math.log2(n * n)
    assert got["bytes"] == 4 * n * n + 8 * n * (n // 2 + 1)


def test_batch_scales_work():
    one = work.transform_work("c2c", (1024, 1024))
    many = work.transform_work("c2c", (1024, 1024), batch=32)
    assert many == {"flops": 32 * one["flops"], "bytes": 32 * one["bytes"]}


def test_unknown_kind_and_device_kind_raise():
    with pytest.raises(ValueError):
        work.transform_work("r2r", (8, 8))
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_v5e_peaks_and_source():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_roofline_share_never_exceeds_one(n, kind):
    peak = work.peaks("TPU v5 lite")
    w = work.transform_work(kind, (n, n), batch=8)
    t_min = max(w["flops"] / peak["flops_per_s"],
                w["bytes"] / peak["bytes_per_s"])
    share, bound = work.roofline(w, t_min, peak)
    assert share == pytest.approx(1.0) and bound == "memory"
    for slower in (1.5, 10.0, 1e3):
        assert work.roofline(w, slower * t_min, peak)[0] < 1.0
    assert work.roofline(w, 0.0, peak) is None


def test_compute_bound_is_named():
    peak = {"flops_per_s": 1.0, "bytes_per_s": 1e9}
    share, bound = work.roofline({"flops": 2.0, "bytes": 1.0}, 4.0, peak)
    assert (share, bound) == (0.5, "compute")
