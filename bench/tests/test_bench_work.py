"""Kernel work functions and the table of peaks."""
import pytest

from lib import peaks, work


def test_nn_assign_work_of_a_build_wave():
    w = work.nn_assign(rows=256, centres=21, dim=8000)
    assert w["flops"] == 2 * 256 * 21 * 8000 + 2 * (256 + 21) * 8000
    assert w["bytes"] == 4 * (256 * 8000 + 21 * 8000 + 21) + 8 * 256


def test_nn_topk_work_of_one_query_row():
    w = work.nn_topk(rows=1, centres=21, dim=8000, k=4)
    assert w["flops"] == 2 * 21 * 8000 + 2 * 22 * 8000
    assert w["bytes"] == 4 * (8000 + 21 * 8000 + 21) + 8 * 4


def test_ell_spmm_work_counts_stored_slots_only():
    w = work.ell_spmm(rows=256, nnz=256, centres=21, dim=8000)
    assert w["flops"] == 2 * 256 * 256 * 21
    assert w["bytes"] == 8 * 256 * 256 + 4 * 21 * 8000 + 4 * 256 * 21


def test_v5e_peaks_and_bounds():
    p = peaks.peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_seconds(work.nn_assign(256, 21, 8000), p)
    assert bound == "hbm" and t == pytest.approx(work.nn_assign(256, 21, 8000)["bytes"] / 819e9)
    t, bound = work.roofline_seconds({"flops": 197e12, "bytes": 1.0}, p)
    assert bound == "compute" and t == pytest.approx(1.0)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)


def test_roofline_share_reads_the_trace():
    layer = {"peak": peaks.peaks("TPU v5 lite"),
             "kernel_work": {"nn_topk": {"rows": 1, "centres": 21, "dim": 8000, "k": 4}},
             "trace": {"kernel_calls": {"nn_topk": 10}, "kernel_s": {"nn_topk": 1e-3}}}
    least, _ = work.roofline_seconds(work.nn_topk(1, 21, 8000, 4), layer["peak"])
    assert work.roofline_share(layer, "nn_topk") == pytest.approx(100 * 10 * least / 1e-3)
    layer["trace"]["kernel_calls"]["nn_topk"] = 0
    assert work.roofline_share(layer, "nn_topk") is None
    assert work.roofline_share({"trace": None}, "nn_topk") is None
