"""The yardstick's operation and byte counts against hand-computed values
at the benchmark's own shapes."""

import json

import pytest

from yardstick import peaks, work
from conftest import BENCH


def test_coded_round_work_at_fig3_shapes():
    # A 18432x3584 in K=24 blocks of 768 rows, T=3 noise blocks, N=30:
    # encode 2*30*27*768*3584, products 2*30*768*3584*512; bytes: A, the
    # three noise blocks, B and the 30 shard products, float32 each
    w = work.coded_round_work(18432, 3584, 512, 30, 24, 3)
    assert w["flops"] == 4_459_069_440 + 84_557_168_640
    assert w["bytes"] == 351_797_248


def test_least_time_names_its_bound():
    w = work.coded_round_work(18432, 3584, 512, 30, 24, 3)
    t, bound = work.least_time_s(w, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(89_016_238_080 / 197e12)
    t, bound = work.least_time_s({"flops": 1.0, "bytes": 819e9},
                                 peaks.peaks_for("TPU v5 lite"))
    assert (t, bound) == (1.0, "memory")


def test_uncoded_product_flops():
    assert work.matmul_flops(18432, 3584, 512) == 67_645_734_912


def test_lm_flops_at_phi3_mini_4l():
    model = json.loads((BENCH / "configs" / "phi3-mini-4l.json")
                       .read_text())["model"]
    # one layer: q|k|v 3072*9216, o 3072*3072, gate|up|down 3*3072*8192;
    # scores and values 2*ctx*32*96; unembed 3072*32064; 2 per multiply-add
    assert work.lm_position_flops(model, 1) == 1_103_020_032
    assert work.lm_position_flops(model, 11) - work.lm_position_flops(
        model, 1) == 2 * 4 * 2 * 10 * 32 * 96
    assert work.lm_request_flops(model, 3, 2) == sum(
        work.lm_position_flops(model, c) for c in (1, 2, 3, 4))


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_steadiness_spreads():
    from steady import iqr_spread, trimmed_spread
    runs = [24.0, 24.5, 25.0, 30.0, 25.5, 24.5]
    # quartiles (exclusive method) 24.375 and 26.625, median 24.75
    assert iqr_spread(runs) == pytest.approx(2.25 / 24.75)
    # 30.0 is farthest from the median; the other five span 1.5
    assert trimmed_spread(runs) == pytest.approx(1.5 / 24.75)
    assert trimmed_spread([1.0, 3.0]) == pytest.approx(1.0)
