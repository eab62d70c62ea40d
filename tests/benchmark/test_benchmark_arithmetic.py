"""The yardstick's arithmetic, pinned: percentiles over every step, the
examples rate, the spread, FLOP and byte counts against hand-worked numbers,
the peaks table, the worst-leaf gap."""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import (  # noqa: E402
    check, costs, manifest, peaks, stats)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([5, 1, 4, 2, 3], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([7], 95, 7.0),
    ([1, 2], 100, 2.0),
    ([1, 2], 0, 1.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("q,want_ms", [(50, 100.0), (95, 100.0),
                                       (100, 400.0)])
def test_step_ms_is_over_every_step_of_the_window(q, want_ms):
    # 99 steps of 100 ms and one stall of 400 ms: every step is a sample,
    # so the stall stands at its full length and is not averaged into its
    # neighbours
    steps = [0.1] * 60 + [0.4] + [0.1] * 39
    assert stats.step_ms(steps, q) == pytest.approx(want_ms)


def test_step_ms_tail_sees_a_slow_twentieth():
    steps = [0.1] * 90 + [0.2] * 10
    assert stats.step_ms(steps, 50) == pytest.approx(100.0)
    assert stats.step_ms(steps, 95) == pytest.approx(200.0)


def test_examples_per_s_counts_distinct_examples_over_the_whole_window():
    assert stats.examples_per_s(200, 256, 32.0) == pytest.approx(1600.0)
    with pytest.raises(ValueError):
        stats.examples_per_s(0, 256, 1.0)


def test_spread_is_interquartile_over_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles (exclusive): q1 = 100.75, q3 = 104.25
    assert stats.spread(vals) == pytest.approx(3.5 / 102.5)


def _layers(name):
    return manifest.load_json(os.path.join(
        manifest.BENCH, "configs", name + ".json"))


def test_resnet18_counts_by_hand():
    c = _layers("resnet18-cifar10")
    # stem 3->64 at 32x32: 2*1024*27*64; 3x3 64->64 at 32x32: 2*1024*576*64
    assert costs.forward_flops([["conv", 32, 32, 3, 3, 64]]) == 3_538_944
    assert costs.forward_flops([["conv", 32, 32, 3, 64, 64]]) == 75_497_472
    assert costs.forward_flops(c["layers"]) == 1_110_845_440
    assert costs.train_flops_per_example(c["layers"]) == 3_332_536_320
    assert costs.param_count(c["layers"], c["conv_bias"],
                             c["norm_after_conv"]) == 11_173_962
    assert c["parameters"] == 11_173_962


def test_vgg11_counts_by_hand():
    c = _layers("vgg11-cifar10")
    assert costs.forward_flops([["dense", 512, 10]]) == 10_240
    assert costs.forward_flops(c["layers"]) == 306_587_648
    assert costs.param_count(c["layers"], c["conv_bias"],
                             c["norm_after_conv"]) == 9_750_922
    assert c["parameters"] == 9_750_922


def test_decode_bytes_by_hand():
    # n=8, d=11 173 962, f32: 2 stacks * 8 * d * 4 + d * 4
    assert costs.cyclic_decode_min_bytes(8, 11_173_962) == 759_829_416
    assert costs.cyclic_decode_min_bytes(8, 1000, "bf16") == 36_000
    with pytest.raises(ValueError):
        costs.forward_flops([["pool", 2]])


def test_peaks_known_and_unknown_device():
    p = peaks.peaks_of("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")


@pytest.mark.parametrize("prog,ref,want", [
    ([1.0, 2.0, 3.0], [1.0, 2.0, 3.0], 0.0),
    ([1.1, 2.0, 3.0], [1.0, 2.0, 3.0], 0.05),  # median 2.0 floors leaf 0
    ([1.0, 2.0, 3.3], [1.0, 2.0, 3.0], 0.1),
    ([0.0, 0.0, 0.0], [1.0, 2.0, 3.0], 1.0),  # a step that did nothing
    ([1.0, 2.0], [1.0, 2.0, 3.0], math.inf),
    ([1.0, math.nan, 3.0], [1.0, 2.0, 3.0], math.inf),
])
def test_leaf_gap(prog, ref, want):
    assert check.leaf_gap(prog, ref) == pytest.approx(want)


def test_noise_units():
    assert check.noise_units(0.1, 0.2) == pytest.approx(0.5)
    assert check.noise_units(3e-7, 0.0) == pytest.approx(3e-4)  # the floor


def test_unlocated_steps():
    ok = {"det_adv": 1, "det_tp": 1, "located_errors": 1}
    rows = [ok, dict(ok, det_tp=0), dict(ok, located_errors=2), {}]
    assert check.unlocated_steps(rows, 1) == 3
    assert check.unlocated_steps(rows, 0) == 0
