"""Work counts from layer shapes, against counts made by hand."""
import json

import pytest
from conftest import BENCH

import work


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet50_conv1_macs_by_hand():
    conv1 = _config("resnet50")["layers"][0]
    # 64 filters x 3 channels x 112 x 112 positions x 7 x 7 taps
    assert work.macs(conv1) == 64 * 3 * 112 * 112 * 7 * 7 == 118_013_952


def test_resnet50_subset_macs_and_flops():
    layers = _config("resnet50")["layers"]
    conv1 = 112 * 112 * 64 * 3 * 49
    # a bottleneck (1x1 reduce of c_in channels, 3x3, 1x1 expand to 4w),
    # every layer at its output's P x P; an identity one reads 4w
    def first(p, w, c_in):
        return p * p * (c_in * w + 9 * w * w + w * 4 * w)

    def identity(p, w):
        return first(p, w, 4 * w)

    stages = [(56, 64, 64), (28, 128, 256), (14, 256, 512), (7, 512, 1024)]
    want = conv1 + sum(first(p, w, c) + identity(p, w) for p, w, c in stages)
    assert want == 1_749_336_064
    assert sum(work.macs(layer) for layer in layers) == want
    assert work.network_work(layers).flops == 2 * want


def test_mobilenet_v3_macs_by_hand():
    layers = _config("mobilenet_v3")["layers"]
    stem = 112 * 112 * 16 * 3 * 9
    b1 = 112 * 112 * 16 * (9 + 16)
    # a bneck: expand c->e at the input's size, k x k depthwise at P x P,
    # project e->o at P x P

    def bneck(p_in, p, c, e, o, k):
        return p_in * p_in * c * e + p * p * e * (k * k + o)

    want = (stem + b1 + bneck(112, 56, 16, 64, 24, 3)
            + bneck(56, 56, 24, 72, 24, 3) + bneck(56, 28, 24, 72, 40, 5)
            + 2 * bneck(28, 28, 40, 120, 40, 5))
    assert want == 71_619_968
    assert sum(work.macs(layer) for layer in layers) == want


def test_depthwise_layer_reads_no_channel_reduction():
    dw3 = next(layer for layer in _config("mobilenet_v3")["layers"]
               if layer["name"] == "mbv3-b4-dw")
    # 72 channels x 28 x 28 positions x 5 x 5 taps, one input channel each
    assert work.macs(dw3) == 72 * 28 * 28 * 25
    assert work.weight_params(dw3) == 5 * 5 * 72


def test_conv1_bytes_by_hand():
    w = work.layer_work(_config("resnet50")["layers"][0])
    # input image 224^2 x 3 (before SAME padding), output 112^2 x 64,
    # weights 7*7*3*64
    assert w.act_bytes == 4 * (224 * 224 * 3 + 112 * 112 * 64)
    assert w.weight_bytes == 4 * 7 * 7 * 3 * 64


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    compute_bound = work.Work(flops=1000.0, act_bytes=1.0, weight_bytes=0.0)
    assert compute_bound.least_seconds(2, 1, peak) == 20.0
    memory_bound = work.Work(flops=1.0, act_bytes=100.0, weight_bytes=50.0)
    # weights once per batch: 2 samples in 2 batches read them twice
    assert memory_bound.least_seconds(2, 2, peak) == (200 + 100) / 10


def test_peaks_table_knows_the_v5e_and_refuses_an_unknown_kind():
    peak = work.peak_for("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peak_for("source")
