"""The readers of a share of the chip's peak count the cell's chips, and
the conv system counts the same work as ``work.py``.

On a hand-made ``Traced``, ``mfu`` and ``roofline_share`` at four chips
read a quarter of what they read at one, and at one chip they equal the
formulas they had before they counted chips.
"""
import dataclasses
import json

import pytest
from conftest import BENCH, REPO

import run
import trace_reduce
import work

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one compute-bound and one bandwidth-bound request, so the roofline's
# least time takes each side of its max
WORKS = {"compute": work.Work(flops=3.5e9, act_bytes=6.0e6,
                              weight_bytes=1.0e8),
         "bandwidth": work.Work(flops=1.0e6, act_bytes=2.0e7,
                                weight_bytes=3.1e10)}


def _traced(w, chips):
    trace = trace_reduce.TraceSummary(
        window_s=15.0, busy_s=11.5, devices=chips, op_s={}, mosaic_s=2.0,
        idle_by_host={})
    return run.Traced(hist={}, counters={}, spans=[], trace=trace,
                      window_s=14.9, served=1937, work=w, max_batch=16,
                      peak=dict(PEAK), chips=chips)


@pytest.mark.parametrize("bound", sorted(WORKS))
@pytest.mark.parametrize("metric", ["mfu.offline", "roofline_share.offline"])
def test_four_chips_read_a_quarter_of_one(metric, bound):
    read = run.load_reader(REPO / "bench", metric)
    one = read(_traced(WORKS[bound], 1))
    four = read(_traced(WORKS[bound], 4))
    assert one > 0
    assert four == pytest.approx(one / 4, rel=1e-12)


@pytest.mark.parametrize("bound", sorted(WORKS))
def test_mfu_at_one_chip_is_the_one_chip_formula(bound):
    t = _traced(WORKS[bound], 1)
    want = 100.0 * t.served * t.work.flops / (t.window_s * PEAK["flops_per_s"])
    assert run.load_reader(REPO / "bench", "mfu.offline")(t) == want


@pytest.mark.parametrize("bound", sorted(WORKS))
def test_roofline_share_at_one_chip_is_the_one_chip_formula(bound):
    t = _traced(WORKS[bound], 1)
    least = t.work.least_seconds(
        t.served, work.batches_for(t.served, t.max_batch), PEAK)
    want = 100.0 * least / t.trace.busy_s
    assert run.load_reader(REPO / "bench", "roofline_share.offline")(t) \
        == want


@pytest.mark.parametrize("metric", ["mfu.offline", "roofline_share.offline"])
def test_readers_read_nothing_off_the_chip(metric):
    read = run.load_reader(REPO / "bench", metric)
    t = dataclasses.replace(_traced(WORKS["compute"], 4), peak=None)
    assert read(t) is None


@pytest.mark.parametrize("name", ["resnet50", "mobilenet_v3"])
def test_conv_system_work_is_the_networks_work(name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    system = run.load_system(BENCH, "conv_graph")
    assert system.work(config) == work.network_work(config["layers"])
