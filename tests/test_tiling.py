"""Tile + buffer-allocation axes through the artifact + execution layers.

Covers the plan schema v4 (per-tensor ``buffer_alloc``, ``fused_with``
edges and ``dram_stall_cycles`` on steps; v1/v2/v3 back-compat via the
checked-in fixtures), the tile-derived kernel block/grid shapes (halved
resident iAct extents for double-buffered steps, power-of-two clamping
with the Pallas sublane floor for small tiles), and the batch-norm/bias
fold through the executor's effective-weight hook point — all validated
against the ``kernels/ref.py``-based oracles.
"""
import dataclasses
import pathlib

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.dataflow import ConvWorkload, tile_extents
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.core.workloads import init_graph_weights
from repro.kernels import ref
from repro.plan import (ExecutionPlan, NetworkPlanner, PlanError,
                        PlannerOptions, execute_network,
                        execute_network_reference, fold_batchnorm,
                        from_layers, prepare_network, step_kernel_blocks)
from repro.plan.executor import MIN_KERNEL_BLOCK
from repro.plan.plan import PLAN_VERSION, RIR_BLOCK

FIXTURE_V1 = pathlib.Path(__file__).parent / "goldens" / "plan_v1_fixture.json"
FIXTURE_V2 = pathlib.Path(__file__).parent / "goldens" / "plan_v2_fixture.json"
FIXTURE_V3 = pathlib.Path(__file__).parent / "goldens" / "plan_v3_fixture.json"
SMALL_LAYOUTS = tuple(Layout.parse(s)
                      for s in ("HWC_C32", "HWC_H32", "HWC_C4W8"))
OPTS = dict(layouts=SMALL_LAYOUTS, parallel_dims=("C", "P", "Q"))


def tiled_plan(graph, **kw):
    opts = PlannerOptions(switch_modes=("rir",), **OPTS, **kw)
    assert opts.search_tiles
    return NetworkPlanner(graph, EvalConfig(), opts).plan()


# ----------------------------------------------------------- schema v2 compat
def test_v1_fixture_loads_and_roundtrips():
    """A checked-in pre-tile (version 1) artifact must load — steps get the
    default whole-tensor tiling, single-buffered — and round-trip
    losslessly."""
    text = FIXTURE_V1.read_text()
    plan = ExecutionPlan.from_json(text)
    assert plan.version == 1
    assert all(s.tiles == () for s in plan.steps)
    assert all(s.dataflow.tiles == () for s in plan.steps)
    assert all(not s.double_buffer for s in plan.steps)
    again = ExecutionPlan.from_json(plan.to_json())
    assert again == plan


def test_v2_fixture_loads_single_buffered():
    """A checked-in pre-pipeline (version 2) artifact must load with every
    step single-buffered — the PR 4 execution semantics — and round-trip
    losslessly."""
    plan = ExecutionPlan.from_json(FIXTURE_V2.read_text())
    assert plan.version == 2
    assert any(s.tiles for s in plan.steps)   # v2 artifacts DO carry tiles
    assert all(not s.double_buffer for s in plan.steps)
    assert all(not s.dataflow.double_buffer for s in plan.steps)
    again = ExecutionPlan.from_json(plan.to_json())
    assert again == plan


def test_v3_fixture_loads_unfused_uniform():
    """A checked-in pre-fusion (version 3) artifact must load with every
    step unfused and uniform-buffered — no ``fused_with`` edges, no
    per-tensor ``buffer_alloc``, zero modeled stall — and round-trip
    losslessly (as a v4 artifact)."""
    plan = ExecutionPlan.from_json(FIXTURE_V3.read_text())
    assert plan.version == 3
    assert all(s.fused_with is None for s in plan.steps)
    assert all(s.buffer_alloc == () for s in plan.steps)
    assert all(s.dataflow.buffer_alloc == () for s in plan.steps)
    assert all(s.dram_stall_cycles == 0.0 for s in plan.steps)
    assert any(s.double_buffer for s in plan.steps), \
        "fixture should carry a ping-pong step"
    again = ExecutionPlan.from_json(plan.to_json())
    assert again == plan


def test_v4_plan_carries_tiles_and_buffer_alloc_through_json():
    graph = from_layers([
        ConvWorkload(M=256, C=128, P=14, Q=14, R=3, S=3, name="big"),
        ConvWorkload(M=128, C=256, P=14, Q=14, R=1, S=1, name="pw"),
    ], "two")
    plan = tiled_plan(graph)
    assert plan.version == PLAN_VERSION == 4
    assert any(s.tiles for s in plan.steps), "no layer chose a tiling"
    assert any(s.double_buffer or s.buffer_alloc for s in plan.steps), \
        "no layer chose any ping-pong buffering"
    for s in plan.steps:
        assert s.tiles == s.dataflow.tiles
        assert s.double_buffer == s.dataflow.double_buffer
        assert s.buffer_alloc == s.dataflow.buffer_alloc
    loaded = ExecutionPlan.from_json(plan.to_json())
    assert loaded == plan
    assert [s.tiles for s in loaded.steps] == [s.tiles for s in plan.steps]
    assert [s.double_buffer for s in loaded.steps] == \
        [s.double_buffer for s in plan.steps]
    assert [s.buffer_alloc for s in loaded.steps] == \
        [s.buffer_alloc for s in plan.steps]
    assert [s.fused_with for s in loaded.steps] == \
        [s.fused_with for s in plan.steps]
    assert [s.dram_stall_cycles for s in loaded.steps] == \
        [s.dram_stall_cycles for s in plan.steps]


def test_v4_fused_plan_roundtrips_fused_edges():
    """A plan whose DP actually fuses an edge must serialize the edge and
    the per-step stall share and reload identically."""
    fused = tiled_plan(from_layers([
        ConvWorkload(M=32, C=16, P=8, Q=8, R=1, S=1, name="a"),
        ConvWorkload(M=16, C=32, P=8, Q=8, R=1, S=1, name="b"),
    ], "pair"))
    steps = fused.steps
    # force a fused edge if the tiny pair's DP did not pick one (cheap
    # nets can be DRAM-free already); serialization must carry it anyway
    if all(s.fused_with is None for s in steps):
        steps = (dataclasses.replace(steps[0], fused_with=1,
                                     dram_stall_cycles=12.5),) + steps[1:]
        fused = dataclasses.replace(fused, steps=steps)
    loaded = ExecutionPlan.from_json(fused.to_json())
    assert loaded == fused
    assert [s.fused_with for s in loaded.steps] == \
        [s.fused_with for s in steps]
    assert [s.dram_stall_cycles for s in loaded.steps] == \
        [s.dram_stall_cycles for s in steps]


def test_unknown_plan_version_rejected():
    text = FIXTURE_V1.read_text().replace('"version": 1', '"version": 99', 1)
    with pytest.raises(ValueError, match="99"):
        ExecutionPlan.from_json(text)


# ------------------------------------------------------- tile-derived blocks
def test_step_kernel_blocks_follow_the_tile():
    wl = ConvWorkload(M=256, C=256, P=14, Q=14, R=3, S=3, name="l")
    graph = from_layers([wl], "one")
    plan = tiled_plan(graph)
    step = plan.steps[0]
    bm, bk = step_kernel_blocks(step)
    assert 8 <= bm <= RIR_BLOCK       # 8 = Pallas f32 sublane floor
    # K = 256*3*3 spans many blocks, so the K block is lane-aligned
    assert bk == RIR_BLOCK
    # tile-less single-buffered steps keep the full hardcoded block (v1)
    untiled = dataclasses.replace(step, tiles=(), double_buffer=False,
                                  buffer_alloc=())
    assert step_kernel_blocks(untiled) == (RIR_BLOCK, RIR_BLOCK)
    wide = dataclasses.replace(step, tiles=(("C", 64),), double_buffer=False,
                               buffer_alloc=())
    assert step_kernel_blocks(wide) == (RIR_BLOCK, RIR_BLOCK)
    # ping-pong halves the resident iAct extents before the pow-2 clamp: a
    # tile that pins the full block single-buffered drops one power of two
    assert step_kernel_blocks(dataclasses.replace(
        wide, double_buffer=True)) == (MIN_KERNEL_BLOCK, RIR_BLOCK)
    # ... and a per-tensor allocation halves iff iActs are in the subset
    assert step_kernel_blocks(dataclasses.replace(
        wide, buffer_alloc=("iact",))) == (MIN_KERNEL_BLOCK, RIR_BLOCK)
    assert step_kernel_blocks(dataclasses.replace(
        wide, buffer_alloc=("w", "oact"))) == (RIR_BLOCK, RIR_BLOCK)
    pinned = dataclasses.replace(
        step, tiles=(("C", 32), ("P", 14), ("Q", 14)), double_buffer=False,
        buffer_alloc=())
    halved = dataclasses.replace(pinned, double_buffer=True)
    bm_sb, bk_sb = step_kernel_blocks(pinned)
    bm_db, bk_db = step_kernel_blocks(halved)
    assert bm_db <= bm_sb and bk_db <= bk_sb


def test_step_kernel_blocks_clamp_to_small_tiles():
    """Regression (small-tile clamping): blocks used to silently round UP
    to MIN_KERNEL_BLOCK even when the tile itself was smaller, so a tiny
    tile got a (64, 64) grid block over mostly-padding rows.  The row clamp
    follows the tile down to the Pallas f32 sublane floor of 8 and never
    exceeds the next power of two above the resident extent.  The K block
    is TPU-aligned: a multiple of 128 lanes, or the whole (padded) K."""
    wl = ConvWorkload(M=256, C=256, P=14, Q=14, R=3, S=3, name="l")
    graph = from_layers([wl], "one")
    step = tiled_plan(graph).steps[0]
    # rows = P*Q tile = 4, kdim = 8*3*3 = 72 of K = 2304: the row block
    # follows the tile to 8, the K block rises from 64 to one lane tile
    tiny = dataclasses.replace(
        step, tiles=(("M", 16), ("C", 8), ("P", 2), ("Q", 2)),
        double_buffer=False, buffer_alloc=())
    assert step_kernel_blocks(tiny) == (8, RIR_BLOCK)
    # a K that fits in the tile's block is taken whole: no lane rounding
    assert step_kernel_blocks(tiny, k=64) == (8, MIN_KERNEL_BLOCK)
    assert step_kernel_blocks(tiny, k=16) == (8, MIN_KERNEL_BLOCK)
    # blocks never exceed the next power of two above the resident extent,
    # and every K block is lane-aligned or covers K
    for tiles in ((("P", 2), ("Q", 2)), (("M", 8), ("C", 4)),
                  (("C", 8), ("P", 4), ("Q", 4))):
        s = dataclasses.replace(step, tiles=tiles, double_buffer=False,
                                buffer_alloc=())
        for k in (None, 36, 256):
            bm, bk = step_kernel_blocks(s, k=k)
            ext = tile_extents(wl, s.dataflow.with_tiles(tiles))
            rows = ext["N"] * ext["P"] * ext["Q"]
            kdim = ext["C"] * wl.R * wl.S
            k_full = wl.C * wl.R * wl.S if k is None else k
            assert bm <= max(8, 1 << (rows - 1).bit_length())
            assert bk <= max(RIR_BLOCK, 1 << (kdim - 1).bit_length())
            assert bk % RIR_BLOCK == 0 or bk >= k_full
            assert bm >= 8 and bk >= 8


def test_tiled_plan_executes_bit_identical_to_untiled():
    """The tile + double-buffer choice changes the kernel block/grid shape,
    never the math: a (possibly ping-pong) tiled and an untiled plan over
    the same boundary layouts must produce identical outputs."""
    graph = from_layers([
        ConvWorkload(M=256, C=128, P=16, Q=16, R=3, S=3, name="conv"),
        ConvWorkload(M=128, C=256, P=16, Q=16, R=1, S=1, name="pw"),
    ], "pair")
    plan_t = tiled_plan(graph)
    assert any(s.tiles for s in plan_t.steps)
    plan_u = dataclasses.replace(
        plan_t, steps=tuple(
            dataclasses.replace(
                s, tiles=(), double_buffer=False,
                dataflow=s.dataflow.with_tiles(()))
            for s in plan_t.steps))
    ws = init_graph_weights(list(graph.layers), seed=11)
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y_ref = np.asarray(execute_network_reference(graph, x, ws))
    for use_pallas in (True, False):
        y_t = np.asarray(execute_network(plan_t, graph, x, ws,
                                         use_pallas=use_pallas))
        y_u = np.asarray(execute_network(plan_u, graph, x, ws,
                                         use_pallas=use_pallas))
        np.testing.assert_allclose(y_t, y_u, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(y_t, y_ref, rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------ batch-norm fold
def bn_params(rng, M):
    return (jnp.asarray(rng.uniform(0.5, 1.5, M), jnp.float32),   # gamma
            jnp.asarray(rng.normal(size=M), jnp.float32),         # beta
            jnp.asarray(rng.normal(size=M), jnp.float32),         # mean
            jnp.asarray(rng.uniform(0.2, 2.0, M), jnp.float32))   # var


def test_fold_batchnorm_matches_ref_conv_bn_oracle():
    """Acceptance oracle: executor with folded (w, bias) == ref.conv2d
    followed by the textbook inference-BN expression."""
    wl = ConvWorkload(M=128, C=64, P=14, Q=14, R=3, S=3, name="conv-bn")
    graph = from_layers([wl], "one")
    plan = tiled_plan(graph)
    rng = np.random.default_rng(21)
    (w,) = init_graph_weights([wl], seed=21)
    gamma, beta, mean, var = bn_params(rng, wl.M)
    conv_bias = jnp.asarray(rng.normal(size=wl.M), jnp.float32)
    eps = 1e-5
    w_fold, b_fold = fold_batchnorm(w, gamma, beta, mean, var, eps=eps,
                                    conv_bias=conv_bias)

    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y = np.asarray(execute_network(plan, graph, x, [w_fold],
                                   biases=[b_fold]))
    # the oracle: plain conv + bias, then BN with running stats
    raw = ref.conv2d(x, jnp.asarray(w), wl.stride) + conv_bias
    want = gamma * (raw - mean) / jnp.sqrt(var + eps) + beta
    np.testing.assert_allclose(y, np.asarray(want), rtol=1e-4, atol=1e-3)
    # and the reference executor agrees given the same folded params
    y_ref = np.asarray(execute_network_reference(graph, x, [w_fold],
                                                 biases=[b_fold]))
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_fold_batchnorm_depthwise_and_residual_graph():
    """BN folding composes with depthwise layers and residual joins."""
    layers = [
        ConvWorkload(M=64, C=32, P=14, Q=14, R=1, S=1, name="pw1"),
        ConvWorkload(M=64, C=1, P=14, Q=14, R=3, S=3, name="dw"),
        ConvWorkload(M=64, C=64, P=12, Q=12, R=1, S=1, name="pw2"),
    ]
    graph = from_layers(layers, "dw-res", skip_edges=((0, 2),))
    plan = tiled_plan(graph)
    ws = init_graph_weights(layers, seed=31)
    rng = np.random.default_rng(32)
    folded, biases = [], []
    for wl, w in zip(layers, ws):
        gamma, beta, mean, var = bn_params(rng, wl.M)
        wf, bf = fold_batchnorm(w, gamma, beta, mean, var)
        folded.append(wf)
        biases.append(bf)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    relu = lambda t: jnp.maximum(t, 0)   # noqa: E731
    y = np.asarray(execute_network(plan, graph, x, folded, biases=biases,
                                   activation=relu))
    y_ref = np.asarray(execute_network_reference(graph, x, folded,
                                                 biases=biases,
                                                 activation=relu))
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_prepared_network_with_stale_biases_rejected():
    wl = ConvWorkload(M=128, C=64, P=8, Q=8, R=1, S=1, name="pw")
    graph = from_layers([wl], "one")
    plan = tiled_plan(graph)
    ws = init_graph_weights([wl], seed=41)
    rng = np.random.default_rng(42)
    bias = jnp.asarray(rng.normal(size=wl.M), jnp.float32)
    prepared = prepare_network(plan, graph, ws, biases=[bias])
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y = execute_network(plan, graph, x, ws, prepared=prepared,
                        biases=[bias])
    assert y.shape == (wl.N, wl.P, wl.Q, wl.M)
    with pytest.raises(PlanError, match="different"):
        execute_network(plan, graph, x, ws, prepared=prepared,
                        biases=[bias + 1.0])
    with pytest.raises(PlanError, match="different"):
        execute_network(plan, graph, x, ws, prepared=prepared)
