"""Whole-network plan execution: conv lowering + branch-aware residuals.

``execute_network`` must run COMPLETE ``LayerGraph``s — strided/padded
convolutions, depthwise layers, and residual joins — through the Pallas
``rir_matmul`` path (no reference fallback), reproducing the canonical
``execute_network_reference`` oracle built on the ``kernels/ref.py``
conv/depthwise references.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dataflow import ConvWorkload
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.core.workloads import (init_graph_weights, input_channels,
                                  is_depthwise, weight_shape)
from repro.kernels import ops, ref
from repro.plan import (JoinSpec, NetworkPlanner, PlanError, PlannerOptions,
                        PreparedNetwork, adapt_activation, execute_network,
                        execute_network_reference, from_layers,
                        layout_block_perm, mobilenet_v3_graph,
                        prepare_network, resnet50_graph)

SMALL_LAYOUTS = tuple(Layout.parse(s)
                      for s in ("HWC_C32", "HWC_H32", "HWC_C4W8"))
OPTS = dict(layouts=SMALL_LAYOUTS, parallel_dims=("C", "P", "Q"))
RELU = lambda t: jnp.maximum(t, 0)   # noqa: E731


def make_plan(graph, modes=("rir",), **kw):
    opts = PlannerOptions(switch_modes=modes, **OPTS, **kw)
    return NetworkPlanner(graph, EvalConfig(), opts).plan()


def run_both(graph, plan=None, activation=None, seed=0, x=None):
    plan = plan if plan is not None else make_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=seed)
    if x is None:
        rng = np.random.default_rng(seed + 1)
        x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y = execute_network(plan, graph, x, ws, activation=activation)
    y_ref = execute_network_reference(graph, x, ws, activation=activation)
    return np.asarray(y), np.asarray(y_ref), plan


# ----------------------------------------------------------- conv path vs ref
@pytest.mark.parametrize("M,C,R,S,stride,P,Q", [
    (64, 16, 3, 3, 1, 14, 14),     # plain 3x3
    (96, 32, 3, 3, 2, 8, 8),       # strided
    (128, 64, 5, 5, 1, 7, 7),      # 5x5, M = one kernel block
    (256, 128, 1, 1, 1, 16, 16),   # GEMM-able 1x1, permutable M
    (40, 24, 3, 1, 1, 10, 12),     # asymmetric taps, ragged channels
    (384, 256, 3, 3, 2, 7, 7),     # strided with permutable in/out blocks
])
def test_single_conv_matches_ref_oracle(M, C, R, S, stride, P, Q):
    """One-layer graphs: the im2col lowering reproduces the direct conv
    oracle across stride / tap / channel shapes (128-multiples and not)."""
    wl = ConvWorkload(M=M, C=C, P=P, Q=Q, R=R, S=S, stride=stride,
                      name="conv")
    graph = from_layers([wl], "one")
    y, y_ref, plan = run_both(graph)
    assert plan.steps[0].kernel == "rir_matmul"
    assert plan.steps[0].lowering == ("gemm" if R == S == stride == 1
                                      else "im2col")
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)
    # and the oracle itself is the plain ref.conv2d on the adapted input
    ws = init_graph_weights([wl], seed=0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    direct = ref.conv2d(x, jnp.asarray(ws[0]), stride)
    np.testing.assert_allclose(
        np.asarray(execute_network_reference(graph, x, ws)),
        np.asarray(direct), rtol=1e-5, atol=1e-5)


def test_lane_aligned_k_blocks_match_ref_oracle():
    """The K block the executor picks is TPU-legal — a multiple of 128
    lanes or the whole padded K — even where the plan's tile asks for a
    64-wide one.  Steps with K in {128, 256} then run one and two K steps
    of 128 and still reproduce the oracle."""
    graph = from_layers([
        ConvWorkload(M=256, C=128, P=8, Q=8, R=1, S=1, name="k128"),
        ConvWorkload(M=128, C=256, P=8, Q=8, R=1, S=1, name="k256"),
    ], "kpair")
    plan = make_plan(graph)
    plan = dataclasses.replace(plan, steps=tuple(
        dataclasses.replace(s, tiles=(("C", 64),), double_buffer=False,
                            buffer_alloc=())
        for s in plan.steps))
    ws = init_graph_weights(list(graph.layers), seed=3)
    prepared = prepare_network(plan, graph, ws)
    assert [(st.k_width, st.block_k, arr.w_eff.shape[0])
            for st, arr in zip(prepared.steps, prepared.arrays)] == \
        [(128, 128, 128), (256, 128, 256)]
    y, y_ref, _ = run_both(graph, plan=plan, seed=3)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_depthwise_conv_matches_ref_oracle():
    wl = ConvWorkload(M=72, C=1, P=14, Q=14, R=5, S=5, stride=2, name="dw")
    assert is_depthwise(wl) and input_channels(wl) == 72
    assert weight_shape(wl) == (5, 5, 72)
    graph = from_layers([wl], "dw1")
    y, y_ref, plan = run_both(graph)
    assert plan.steps[0].lowering == "depthwise"
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_conv_chain_with_same_padding_boundary():
    """res50-l47 shape: the consumer wants H=16 from a 14x14 producer — the
    boundary adapter's symmetric zero pad is SAME padding, and the fused
    row map must reproduce it exactly."""
    graph = from_layers([
        ConvWorkload(M=256, C=64, P=14, Q=14, R=1, S=1, name="reduce"),
        ConvWorkload(M=256, C=256, P=14, Q=14, R=3, S=3, name="same3x3"),
    ], "same-pad")
    y, y_ref, _ = run_both(graph, activation=RELU)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_conv_chain_with_channel_mismatch_boundary():
    """Channel truncation/padding at a boundary folds into the effective
    weight (zero rows / absent columns), never a runtime relayout."""
    graph = from_layers([
        ConvWorkload(M=512, C=32, P=8, Q=8, R=1, S=1, name="wide"),
        ConvWorkload(M=256, C=256, P=8, Q=8, R=1, S=1, name="narrower"),
        ConvWorkload(M=384, C=512, P=8, Q=8, R=1, S=1, name="wants-more"),
    ], "chan-adapt")
    y, y_ref, _ = run_both(graph)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_adapt_activation_semantics():
    x = jnp.arange(2 * 8 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 8, 4)
    sub = adapt_activation(x, 4, 4, 4)
    assert sub.shape == (2, 4, 4, 4)
    np.testing.assert_array_equal(np.asarray(sub), np.asarray(x[:, ::2, ::2]))
    pad = adapt_activation(x, 10, 8, 6)
    assert pad.shape == (2, 10, 8, 6)
    np.testing.assert_array_equal(np.asarray(pad[:, 1:9, :, :4]),
                                  np.asarray(x))
    assert float(jnp.sum(jnp.abs(pad[:, 0]))) == 0.0
    assert float(jnp.sum(jnp.abs(pad[..., 4:]))) == 0.0
    trunc = adapt_activation(x, 8, 8, 3)
    np.testing.assert_array_equal(np.asarray(trunc), np.asarray(x[..., :3]))


# ------------------------------------------------------------- full networks
@pytest.mark.parametrize("modes", [("rir",), ("offchip", "rir")])
def test_full_resnet50_executes_through_pallas(modes):
    """Acceptance: the complete ResNet-50 graph — convs and residual joins —
    runs the plan-driven Pallas path with no reference fallback."""
    graph = resnet50_graph()
    plan = make_plan(graph, modes=modes)
    assert all(s.kernel == "rir_matmul" for s in plan.steps)
    # plans are tiled by default now: the executed path must honour the
    # tile-derived kernel block/grid shapes, not just the modeled numbers
    assert any(s.tiles for s in plan.steps)
    assert {i for i, s in enumerate(plan.steps) if s.joins} == {3, 6, 9}
    y, y_ref, _ = run_both(graph, plan=plan, activation=RELU)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_full_mobilenet_v3_executes_through_pallas():
    """Acceptance: Mob-V3 with depthwise layers and the inverted-residual
    join executes end to end, matching the oracle."""
    graph = mobilenet_v3_graph()
    plan = make_plan(graph)
    assert all(s.kernel == "rir_matmul" for s in plan.steps)
    assert any(s.lowering == "depthwise" for s in plan.steps)
    assert any(s.tiles for s in plan.steps)
    # pw2 (24ch) joins pw3's 72ch output: shapes disagree, so the planner
    # must charge (and record) the residual relayout even if layouts match
    assert plan.steps[5].joins == (
        JoinSpec(src=4, src_layout=plan.steps[4].out_layout,
                 relayout="offchip"),)
    y, y_ref, _ = run_both(graph, plan=plan, activation=RELU)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_plan_with_joins_roundtrips_json():
    graph = resnet50_graph()
    plan = make_plan(graph)
    from repro.plan import ExecutionPlan
    assert ExecutionPlan.from_json(plan.to_json()) == plan
    assert any(s.joins for s in plan.steps)


# ------------------------------------------------------------ residual joins
def residual_gemm_graph():
    """GEMM trunk whose skip edge endpoints share shape (512 features), so
    the join exercises pure layout (dis)agreement, not the shape adapter."""
    return from_layers([
        ConvWorkload.from_gemm(M=512, N=128, K=256, name="in"),
        ConvWorkload.from_gemm(M=512, N=128, K=512, name="mid"),
        ConvWorkload.from_gemm(M=512, N=128, K=512, name="out"),
    ], "res-mlp", skip_edges=((0, 2),))


def _force_boundaries(plan, names):
    """Rewrite a plan's boundary layouts (and derived perms/joins)."""
    steps = []
    for i, s in enumerate(plan.steps):
        n_blocks = s.workload.M // 128 if s.workload.M % 128 == 0 else 0
        joins = tuple(dataclasses.replace(
            j, src_layout=names[j.src + 1],
            relayout="none" if names[j.src + 1] == names[i + 1] else "offchip")
            for j in s.joins)
        steps.append(dataclasses.replace(
            s, in_layout=names[i], out_layout=names[i + 1],
            epilogue_perm=(layout_block_perm(names[i + 1], n_blocks)
                           if n_blocks >= 1 else None),
            joins=joins))
    return dataclasses.replace(plan, steps=tuple(steps))


def test_residual_join_layouts_agree_fuses():
    """Same boundary layout at both skip endpoints: the join is fused into
    the consumer's epilogue (JoinSpec.relayout == 'none')."""
    graph = residual_gemm_graph()
    plan = _force_boundaries(make_plan(graph),
                             ["HWC_C32", "HWC_C32", "HWC_C32", "HWC_C32"])
    assert plan.steps[2].joins[0].relayout == "none"
    ws = init_graph_weights(list(graph.layers), seed=5)
    prepared = prepare_network(plan, graph, ws)
    assert prepared.steps[2].joins[0].fused
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y = execute_network(plan, graph, x, ws, prepared=prepared)
    y_ref = execute_network_reference(graph, x, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


def test_residual_join_layouts_deliberately_disagree():
    """Skip-edge endpoints in different boundary layouts: the executor must
    apply the planner-costed relayout at the join and still match the
    oracle (the oracle knows nothing about layouts)."""
    graph = residual_gemm_graph()
    names = ["HWC_C32", "HWC_H32", "HWC_C32", "HWC_C4W8"]   # src b1 != dst b3
    plan = _force_boundaries(make_plan(graph), names)
    join = plan.steps[2].joins[0]
    assert join.src_layout == "HWC_H32" and join.relayout == "offchip"
    ws = init_graph_weights(list(graph.layers), seed=7)
    prepared = prepare_network(plan, graph, ws)
    assert not prepared.steps[2].joins[0].fused
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    for use_pallas in (True, False):
        y = execute_network(plan, graph, x, ws, prepared=prepared,
                            use_pallas=use_pallas)
        y_ref = execute_network_reference(graph, x, ws)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)


def test_fused_residual_kernel_matches_ref():
    """The rir_matmul residual operand: epilogue add in stored layout."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(128, 512)), jnp.float32)
    perm = (3, 1, 0, 2)
    y = ops.rir_matmul(a, b, perm, residual=res)
    want = ref.rir_matmul(a, b, perm, 128, residual=res)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    # and equals the unfused form: permuted product plus stored residual
    plain = ref.rir_matmul(a, b, perm, 128) + res
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain),
                               rtol=1e-4, atol=1e-3)


# -------------------------------------------------------------- prepare/reuse
def test_prepared_network_reuse_and_staleness():
    graph = residual_gemm_graph()
    plan = make_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=9)
    prepared = prepare_network(plan, graph, ws)
    rng = np.random.default_rng(10)
    for _ in range(2):
        x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
        y_prep = execute_network(plan, graph, x, ws, prepared=prepared)
        y_cold = execute_network(plan, graph, x, ws)
        np.testing.assert_array_equal(np.asarray(y_prep), np.asarray(y_cold))
    new_ws = [w + 1.0 for w in ws]
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    with pytest.raises(PlanError, match="different"):
        execute_network(plan, graph, x, new_ws, prepared=prepared)


def test_plan_graph_mismatch_rejected():
    graph = residual_gemm_graph()
    plan = make_plan(graph)
    other = resnet50_graph()
    ws = init_graph_weights(list(other.layers), seed=0)
    with pytest.raises(PlanError):
        prepare_network(plan, other, ws)


# -------------------------------------------------------------- one program
def program_graph():
    """A 3x3 conv (a row-map gather) into two 1x1s with a skip join, at
    batch 4: every kind of prepared array the program reads."""
    return from_layers([
        ConvWorkload(M=128, C=16, P=16, Q=16, R=3, S=3, name="c3"),
        ConvWorkload(M=128, C=128, P=16, Q=16, R=1, S=1, name="c1"),
        ConvWorkload(M=128, C=128, P=16, Q=16, R=1, S=1, name="c1b"),
    ], "prog", skip_edges=((0, 2),)).with_batch(4)


def test_network_traces_once_per_batch_shape(monkeypatch):
    """Repeated calls, and ``execute_requests`` at every batch size
    1..max_batch (all padded to the plan's N), trace the network once;
    another activation is another program."""
    traces = []
    real = PreparedNetwork._forward
    monkeypatch.setattr(PreparedNetwork, "_forward",
                        lambda self, *a: traces.append(1) or real(self, *a))
    graph = program_graph()
    plan = make_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=11)
    prepared = prepare_network(plan, graph, ws)
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    full = [np.asarray(prepared(x)) for _ in range(3)]
    samples = [x[i] for i in range(prepared.max_batch)]
    for k in range(1, prepared.max_batch + 1):
        outs = prepared.execute_requests(samples[:k])
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(o), full[0][i])
    assert len(traces) == 1
    prepared(x, activation=RELU)
    prepared(x, activation=RELU)
    assert len(traces) == 2
    np.testing.assert_array_equal(full[1], full[0])


def _constant_sizes(program_text):
    """Element counts of every ``stablehlo.constant`` in a program."""
    sizes = []
    for line in program_text.splitlines():
        if "stablehlo.constant" not in line:
            continue
        dims = line.rsplit("tensor<", 1)[1].split("x")[:-1]
        sizes.append(int(np.prod([int(d) for d in dims])))
    return sizes


def test_prepared_arrays_are_program_arguments():
    """Weights, row maps and biases enter the program as parameters: the
    lowered program holds no constant beyond a few thousand elements."""
    graph = program_graph()
    plan = make_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=13)
    biases = [jnp.full((wl.M,), 0.5, jnp.float32) for wl in graph.layers]
    prepared = prepare_network(plan, graph, ws, biases=biases)
    leaves = jax.tree.leaves(prepared.arrays)
    assert max(a.size for a in leaves) > 4096
    assert prepared.arrays[0].row_map is not None
    x = jax.ShapeDtypeStruct(graph.input_shape(), jnp.float32)
    text = prepared.program().lower(prepared.arrays, x).as_text()
    assert max(_constant_sizes(text), default=0) <= 4096
    main = next(ln for ln in text.splitlines() if "func.func public @main"
                in ln)
    assert main.count("%arg") == len(leaves) + 1
