"""repro.plan — network planner, plan artifacts, plan-driven executor."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.core.dataflow import ConvWorkload
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.plan import (ExecutionPlan, NetworkPlanner, PlanCache, PlanError,
                        PlannerOptions, bert_graph, execute_plan,
                        execute_plan_reference, from_arch_config, from_layers,
                        layout_block_perm, mobilenet_v3_graph, prepare_plan,
                        resnet50_graph)
from repro.plan.executor import (apply_block_perm, invert_block_perm,
                                 permute_weight_blocks)

SMALL_LAYOUTS = tuple(Layout.parse(s)
                      for s in ("HWC_C32", "HWC_H32", "HWC_C4W8"))


def small_chain(n=3):
    shapes = [
        ConvWorkload(M=64, C=32, P=14, Q=14, R=1, S=1, name="a"),
        ConvWorkload(M=32, C=64, P=14, Q=14, R=3, S=3, name="b"),
        ConvWorkload(M=96, C=32, P=7, Q=7, R=1, S=1, name="c"),
        ConvWorkload(M=32, C=96, P=7, Q=7, R=1, S=1, name="d"),
    ]
    return from_layers(shapes[:n], f"chain{n}")


@pytest.fixture
def obs_enabled():
    """Tracing on for the test body; global obs state reset afterwards."""
    obs.reset()
    obs.enable()
    yield
    obs.reset()


def gemm_chain():
    return from_layers([
        ConvWorkload.from_gemm(M=384, N=128, K=256, name="fc1"),
        ConvWorkload.from_gemm(M=512, N=128, K=384, name="fc2"),
        ConvWorkload.from_gemm(M=256, N=128, K=512, name="fc3"),
    ], "mlp3")


# ------------------------------------------------------------------ DP search
@pytest.mark.parametrize("n,modes", [(3, ("offchip",)), (4, ("rir",)),
                                     (4, ("offchip", "rir"))])
def test_dp_equals_bruteforce_on_chains(n, modes):
    """Viterbi over boundary layouts is exact: equals full enumeration."""
    opts = PlannerOptions(switch_modes=modes, layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    planner = NetworkPlanner(small_chain(n), EvalConfig(), opts)
    dp = planner.plan()
    bf = planner.brute_force()
    assert dp.total_cycles == bf.total_cycles
    assert dp.total_energy_pj == bf.total_energy_pj


def test_planned_dominates_greedy_resnet50():
    """Network planning never loses to per-layer-greedy under the same
    total-cost objective (incl. residual skip edges)."""
    opts = PlannerOptions(switch_modes=("offchip",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    planner = NetworkPlanner(resnet50_graph(), EvalConfig(), opts)
    assert planner.plan().total_cycles <= planner.greedy().total_cycles


def test_rir_switching_beats_offchip_switching_mbv3():
    """The FEATHER claim: with RIR the planner switches for free, so the
    planned schedule is no slower than on reorder-less hardware."""
    cfg = EvalConfig()
    mk = lambda modes: NetworkPlanner(
        mobilenet_v3_graph(), cfg,
        PlannerOptions(switch_modes=modes, layouts=SMALL_LAYOUTS,
                       parallel_dims=("C", "P", "Q"))).plan()
    assert mk(("rir",)).total_cycles <= mk(("offchip",)).total_cycles


def test_plan_discontinuity_rejected():
    plan = NetworkPlanner(gemm_chain(), EvalConfig(),
                          PlannerOptions(layouts=SMALL_LAYOUTS)).plan()
    import dataclasses
    bad_step = dataclasses.replace(plan.steps[1], in_layout="HWC_W32")
    bad = dataclasses.replace(
        plan, steps=(plan.steps[0], bad_step, plan.steps[2]))
    x = jnp.zeros((128, 256), jnp.float32)
    ws = [jnp.zeros((256, 384)), jnp.zeros((384, 512)), jnp.zeros((512, 256))]
    with pytest.raises(PlanError):
        execute_plan(bad, x, ws)


# ------------------------------------------------------------- plan artifacts
def test_plan_json_roundtrip_lossless(tmp_path):
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(small_chain(3), EvalConfig(), opts).plan()
    assert ExecutionPlan.from_json(plan.to_json()) == plan
    p = tmp_path / "plan.json"
    plan.save(p)
    assert ExecutionPlan.load(p) == plan


def test_plan_cache_memoizes_and_persists(tmp_path, obs_enabled):
    graph = small_chain(3)
    cfg = EvalConfig()
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    calls = []

    def planner_fn(g, c):
        calls.append(1)
        return NetworkPlanner(g, c, opts).plan()

    cache = PlanCache(tmp_path)
    a = cache.get_or_plan(graph, cfg, planner_fn, extra_key=opts.key())
    b = cache.get_or_plan(graph, cfg, planner_fn, extra_key=opts.key())
    assert len(calls) == 1 and a == b
    # a fresh cache over the same directory hits the persisted artifact
    c = PlanCache(tmp_path).get_or_plan(graph, cfg, planner_fn,
                                        extra_key=opts.key())
    assert len(calls) == 1 and c == a
    # every lookup landed in a counter: 1 plan (miss+put), then a memory
    # hit, then the fresh process's disk hit
    assert obs.counter_value("plan_cache.miss") == 1
    assert obs.counter_value("plan_cache.put") == 1
    assert obs.counter_value("plan_cache.hit", tier="mem") == 1
    assert obs.counter_value("plan_cache.hit", tier="disk") == 1


def test_plan_cache_corrupt_artifact_is_a_miss(tmp_path, obs_enabled):
    """A corrupt on-disk artifact must not raise out of ``get``: it is
    deleted, treated as a miss, and ``get_or_plan`` re-plans over it —
    and each eviction is visible in the ``plan_cache.evict`` counter."""
    graph = small_chain(2)
    cfg = EvalConfig()
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    calls = []

    def planner_fn(g, c):
        calls.append(1)
        return NetworkPlanner(g, c, opts).plan()

    plan = PlanCache(tmp_path).get_or_plan(graph, cfg, planner_fn,
                                           extra_key=opts.key())
    (artifact,) = tmp_path.glob("plan-*.json")
    for i, garbage in enumerate(("{not json", '{"version": 3}')):
        artifact.write_text(garbage)
        cache = PlanCache(tmp_path)   # fresh: no in-memory hit
        assert cache.get(plan.graph_hash, plan.config_key) is None
        assert not artifact.exists(), "corrupt cache file not evicted"
        assert obs.counter_value("plan_cache.evict", reason="corrupt") == i + 1
        replanned = cache.get_or_plan(graph, cfg, planner_fn,
                                      extra_key=opts.key())
        assert replanned == plan
    # 1 initial miss + per corrupt round (evicting get + get_or_plan's get)
    assert obs.counter_value("plan_cache.miss") == 5
    assert obs.counter_value("plan_cache.put") == 3
    assert obs.counter_value("plan_cache.hit", tier="mem") == 0
    assert obs.counter_value("plan_cache.hit", tier="disk") == 0


def test_plan_cache_validates_full_key_after_load(tmp_path, obs_enabled):
    """The filename only encodes 16-char truncated hashes; a filename
    collision (or hand-edited artifact) whose recorded full identity
    mismatches must be a miss, never the wrong plan."""
    graph = small_chain(2)
    cfg = EvalConfig()
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    cache = PlanCache(tmp_path)
    plan = cache.get_or_plan(
        graph, cfg, lambda g, c: NetworkPlanner(g, c, opts).plan(),
        extra_key=opts.key())
    (artifact,) = tmp_path.glob("plan-*.json")
    # another (graph, config)'s plan lands on this filename: simulate the
    # truncated-hash collision by swapping in a mismatching artifact
    import dataclasses
    impostor = dataclasses.replace(plan, graph_hash="f" * 64)
    artifact.write_text(impostor.to_json())
    fresh = PlanCache(tmp_path)
    assert fresh.get(plan.graph_hash, plan.config_key) is None
    assert not artifact.exists(), "mismatched cache file not evicted"
    assert obs.counter_value("plan_cache.evict", reason="mismatch") == 1
    assert obs.counter_value("plan_cache.miss") == 2  # initial + collision


def test_graph_hash_tracks_content():
    assert small_chain(3).graph_hash() == small_chain(3).graph_hash()
    assert small_chain(3).graph_hash() != small_chain(4).graph_hash()
    assert resnet50_graph().graph_hash() != \
        from_layers(resnet50_graph().layers, "resnet50").graph_hash()


def test_lm_graph_adapter():
    from repro.configs import get_config
    g = from_arch_config(get_config("llama3p2_3b", smoke=True), seq=128)
    assert len(g) >= 4 and g.skip_edges
    assert bert_graph(layers_sampled=2).skip_edges


# ------------------------------------------------------------------- executor
def test_layout_block_perm_is_permutation():
    for name in ("HWC_C32", "HWC_H32", "HWC_C4W8"):
        for n in (2, 3, 4, 8):
            perm = layout_block_perm(name, n)
            assert sorted(perm) == list(range(n))
    assert layout_block_perm("HWC_C32", 4) != layout_block_perm("HWC_H32", 4)


def test_block_perm_helpers_invert():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 512)), jnp.float32)
    perm = layout_block_perm("HWC_C4W8", 4)
    stored = apply_block_perm(x, perm)
    assert np.allclose(np.asarray(invert_block_perm(stored, perm)),
                       np.asarray(x))
    # weight prep contracts correctly against a perm-stored activation
    w = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    w_eff = permute_weight_blocks(w, perm)
    assert np.allclose(np.asarray(stored @ w_eff), np.asarray(x @ w),
                       atol=1e-3)


def test_executor_matches_ref_oracle_after_roundtrip(tmp_path):
    """Acceptance: serialize -> deserialize -> execute, Pallas output matches
    the kernels/ref.py oracle (and the plain matmul chain)."""
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(gemm_chain(), EvalConfig(), opts).plan()
    p = tmp_path / "plan.json"
    plan.save(p)
    plan = ExecutionPlan.load(p)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(256, 384)), jnp.float32),
          jnp.asarray(rng.normal(size=(384, 512)), jnp.float32),
          jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)]
    y_pallas = np.asarray(execute_plan(plan, x, ws))
    y_ref = np.asarray(execute_plan_reference(plan, x, ws))
    y_plain = np.asarray(x @ ws[0] @ ws[1] @ ws[2])
    np.testing.assert_allclose(y_pallas, y_ref, rtol=1e-4, atol=0.1)
    np.testing.assert_allclose(y_pallas, y_plain, rtol=1e-4, atol=0.1)


def test_prepared_plan_reuse_matches_per_call_setup():
    """prepare_plan hoists perms/effective weights once; repeat calls over
    fresh batches match the unprepared path and the plain matmul chain."""
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(gemm_chain(), EvalConfig(), opts).plan()
    rng = np.random.default_rng(3)
    ws = [jnp.asarray(rng.normal(size=(256, 384)), jnp.float32),
          jnp.asarray(rng.normal(size=(384, 512)), jnp.float32),
          jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)]
    prepared = prepare_plan(plan, 256, ws)
    for _ in range(3):   # e.g. consecutive serving batches
        x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
        y_prep = np.asarray(execute_plan(plan, x, ws, prepared=prepared))
        y_cold = np.asarray(execute_plan(plan, x, ws))
        y_plain = np.asarray(x @ ws[0] @ ws[1] @ ws[2])
        np.testing.assert_array_equal(y_prep, y_cold)
        np.testing.assert_allclose(y_prep, y_plain, rtol=1e-4, atol=0.1)


def test_traced_prepared_plan_never_fences(obs_enabled, monkeypatch):
    """A traced GEMM chain waits for the device nowhere and emits one
    ``exec.step`` per plan step; its output is the untraced one's."""
    import jax
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(gemm_chain(), EvalConfig(), opts).plan()
    rng = np.random.default_rng(5)
    ws = [jnp.asarray(rng.normal(size=(256, 384)), jnp.float32),
          jnp.asarray(rng.normal(size=(384, 512)), jnp.float32),
          jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)]
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    prepared = prepare_plan(plan, 256, ws)
    obs.disable()
    y_off = np.asarray(prepared(x))
    obs.reset()
    obs.enable()
    fences = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: fences.append(1) or real(v))
    y_on = prepared(x)
    monkeypatch.undo()
    assert fences == []
    steps = [e for e in obs.events() if e["name"] == "exec.step"]
    assert [e["attrs"]["step"] for e in steps] == list(range(len(plan.steps)))
    (chain,) = [e for e in obs.events() if e["name"] == "exec.chain"]
    assert all(chain["ts"] <= e["ts"] for e in steps)
    np.testing.assert_array_equal(np.asarray(y_on), y_off)


def test_stale_prepared_plan_rejected():
    """prepared= built from different weights/plan must fail loudly, not
    silently compute with the old pre-permuted weights."""
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(gemm_chain(), EvalConfig(), opts).plan()
    rng = np.random.default_rng(4)
    ws = [jnp.asarray(rng.normal(size=(256, 384)), jnp.float32),
          jnp.asarray(rng.normal(size=(384, 512)), jnp.float32),
          jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)]
    prepared = prepare_plan(plan, 256, ws)
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    new_ws = [w + 1.0 for w in ws]
    with pytest.raises(PlanError, match="different"):
        execute_plan(plan, x, new_ws, prepared=prepared)


def test_executor_with_activation_and_forced_switches():
    """Boundary layouts that differ per step exercise real epilogue perms."""
    import dataclasses
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    plan = NetworkPlanner(gemm_chain(), EvalConfig(), opts).plan()
    # force distinct boundary layouts (a valid plan need not switch; the
    # executor must honour whatever the artifact says)
    names = ["HWC_C32", "HWC_H32", "HWC_C4W8", "HWC_C32"]
    steps = []
    from repro.plan.plan import layout_block_perm as lbp
    for i, s in enumerate(plan.steps):
        n_blocks = s.workload.M // 128
        steps.append(dataclasses.replace(
            s, in_layout=names[i], out_layout=names[i + 1],
            epilogue_perm=lbp(names[i + 1], n_blocks)))
    plan = dataclasses.replace(plan, steps=tuple(steps))

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(256, 384)), jnp.float32),
          jnp.asarray(rng.normal(size=(384, 512)), jnp.float32),
          jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)]
    relu = lambda t: jnp.maximum(t, 0)
    y = np.asarray(execute_plan(plan, x, ws, activation=relu))
    y_ref = np.asarray(execute_plan_reference(plan, x, ws, activation=relu))
    y_plain = np.asarray(
        jnp.maximum(jnp.maximum(x @ ws[0], 0) @ ws[1], 0) @ ws[2])
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=0.1)
    np.testing.assert_allclose(y, y_plain, rtol=1e-4, atol=0.1)
