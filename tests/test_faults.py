"""Fault injection, retry, degradation ladder, and atomic-artifact tests.

Covers the robustness tentpole end to end: the ``repro.runtime.faults``
no-op/armed contract, ``retry_call`` backoff semantics, atomic
``ExecutionPlan.save`` and checkpoint writes (kill-between-write-and-rename
leaves the previous artifact loadable), ``PlanCache`` quarantine, the
``resolve_plan`` degradation ladder, checkpoint integrity digests with
restore fallback, and supervisor backoff/restart-window behaviour.
"""
import json
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.checkpoint import (CheckpointManager, committed_steps, latest_step,
                              restore_pytree, save_pytree)
from repro.core.dataflow import ConvWorkload
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.core.workloads import init_graph_weights
from repro.plan import (ExecutionPlan, NetworkPlanner, PlanCache,
                        PlannerOptions, ResolvedPlan, TIER_NAMES, config_key,
                        execute_network, from_layers, prepare_network,
                        prepare_plan, resolve_plan)
from repro.runtime import faults
from repro.runtime.retry import RetryPolicy, retry_call

SMALL_LAYOUTS = tuple(Layout.parse(s) for s in ("HWC_C32", "HWC_H32"))
FAST = RetryPolicy(max_attempts=3, base_delay_s=0.01, max_delay_s=0.05)
NOSLEEP = lambda s: None  # noqa: E731


@pytest.fixture
def obs_enabled():
    obs.reset()
    obs.enable()
    yield
    obs.reset()


@pytest.fixture
def obs_reset():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(autouse=True)
def disarmed():
    faults.disarm()
    yield
    faults.disarm()


def tiny_graph(n=2):
    wls = [ConvWorkload(name=f"f-l{i}", N=1, M=64, C=16 if i == 0 else 64,
                        P=8, Q=8, R=1, S=1) for i in range(n)]
    return from_layers(wls, name="tinyfaults")


def tiny_opts():
    return PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))


def tiny_plan(graph, opts=None):
    return NetworkPlanner(graph, EvalConfig(), opts or tiny_opts()).plan()


# ------------------------------------------------------------- faults core
def test_disarmed_site_is_noop():
    assert not faults.is_armed()
    for _ in range(100):
        faults.site("plan.load")          # must not raise or allocate state
    assert faults.current() is None


def test_disarmed_overhead_wall_time_guard():
    """200k disarmed site() calls must stay trivially cheap (the executor
    hits this per plan step).  2s is ~100x slack, same guard as obs."""
    t0 = time.perf_counter()
    for _ in range(200_000):
        faults.site("exec.dispatch")
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, f"disarmed fault path took {elapsed:.2f}s for 200k"


def test_count_mode_exact_and_typed(obs_enabled):
    sched = faults.FaultSchedule(seed=0, sites={
        "plan.load": faults.SiteSpec(count=2, exc="OSError"),
        "heartbeat": faults.SiteSpec(count=1, exc="ConnectionError",
                                     after=1)})
    with faults.injecting(sched):
        for i in range(4):
            if i < 2:
                with pytest.raises(OSError) as ei:
                    faults.site("plan.load")
                assert faults.is_injected(ei.value)
            else:
                faults.site("plan.load")    # count exhausted: clean pass
        faults.site("heartbeat")            # visit 1: skipped (after=1)
        with pytest.raises(ConnectionError):
            faults.site("heartbeat")        # visit 2: injected
        faults.site("heartbeat")
    assert sched.injected("plan.load") == 2
    assert sched.visits("plan.load") == 4
    assert sched.injected("heartbeat") == 1
    assert sched.all_fired()
    assert sched.total_injected() == 3
    assert obs.counter_value("faults.injected", site="plan.load") == 2
    assert obs.counter_value("faults.injected", site="heartbeat") == 1
    # disarmed again: the same site is a no-op
    faults.site("plan.load")


def test_probability_mode_deterministic_per_seed():
    def run(seed):
        sched = faults.FaultSchedule(seed=seed, sites={
            "exec.dispatch": faults.SiteSpec(p=0.5)})
        fired = []
        with faults.injecting(sched):
            for _ in range(64):
                try:
                    faults.site("exec.dispatch")
                    fired.append(0)
                except RuntimeError:
                    fired.append(1)
        return fired

    a, b, c = run(7), run(7), run(8)
    assert a == b                       # same seed -> same injection pattern
    assert a != c                       # different seed -> different pattern
    assert 0 < sum(a) < 64              # actually probabilistic


def test_sitespec_validation():
    with pytest.raises(ValueError):
        faults.SiteSpec(exc="KeyboardInterrupt")
    with pytest.raises(ValueError):
        faults.SiteSpec(count=-1)
    with pytest.raises(ValueError):
        faults.SiteSpec(p=1.5)


# ------------------------------------------------------------------- retry
def test_retry_absorbs_transients_and_counts(obs_enabled):
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TimeoutError("transient")
        return "ok"

    slept = []
    assert retry_call(flaky, site="t", policy=FAST,
                      sleep=slept.append) == "ok"
    assert len(calls) == 3
    assert len(slept) == 2
    assert obs.counter_value("retry.attempts", site="t") == 2
    assert obs.counter_value("retry.exhausted", site="t") == 0


def test_retry_backoff_is_deterministic_and_exponential():
    def run():
        slept = []
        with pytest.raises(OSError):
            retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                       site="s", policy=FAST, sleep=slept.append, seed=3)
        return slept

    a, b = run(), run()
    assert a == b                       # jitter is seeded per (seed, site)
    assert len(a) == FAST.max_attempts - 1
    assert a[1] > a[0]                  # exponential growth through jitter


def test_retry_exhaustion_reraises_last(obs_enabled):
    with pytest.raises(ConnectionError):
        retry_call(lambda: (_ for _ in ()).throw(ConnectionError("down")),
                   site="x", policy=FAST, sleep=NOSLEEP)
    assert obs.counter_value("retry.exhausted", site="x") == 1


def test_retry_non_fault_types_propagate_immediately():
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("content bug, not a machine fault")

    with pytest.raises(ValueError):
        retry_call(bug, site="x", policy=FAST, sleep=NOSLEEP)
    assert len(calls) == 1


def test_retry_deadline_skips_sleep_past_budget():
    t = [0.0]
    slept = []

    def clock():
        return t[0]

    def sleep(d):
        slept.append(d)
        t[0] += d

    with pytest.raises(OSError):
        retry_call(lambda: (_ for _ in ()).throw(OSError("x")), site="d",
                   policy=RetryPolicy(max_attempts=5, base_delay_s=1.0,
                                      max_delay_s=8.0, jitter=0.0),
                   sleep=sleep, clock=clock, deadline=2.5)
    # first backoff (1s) fits, second (2s) would land at 3s > 2.5 deadline
    assert slept == [1.0]


# ---------------------------------------------------- atomic plan artifacts
def test_plan_save_is_atomic_under_injected_kill(tmp_path):
    plan = tiny_plan(tiny_graph())
    p = tmp_path / "plan.json"
    plan.save(p)
    old_json = p.read_text()

    # mutate, then kill between write and rename: old artifact must survive
    sched = faults.FaultSchedule(seed=0, sites={
        "plan.save": faults.SiteSpec(count=1, exc="OSError")})
    with faults.injecting(sched):
        with pytest.raises(OSError):
            plan.save(p)
    assert p.read_text() == old_json
    assert ExecutionPlan.load(p).to_json() == plan.to_json()
    # and a clean retry completes the write
    plan.save(p)
    assert ExecutionPlan.load(p).to_json() == plan.to_json()


def test_plan_save_leaves_no_partial_on_fresh_path(tmp_path):
    plan = tiny_plan(tiny_graph())
    p = tmp_path / "fresh.json"
    sched = faults.FaultSchedule(seed=0, sites={
        "plan.save": faults.SiteSpec(count=1, exc="OSError")})
    with faults.injecting(sched):
        with pytest.raises(OSError):
            plan.save(p)
    assert not p.exists()               # no half-written artifact at the path


# ------------------------------------------------------ plan cache hardening
def test_cache_quarantines_corrupt_artifact(tmp_path, obs_enabled):
    graph = tiny_graph()
    plan = tiny_plan(graph)
    cache = PlanCache(tmp_path, sleep=NOSLEEP)
    cache.put(plan)
    art = next(tmp_path.glob("plan-*.json"))
    art.write_text("{not json")

    fresh = PlanCache(tmp_path, sleep=NOSLEEP)
    assert fresh.get(plan.graph_hash, plan.config_key) is None
    assert not art.exists()
    qfiles = list((tmp_path / "quarantine").iterdir())
    assert len(qfiles) == 1 and qfiles[0].name == art.name
    assert obs.counter_value("plan_cache.evict", reason="corrupt") == 1
    assert obs.counter_value("plan_cache.quarantined", reason="corrupt") == 1


def test_cache_io_fault_is_a_miss_not_a_crash(tmp_path, obs_enabled):
    graph = tiny_graph()
    plan = tiny_plan(graph)
    PlanCache(tmp_path, sleep=NOSLEEP).put(plan)
    art = next(tmp_path.glob("plan-*.json"))

    sched = faults.FaultSchedule(seed=0, sites={
        "plan_cache.io": faults.SiteSpec(count=99, exc="OSError")})
    fresh = PlanCache(tmp_path, sleep=NOSLEEP)
    with faults.injecting(sched):
        assert fresh.get(plan.graph_hash, plan.config_key) is None
    assert art.exists()                  # disk trouble != bad content
    assert obs.counter_value("plan_cache.io_error", op="get") == 1
    # with the fault gone the same cache serves the artifact
    got = fresh.get(plan.graph_hash, plan.config_key)
    assert got is not None and got.to_json() == plan.to_json()


def test_cache_transient_io_fault_absorbed_by_retry(tmp_path, obs_enabled):
    graph = tiny_graph()
    plan = tiny_plan(graph)
    PlanCache(tmp_path, sleep=NOSLEEP).put(plan)

    sched = faults.FaultSchedule(seed=0, sites={
        "plan_cache.io": faults.SiteSpec(count=1, exc="OSError")})
    fresh = PlanCache(tmp_path, sleep=NOSLEEP)
    with faults.injecting(sched):
        got = fresh.get(plan.graph_hash, plan.config_key)
    assert got is not None and got.to_json() == plan.to_json()
    assert obs.counter_value("retry.attempts", site="plan_cache.io") == 1
    assert obs.counter_value("plan_cache.hit", tier="disk") == 1


def test_cache_put_survives_persistent_write_fault(tmp_path, obs_enabled):
    plan = tiny_plan(tiny_graph())
    cache = PlanCache(tmp_path, sleep=NOSLEEP)
    sched = faults.FaultSchedule(seed=0, sites={
        "plan_cache.io": faults.SiteSpec(count=99, exc="OSError")})
    with faults.injecting(sched):
        cache.put(plan)                  # must not raise
    assert obs.counter_value("plan_cache.io_error", op="put") == 1
    # memory tier still serves it
    assert cache.get(plan.graph_hash, plan.config_key) is plan


# --------------------------------------------------------- degradation ladder
def test_resolve_cached_tier(tmp_path, obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()
    cache = PlanCache(tmp_path, sleep=NOSLEEP)
    r1 = resolve_plan(graph, EvalConfig(), opts, cache=cache, sleep=NOSLEEP)
    assert (r1.tier, r1.tier_name) == (1, "replanned")
    r0 = resolve_plan(graph, EvalConfig(), opts, cache=cache, sleep=NOSLEEP)
    assert r0.tier == 0
    assert r0.plan.to_json() == r1.plan.to_json()
    assert obs.counter_value("degrade.tier", level="cached") == 1
    assert obs.counter_value("degrade.tier", level="replanned") == 1


def test_resolve_replan_identical_after_cache_fault(tmp_path, obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()
    r1 = resolve_plan(graph, EvalConfig(), opts,
                      cache=PlanCache(tmp_path, sleep=NOSLEEP),
                      sleep=NOSLEEP)
    sched = faults.FaultSchedule(seed=0, sites={
        "plan_cache.io": faults.SiteSpec(count=99, exc="OSError")})
    with faults.injecting(sched):
        r2 = resolve_plan(graph, EvalConfig(), opts,
                          cache=PlanCache(tmp_path, sleep=NOSLEEP),
                          sleep=NOSLEEP)
    # the planner is deterministic: tier-1 replaces the lost cache entry
    # with a byte-identical plan, so execution stays bit-identical
    assert r2.tier == 1
    assert r2.plan.to_json() == r1.plan.to_json()


def test_resolve_degrades_to_greedy_then_fixed(obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()

    def broken(*a, **k):
        raise RuntimeError("planner down")

    r2 = resolve_plan(graph, EvalConfig(), opts, planner_fn=broken,
                      sleep=NOSLEEP)
    assert (r2.tier, r2.tier_name) == (2, "greedy")
    r3 = resolve_plan(graph, EvalConfig(), opts, planner_fn=broken,
                      greedy_fn=broken, sleep=NOSLEEP)
    assert (r3.tier, r3.tier_name) == (3, "fixed")
    assert obs.counter_value("degrade.tier", level="greedy") == 1
    assert obs.counter_value("degrade.tier", level="fixed") == 1
    assert obs.counter_value("retry.exhausted", site="plan.replan") == 2
    # degraded plans still execute
    ws = init_graph_weights(list(graph.layers), seed=0)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=graph.input_shape()), jnp.float32)
    y = np.asarray(execute_network(r3.plan, graph, x, ws))
    assert np.isfinite(y).all()


def test_degraded_plans_never_poison_the_cache(tmp_path, obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()
    cache = PlanCache(tmp_path, sleep=NOSLEEP)

    def broken(*a, **k):
        raise RuntimeError("planner down")

    r2 = resolve_plan(graph, EvalConfig(), opts, cache=cache,
                      planner_fn=broken, sleep=NOSLEEP)
    assert r2.tier == 2
    # neither memory nor disk may serve the degraded plan under the full key
    ck = config_key(EvalConfig(), opts.key())
    assert cache.get(graph.graph_hash(), ck) is None
    assert not list(tmp_path.glob("plan-*.json"))


def test_resolve_deadline_goes_straight_to_fixed(obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()
    r = resolve_plan(graph, EvalConfig(), opts, deadline_s=0.0,
                     sleep=NOSLEEP)
    assert (r.tier, r.tier_name) == (3, "fixed")


def test_resolve_seeds_cache_from_pinned_artifact(tmp_path, obs_enabled):
    graph, opts = tiny_graph(), tiny_opts()
    art = tmp_path / "pinned.json"
    r1 = resolve_plan(graph, EvalConfig(), opts, cache=PlanCache(),
                      artifact=art, sleep=NOSLEEP)
    assert r1.tier == 1 and art.exists()
    r0 = resolve_plan(graph, EvalConfig(), opts, cache=PlanCache(),
                      artifact=art, sleep=NOSLEEP)
    assert r0.tier == 0
    assert r0.plan.to_json() == r1.plan.to_json()


def test_tier_names_cover_ladder():
    assert TIER_NAMES == ("cached", "replanned", "greedy", "fixed")
    r = ResolvedPlan(plan=None, tier=2)
    assert r.tier_name == "greedy"


# ----------------------------------------------------------- exec.dispatch
def test_exec_dispatch_injection_and_retry_bitidentical(obs_enabled):
    graph = tiny_graph()
    plan = tiny_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=0)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=graph.input_shape()), jnp.float32)
    y0 = np.asarray(execute_network(plan, graph, x, ws))

    # count=2: the unguarded call burns one injection (and raises), the
    # retry-wrapped call absorbs the second and completes
    sched = faults.FaultSchedule(seed=0, sites={
        "exec.dispatch": faults.SiteSpec(count=2)})
    with faults.injecting(sched):
        with pytest.raises(RuntimeError) as ei:
            execute_network(plan, graph, x, ws)
        assert faults.is_injected(ei.value)
        y1 = np.asarray(retry_call(
            lambda: execute_network(plan, graph, x, ws),
            site="exec.dispatch", policy=FAST, sleep=NOSLEEP))
    assert sched.injected("exec.dispatch") == 2
    assert np.array_equal(y0, y1)


def test_exec_dispatch_fires_once_per_call():
    """The site fires once per executor call, however many plan steps the
    program holds: the network executor and the GEMM chain alike."""
    graph = tiny_graph(3)
    plan = tiny_plan(graph)
    ws = init_graph_weights(list(graph.layers), seed=0)
    prepared = prepare_network(plan, graph, ws)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=graph.input_shape()), jnp.float32)
    chain_ws = [jnp.ones((16, 64), jnp.float32),
                jnp.ones((64, 64), jnp.float32),
                jnp.ones((64, 64), jnp.float32)]
    chain = prepare_plan(plan, 16, chain_ws)
    sched = faults.FaultSchedule(seed=0, sites={
        "exec.dispatch": faults.SiteSpec(count=0)})
    with faults.injecting(sched):
        for _ in range(2):
            prepared(x)
        assert sched.visits("exec.dispatch") == 2
        chain(jnp.ones((64, 16), jnp.float32))
    assert sched.visits("exec.dispatch") == 3
    assert sched.injected("exec.dispatch") == 0


def test_armed_unrelated_sites_leave_plan_json_identical(tmp_path):
    """Arming a schedule on OTHER sites must not perturb planning output —
    the strict no-op discipline, byte-for-byte."""
    graph, opts = tiny_graph(), tiny_opts()
    j0 = tiny_plan(graph, opts).to_json()
    sched = faults.FaultSchedule(seed=0, sites={
        "heartbeat": faults.SiteSpec(count=99)})
    with faults.injecting(sched):
        j1 = tiny_plan(graph, opts).to_json()
    assert j0 == j1


# ------------------------------------------------------------- checkpoints
def _tree(v=1.0):
    return {"w": np.arange(6, dtype=np.float32) * v, "b": np.float32(v)}


def test_checkpoint_digests_written_and_verified(tmp_path):
    d = tmp_path / "step_00000001"
    save_pytree(_tree(), d)
    digests = json.loads((d / "digests.json").read_text())
    assert "manifest.json" in digests and "arrays/w.npy" in digests
    got = restore_pytree(_tree(0.0), d)
    assert np.array_equal(np.asarray(got["w"]), _tree()["w"])


def test_checkpoint_tamper_raises_oserror(tmp_path):
    d = tmp_path / "step_00000001"
    save_pytree(_tree(), d)
    raw = bytearray((d / "arrays" / "w.npy").read_bytes())
    raw[-1] ^= 0xFF                      # flip one payload byte
    (d / "arrays" / "w.npy").write_bytes(raw)
    with pytest.raises(OSError, match="integrity"):
        restore_pytree(_tree(0.0), d)


def test_checkpoint_without_sidecar_still_restores(tmp_path):
    d = tmp_path / "step_00000001"
    save_pytree(_tree(), d)
    (d / "digests.json").unlink()        # pre-sidecar layout
    got = restore_pytree(_tree(0.0), d)
    assert np.array_equal(np.asarray(got["w"]), _tree()["w"])


def test_checkpoint_kill_between_write_and_rename(tmp_path, obs_enabled):
    root = tmp_path / "ckpt"
    save_pytree(_tree(1.0), root / "step_00000001")
    sched = faults.FaultSchedule(seed=0, sites={
        "ckpt.write": faults.SiteSpec(count=99, exc="OSError")})
    with faults.injecting(sched):
        with pytest.raises(OSError):
            retry_call(lambda: save_pytree(_tree(2.0),
                                           root / "step_00000002"),
                       site="ckpt.write", policy=FAST, sleep=NOSLEEP)
    assert latest_step(root) == 1        # previous-good untouched
    got = restore_pytree(_tree(0.0), root / "step_00000001")
    assert np.asarray(got["b"]) == np.float32(1.0)
    # fault gone: the exact same save completes cleanly over its own debris
    save_pytree(_tree(2.0), root / "step_00000002")
    assert committed_steps(root) == [1, 2]


def test_restore_latest_falls_back_past_corrupt(tmp_path, obs_enabled):
    root = tmp_path / "ckpt"
    mgr = CheckpointManager(root, keep=3, sleep=NOSLEEP)
    try:
        mgr.save(1, _tree(1.0))
        assert mgr.wait(30)
        mgr.save(2, _tree(2.0))
        assert mgr.wait(30)
        # corrupt the newest checkpoint's array payload
        raw = bytearray((root / "step_00000002" / "arrays" / "w.npy")
                        .read_bytes())
        raw[-1] ^= 0xFF
        (root / "step_00000002" / "arrays" / "w.npy").write_bytes(raw)
        step, tree = mgr.restore_latest(_tree(0.0))
    finally:
        mgr.close()
    assert step == 1
    assert np.asarray(tree["b"]) == np.float32(1.0)
    assert obs.counter_value("ckpt.restore_fallback") == 1
    assert obs.counter_value("ckpt.restore_failed", type="OSError") > 0


def test_manager_writer_survives_persistent_write_fault(tmp_path,
                                                        obs_enabled):
    root = tmp_path / "ckpt"
    mgr = CheckpointManager(root, sleep=NOSLEEP)
    try:
        mgr.save(1, _tree(1.0))
        assert mgr.wait(30)
        sched = faults.FaultSchedule(seed=0, sites={
            "ckpt.write": faults.SiteSpec(count=99, exc="OSError")})
        with faults.injecting(sched):
            mgr.save(2, _tree(2.0))
            assert mgr.wait(30)          # writer dropped the save, thread OK
        assert latest_step(root) == 1
        assert obs.counter_value("ckpt.write_failed", type="OSError") == 1
        mgr.save(3, _tree(3.0))          # thread still alive and writing
        assert mgr.wait(30)
        assert latest_step(root) == 3
    finally:
        mgr.close()
