"""repro.obs — tracing, metrics, profiler annotations, and the
zero-overhead off path."""
import dataclasses
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.core.dataflow import ConvWorkload
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.plan import (NetworkPlanner, PlannerOptions, execute_network,
                        from_layers)

SMALL_LAYOUTS = tuple(Layout.parse(s) for s in ("HWC_C32", "HWC_H32"))


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


def tiny_graph(n=2):
    wls = [ConvWorkload(name=f"t-l{i}", N=1, M=64, C=16 if i == 0 else 64,
                        P=8, Q=8, R=1, S=1) for i in range(n)]
    return from_layers(wls, name="tinyobs")


def tiny_plan(graph):
    opts = PlannerOptions(switch_modes=("rir",), layouts=SMALL_LAYOUTS,
                          parallel_dims=("C", "P", "Q"))
    return NetworkPlanner(graph, EvalConfig(), opts).plan()


# ------------------------------------------------------------------- spans
def test_span_nesting_depth_and_attrs(obs_enabled):
    with obs.span("outer", {"a": 1}) as outer:
        outer.set("b", 2)
        with obs.span("inner") as inner:
            inner.set("k", "v")
    evs = obs.events()
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner"}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["attrs"] == {"a": 1, "b": 2}
    assert by_name["inner"]["attrs"] == {"k": "v"}
    # the inner interval nests inside the outer one
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert all(e["dur"] >= 0 for e in evs)


def test_record_span_without_with(obs_enabled):
    t0 = obs.now_us()
    obs.record_span("manual", t0, {"step": 3})
    (e,) = obs.events()
    assert e["name"] == "manual" and e["attrs"] == {"step": 3}
    assert e["dur"] >= 0


def test_span_survives_exception(obs_enabled):
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in obs.events()] == ["boom"]
    assert obs_trace._depth() == 0, "depth leaked after exception"


# ----------------------------------------------------------------- metrics
def test_counter_aggregation_and_labels(obs_enabled):
    obs.inc_counter("c")
    obs.inc_counter("c", 2.5)
    obs.inc_counter("c", tier="mem")
    obs.inc_counter("c", tier="mem")
    assert obs.counter_value("c") == 3.5
    assert obs.counter_value("c", tier="mem") == 2.0
    # label order never splits a series
    obs.inc_counter("d", a=1, b=2)
    obs.inc_counter("d", b=2, a=1)
    assert obs.counter_value("d", b=2, a=1) == 2.0


def test_gauge_and_histogram(obs_enabled):
    obs.set_gauge("g", 1.0)
    obs.set_gauge("g", 7.0)
    assert obs.gauge_value("g") == 7.0
    for v in (3.0, 1.0, 2.0):
        obs.observe("h", v)
    st = obs.hist_stats("h")
    assert st["count"] == 3 and st["min"] == 1.0 and st["max"] == 3.0
    assert st["p50"] == 2.0
    assert obs.hist_samples("h") == [3.0, 1.0, 2.0]


# ------------------------------------------------------ flush / validation
def test_flush_roundtrip_and_schema(tmp_path, obs_enabled):
    with obs.span("s", {"plan_id": "abc"}):
        pass
    obs.inc_counter("n", 2)
    obs.observe("lat_ms", 1.5)
    obs.get_logger("t").info("hello %d", 7)
    p = obs.flush(tmp_path / "t.jsonl")
    evs = obs.read_trace(p)
    assert obs.validate_trace(evs) == []
    assert evs[0]["ev"] == "meta" and evs[0]["schema"] == obs.TRACE_SCHEMA
    kinds = {e["ev"] for e in evs}
    assert {"meta", "span", "log", "counter", "hist"} <= kinds
    (lg,) = [e for e in evs if e["ev"] == "log"]
    assert lg["msg"] == "hello 7" and lg["level"] == "info"


def test_validate_trace_catches_violations():
    assert obs.validate_trace([]) == ["empty trace"]
    bad = [{"ev": "meta", "schema": 99, "pid": 1},
           {"ev": "span", "name": "x", "ts": -1, "dur": 1, "tid": 0,
            "depth": 0, "attrs": {}},
           {"ev": "span", "name": "y"},
           {"ev": "wat"}]
    errs = obs.validate_trace(bad)
    assert len(errs) == 4
    assert any("schema" in e for e in errs)
    assert any("negative" in e for e in errs)
    assert any("missing" in e for e in errs)
    assert any("unknown event kind" in e for e in errs)


# ------------------------------------------------------- disabled == no-op
def test_disabled_path_allocates_no_events(obs_reset):
    assert not obs.enabled()
    n0 = len(obs.events())
    with obs.span("hot", None) as sp:
        sp.set("k", 1)
    obs.record_span("hot2", 0.0, {"x": 1})
    obs.inc_counter("c")
    obs.set_gauge("g", 1.0)
    obs.observe("h", 1.0)
    assert len(obs.events()) == n0 == 0
    assert obs_metrics.registry() == [{}, {}, {}]
    assert obs.counter_value("c") == 0.0


def test_disabled_span_is_shared_singleton(obs_reset):
    s1, s2 = obs.span("a"), obs.span("b", {"big": "dict"})
    assert s1 is s2 is obs.NULL_SPAN
    assert s1.set("k", 1) is obs.NULL_SPAN


def test_disabled_overhead_wall_time_guard(obs_reset):
    """200k disabled span+counter calls must stay trivially cheap (the
    instrumented hot paths run these per step/token).  2s is ~100x slack."""
    t0 = time.perf_counter()
    for _ in range(200_000):
        with obs.span("hot"):
            pass
        obs.inc_counter("c")
    elapsed = time.perf_counter() - t0
    assert len(obs.events()) == 0
    assert elapsed < 2.0, f"disabled obs path took {elapsed:.2f}s for 200k"


def test_reset_clears_state():
    obs.reset()
    obs.enable()
    with obs.span("x"):
        pass
    obs.inc_counter("c")
    obs.reset()
    assert not obs.enabled()
    assert obs.events() == []
    assert obs.counter_value("c") == 0.0


# ------------------------------------------------ profiler annotations
def _profile(tmp_path, body):
    """Run ``body`` under a JAX profiler session; return its ProfileData."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    return jax.profiler.ProfileData.from_file(str(xplane))


def _host_events(profile, name):
    return [ev for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_span_reaches_the_profiler_with_recording_off(tmp_path, obs_reset):
    import jax

    def body():
        with jax.profiler.TraceAnnotation("outer"):
            with obs.span("exec.step", {"step": 3, "layer": "conv1",
                                        "shape": (2, 2)}) as sp:
                sp.set("extra", 5)

    prof = _profile(tmp_path, body)
    assert obs.events() == [], "the JSONL recording was off"
    (outer,) = _host_events(prof, "outer")
    (ev,) = _host_events(prof, "exec.step")
    stats = dict(ev.stats)
    assert stats["step"] == 3 and stats["layer"] == "conv1"
    assert stats["extra"] == 5
    assert "shape" not in stats, "only scalar attrs ride along"
    assert outer.start_ns <= ev.start_ns
    assert ev.start_ns + ev.duration_ns <= outer.start_ns + outer.duration_ns


def test_span_records_and_annotates_when_both_on(tmp_path, obs_enabled):
    def body():
        with obs.span("serve.batch", {"batch": 2}):
            with obs.span("serve.wait"):
                pass

    prof = _profile(tmp_path, body)
    assert [e["name"] for e in obs.events()] == ["serve.wait", "serve.batch"]
    assert [e["depth"] for e in obs.events()] == [1, 0]
    (batch,) = _host_events(prof, "serve.batch")
    (wait,) = _host_events(prof, "serve.wait")
    assert dict(batch.stats)["batch"] == 2
    assert batch.start_ns <= wait.start_ns
    assert obs_trace._depth() == 0


def test_span_is_null_again_once_the_profiler_stops(tmp_path, obs_reset):
    seen = {}

    def body():
        seen["span"] = obs.span("x")
        seen["active"] = obs.active()

    _profile(tmp_path, body)
    assert seen["span"] is not obs.NULL_SPAN and seen["active"]
    assert not obs.enabled() and not obs.active()
    assert obs.span("x", {"a": 1}) is obs.NULL_SPAN
    assert len(obs.events()) == 0


# ------------------------------------------------------------------ measure
def test_measure_blocks_and_returns_result(obs_reset):
    import jax
    f = jax.jit(lambda a: a * 2.0)
    a = jnp.ones((64, 64), jnp.float32)
    out, secs = obs.measure(f, a)
    assert secs >= 0.0
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    # pure-python callables pass through
    out, secs = obs.measure(lambda: 41 + 1)
    assert out == 42 and secs >= 0.0


# -------------------------------------------------- executor instrumentation
def test_execute_network_bit_identical_and_traced(obs_reset):
    graph = tiny_graph(2)
    plan = tiny_plan(graph)
    from repro.core.workloads import init_graph_weights
    ws = init_graph_weights(list(graph.layers), seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)

    y_off = np.asarray(execute_network(plan, graph, x, ws))
    obs.enable()
    try:
        y_on = np.asarray(execute_network(plan, graph, x, ws))
        evs = list(obs.events())
    finally:
        obs.reset()
    assert (y_off == y_on).all(), "tracing changed numeric outputs"

    steps = [e for e in evs if e["name"] == "exec.step"]
    nets = [e for e in evs if e["name"] == "exec.network"]
    assert len(steps) == len(plan.steps) and len(nets) == 1
    for i, e in enumerate(steps):
        a = e["attrs"]
        assert a["plan_id"] == plan.plan_id
        assert a["graph_hash"] == plan.graph_hash
        assert a["schema_version"] == plan.version
        assert a["step"] == i
        assert a["layer"] == plan.steps[i].layer
    assert nets[0]["attrs"]["plan_id"] == plan.plan_id


def test_traced_execute_network_never_fences(obs_reset, monkeypatch):
    """Spans time host dispatch: no ``block_until_ready`` anywhere in a
    traced execution, and one ``exec.step`` per plan step, fused or not."""
    import jax
    graph = tiny_graph(3)
    plan = tiny_plan(graph)
    plan = dataclasses.replace(plan, steps=(
        dataclasses.replace(plan.steps[0], fused_with=1),) + plan.steps[1:])
    from repro.core.workloads import init_graph_weights
    ws = init_graph_weights(list(graph.layers), seed=0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y_off = np.asarray(execute_network(plan, graph, x, ws))

    fences = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: fences.append(1) or real(v))
    obs.enable()
    y_on = execute_network(plan, graph, x, ws)
    monkeypatch.undo()
    assert fences == []
    steps = [e for e in obs.events() if e["name"] == "exec.step"]
    assert [e["attrs"]["step"] for e in steps] == list(range(len(plan.steps)))
    assert [e["attrs"]["layer"] for e in steps] == \
        [s.layer for s in plan.steps]
    assert (np.asarray(y_on) == y_off).all()


# ------------------------------------------------------------------ planner
def test_planner_spans_and_gauges(obs_enabled):
    tiny_plan(tiny_graph(2))
    names = [e["name"] for e in obs.events()]
    for want in ("planner.plan", "planner.lattice_build", "planner.dp_extend",
                 "planner.argmin"):
        assert want in names, f"missing {want}"
    assert obs.gauge_value("planner.layers") == 2
    assert obs.gauge_value("planner.lattice_points") > 0
    (root,) = [e for e in obs.events() if e["name"] == "planner.plan"]
    assert root["attrs"]["graph"] == "tinyobs"
    assert "plan_id" in root["attrs"]


def test_plans_identical_with_tracing_on_and_off(obs_reset):
    graph = tiny_graph(2)
    off = tiny_plan(graph).to_json()
    obs.enable()
    try:
        on = tiny_plan(graph).to_json()
    finally:
        obs.reset()
    assert on == off, "instrumentation changed the planned artifact"


# ------------------------------------------------------------------- logger
def test_logger_level_filter_and_lazy_format(obs_reset, capsys):
    log = obs.get_logger("t")
    obs.set_level("warning")
    try:
        class Boom:
            def __str__(self):
                raise AssertionError("formatted a suppressed record")
        log.info("nope %s", Boom())
        log.warning("yes %d", 2, path="/x")
    finally:
        obs.set_level("info")
    out = capsys.readouterr().out
    assert "nope" not in out
    assert "[t] yes 2 path=/x" in out


def test_train_supervisor_fault_counters(obs_enabled):
    from repro.runtime.fault_tolerance import TrainSupervisor
    calls = []

    def step_fn(step):
        if step == 1 and len(calls) < 2:
            calls.append(1)
            raise RuntimeError("chip fell over")
        return {"loss": 0.0}

    sup = TrainSupervisor(
        total_steps=3, step_fn=step_fn, save_every=10,
        save_fn=lambda s: None, restore_fn=lambda: 1,
        failure_detector=lambda: False, restart_fn=lambda: None)
    restarts, history = sup.run()
    assert restarts == 2 and len(history) == 3
    assert obs.counter_value("train.faults", type="RuntimeError") == 2
    assert obs.counter_value("train.restarts", cause="fault") == 2
    assert obs.counter_value("train.restarts", cause="detector") == 0
