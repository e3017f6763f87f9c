"""Concurrency suite for the continuous-batching serve engine.

The claims under test, per the serving contract:

* dynamic batch assembly is invisible — a request's output is bit-identical
  whether it was served alone, padded, or packed with strangers, across
  ragged batch sizes;
* backpressure is typed and non-blocking — a full queue (or an injected
  ``serve.queue`` admission fault) raises ``QueueFullError`` immediately and
  the engine never deadlocks its clients;
* degraded plans heal in the background — an engine built while the planner
  is down serves at a degraded tier, then upgrades to tier 1 without the
  serving loop ever blocking, observable through ``degrade.tier`` /
  ``serve.plan_upgrade`` counters;
* the ``repro.api`` facade is the importable, keyword-only stable surface.
"""
from __future__ import annotations

import inspect
import threading

import numpy as np
import pytest

from repro import api, obs
from repro.api import (EvalConfig, PlanCache, PlannerOptions, QueueFullError,
                       ServeConfig, ServeEngine, resolve_plan)
from repro.runtime import faults
from repro.serve.engine import ServeError


def _nosleep(_s: float) -> None:
    return None


@pytest.fixture(autouse=True)
def _tracing(tmp_path):
    """Counters/histograms are strict no-ops with tracing off; every test
    here reads them, so run traced against a throwaway file."""
    obs.reset()
    obs.enable(str(tmp_path / "serve-test-trace.jsonl"))
    yield
    obs.disable()


@pytest.fixture(scope="module")
def cache():
    """One warm PlanCache for the whole module: the tiny graph is planned
    once, every engine after that resolves at tier 0."""
    return PlanCache()


def _samples(eng, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(eng.sample_shape).astype(np.float32)
            for _ in range(n)]


# ---------------------------------------------------------------- config
def test_config_validates_mode_and_bounds():
    with pytest.raises(ValueError):
        ServeConfig()                                   # neither mode
    with pytest.raises(ValueError):
        ServeConfig(arch="llama3p2_3b", graph="tiny")   # both modes
    with pytest.raises(ValueError):
        ServeConfig(graph="nope")
    with pytest.raises(ValueError):
        ServeConfig(graph="tiny", max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(graph="tiny", max_batch=4, assemble_max=5)
    assert ServeConfig(graph="tiny", max_batch=4).batch_limit == 4
    assert ServeConfig(graph="tiny", max_batch=4,
                       assemble_max=1).batch_limit == 1


def test_config_cli_roundtrip():
    import argparse

    ap = argparse.ArgumentParser()
    ServeConfig.add_args(ap)
    cfg = ServeConfig.from_args(ap.parse_args(
        ["--graph", "tiny", "--batch", "8", "--workers", "2",
         "--queue-capacity", "5"]))
    assert (cfg.graph, cfg.max_batch, cfg.workers, cfg.queue_capacity) == \
        ("tiny", 8, 2, 5)
    # LM serving is the default when neither mode flag is given
    lm = ServeConfig.from_args(ap.parse_args([]))
    assert lm.arch == "llama3p2_3b" and lm.graph is None


# ------------------------------------------------- batching bit-identity
def test_batched_identical_to_sequential_across_ragged_sizes(cache):
    cfg = ServeConfig(graph="tiny", max_batch=4, workers=2,
                      queue_capacity=32)
    seq_cfg = ServeConfig(graph="tiny", max_batch=4, workers=1,
                          assemble_max=1, queue_capacity=32)
    with ServeEngine(cfg, cache=cache) as eng, \
            ServeEngine(seq_cfg, cache=cache) as seq:
        for k in (1, 2, 3, 4, 5, 11):   # under, at, and over the extent
            samples = _samples(eng, k, seed=k)
            got = eng.serve(samples)
            ref = seq.serve(samples)
            for i, (a, b) in enumerate(zip(got, ref)):
                assert np.array_equal(a, b), (k, i)


def test_execute_requests_matches_full_batch(cache):
    """The PreparedNetwork batch hooks themselves: k padded samples produce
    exactly the first k rows of the padded batch execution."""
    import jax.numpy as jnp

    from repro.api import prepare_network
    from repro.core.workloads import init_graph_weights
    from repro.obs.smoke import build_graph

    graph = build_graph("tiny").with_batch(4)
    opts = PlannerOptions(switch_modes=("rir",),
                          layouts=tuple(api.Layout.parse(s) for s in
                                        ("HWC_C32", "HWC_H32")),
                          parallel_dims=("C", "P", "Q"))
    plan = resolve_plan(graph, EvalConfig(), opts=opts, cache=cache).plan
    ws = init_graph_weights(list(graph.layers), seed=0)
    prepared = prepare_network(plan, graph, ws)
    assert prepared.max_batch == 4
    rng = np.random.default_rng(3)
    samples = [jnp.asarray(rng.standard_normal(prepared.input_shape[1:]),
                           jnp.float32) for _ in range(3)]
    outs = prepared.execute_requests(samples)
    full = prepared(prepared.assemble_batch(samples))
    for i, o in enumerate(outs):
        assert np.array_equal(np.asarray(o), np.asarray(full[i]))
    with pytest.raises(ValueError):
        prepared.assemble_batch(samples * 2)        # 6 > max_batch
    with pytest.raises(ValueError):
        prepared.assemble_batch([])


# -------------------------------------------------------- backpressure
def test_queue_full_is_typed_and_never_deadlocks(cache):
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1,
                      queue_capacity=2)
    with ServeEngine(cfg, cache=cache) as eng:
        release = threading.Event()
        real_launch = eng._backend.launch

        def stalled_launch(prepared, payloads):
            assert release.wait(30.0), "test released too late"
            return real_launch(prepared, payloads)

        eng._backend.launch = stalled_launch
        tickets, rejected = [], 0
        for i in range(cfg.queue_capacity + cfg.max_batch + 4):
            try:
                tickets.append(eng.submit(_samples(eng, 1, seed=i)[0]))
            except QueueFullError as e:
                assert e.reason == "capacity"
                rejected += 1
        assert rejected >= 1, "bounded queue never pushed back"
        assert obs.counter_value("serve.rejected", reason="capacity") >= 1
        release.set()
        for t in tickets:               # admitted requests all complete
            t.result(timeout=30.0)


def test_admission_fault_is_typed_rejection(cache):
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1,
                      queue_capacity=8)
    schedule = faults.FaultSchedule(seed=0, sites={
        "serve.queue": faults.SiteSpec(count=2, exc="ConnectionError")})
    with ServeEngine(cfg, cache=cache) as eng:
        sample = _samples(eng, 1)[0]
        with faults.injecting(schedule):
            for _ in range(2):
                with pytest.raises(QueueFullError) as ei:
                    eng.submit(sample)
                assert ei.value.reason == "fault"
            out = eng.submit(sample).result(timeout=30.0)   # schedule spent
    assert schedule.all_fired()
    assert out is not None and np.isfinite(out).all()


def test_stopped_engine_rejects_and_fails_stranded_tickets(cache):
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1)
    eng = ServeEngine(cfg, cache=cache)
    with pytest.raises(QueueFullError) as ei:
        eng.submit(np.zeros(eng.sample_shape, np.float32))   # never started
    assert ei.value.reason == "stopped"
    eng.start()
    with pytest.raises(ServeError):
        eng.submit(np.zeros((3,), np.float32))               # bad shape
    eng.stop()
    with pytest.raises(QueueFullError):
        eng.submit(np.zeros(eng.sample_shape, np.float32))


# --------------------------------------------------- background upgrade
def test_degraded_engine_upgrades_in_background(cache):
    # the planner is "down": every tier-1 attempt (3 retries) faults, so
    # the ladder descends to greedy; the admission path keeps working
    down = faults.FaultSchedule(seed=0, sites={
        "plan.replan": faults.SiteSpec(count=3, exc="RuntimeError")})
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1,
                      upgrade_interval_s=0.01, queue_capacity=8,
                      layouts=("HWC_C32",))   # distinct opts: its own cache key
    up0 = obs.counter_value("serve.plan_upgrade")
    t1_0 = obs.counter_value("degrade.tier", level="replanned")
    with faults.injecting(down):
        eng = ServeEngine(cfg, cache=cache, sleep=_nosleep)
        assert eng.resolved.tier == 2 and eng.resolved.tier_name == "greedy"
        assert "replanned: RuntimeError" in eng.resolved.reason
    assert down.all_fired()
    with eng:
        samples = _samples(eng, 3)
        degraded_outs = eng.serve(samples)
        deadline = threading.Event()
        for _ in range(3000):           # planner recovered; poll the swap
            if eng.resolved.tier <= 1:
                break
            deadline.wait(0.01)
        assert eng.resolved.tier == 1, "background upgrade never landed"
        assert eng.resolved.reason == ""
        upgraded_outs = eng.serve(samples)
    assert obs.counter_value("serve.plan_upgrade") == up0 + 1
    assert obs.counter_value("degrade.tier", level="replanned") > t1_0
    # greedy and full plans may differ; both must be valid executions of
    # the same network on the same weights
    for a, b in zip(degraded_outs, upgraded_outs):
        assert a.shape == b.shape and np.isfinite(a).all()


def test_upgraded_plan_is_compiled_before_swap(cache, monkeypatch):
    """The upgrader compiles the new plan's program before the swap, so the
    batches served after it trace nothing."""
    from repro.plan import PreparedNetwork
    traces = {}
    real = PreparedNetwork._forward

    def counting(self, *a):
        traces[id(self)] = traces.get(id(self), 0) + 1
        return real(self, *a)

    monkeypatch.setattr(PreparedNetwork, "_forward", counting)
    down = faults.FaultSchedule(seed=0, sites={
        "plan.replan": faults.SiteSpec(count=3, exc="RuntimeError")})
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1,
                      upgrade_interval_s=0.01, queue_capacity=8,
                      layouts=("HWC_H32",))   # distinct opts: its own key
    with faults.injecting(down):
        eng = ServeEngine(cfg, cache=cache, sleep=_nosleep)
    assert eng.resolved.degraded
    with eng:
        wait = threading.Event()
        for _ in range(3000):           # no request in flight: poll the swap
            if eng.resolved.tier <= 1:
                break
            wait.wait(0.01)
        assert eng.resolved.tier == 1, "background upgrade never landed"
        with eng._swap_lock:
            upgraded = eng._prepared
        assert traces.get(id(upgraded)) == 1, "swapped in uncompiled"
        outs = eng.serve(_samples(eng, 3))
    assert traces[id(upgraded)] == 1
    assert all(np.isfinite(o).all() for o in outs)


# ------------------------------------------------------ reason + spans
def test_resolved_plan_reason_records_ladder_descent():
    from repro.obs.smoke import build_graph

    graph = build_graph("tiny")
    opts = PlannerOptions(switch_modes=("rir",), parallel_dims=("C", "P", "Q"))

    def boom(*_a, **_k):
        raise ValueError("planner bug")

    r = resolve_plan(graph, EvalConfig(), opts=opts, planner_fn=boom,
                     greedy_fn=boom, sleep=_nosleep)
    assert r.tier == 3 and r.degraded
    assert "replanned: ValueError: planner bug" in r.reason
    assert "greedy: ValueError: planner bug" in r.reason

    rd = resolve_plan(graph, EvalConfig(), opts=opts, deadline_s=0.0,
                      sleep=_nosleep)
    assert rd.tier == 3
    assert rd.reason == ("replanned: deadline exceeded; "
                         "greedy: deadline exceeded")

    ok = resolve_plan(graph, EvalConfig(), opts=opts, sleep=_nosleep)
    assert ok.tier == 1 and ok.reason == "" and not ok.degraded


def test_serve_batch_span_carries_plan_attrs(cache):
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1)
    with ServeEngine(cfg, cache=cache) as eng:
        eng.serve(_samples(eng, 2))
        plan_id = eng.resolved.plan.plan_id
    spans = [e for e in obs.events()
             if e.get("ev") == "span" and e["name"] == "serve.batch"]
    assert spans, "no serve.batch span recorded"
    attrs = spans[-1]["attrs"]
    assert attrs["plan_id"] == plan_id
    assert attrs["plan_tier"] in ("cached", "replanned")
    assert attrs["plan_reason"] == ""
    assert obs.counter_value("serve.batches") >= 1
    assert len(obs.hist_samples("serve.e2e_ms")) >= 2


def test_served_batch_host_phases_nest_in_serve_batch(cache):
    """Each served batch emits each host phase once, in order, on the
    worker's thread and inside that batch's ``serve.batch`` interval; the
    intervals of neighbouring batches may overlap (the pipeline)."""
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1, queue_capacity=16)
    with ServeEngine(cfg, cache=cache) as eng:
        obs.reset()
        obs.enable()
        with _stalled_first_launch(eng) as (started, release):
            tickets = [eng.submit(s) for s in _samples(eng, 1)]
            assert started.wait(30.0)
            tickets += [eng.submit(s) for s in _samples(eng, 6, seed=1)]
            release.set()
            for t in tickets:
                t.result(timeout=30.0)
    spans = [e for e in obs.events() if e.get("ev") == "span"]
    batches = sorted((e for e in spans if e["name"] == "serve.batch"),
                     key=lambda e: e["ts"])
    assert len(batches) == 4
    phases = ("exec.assemble", "exec.network", "exec.split", "serve.wait",
              "serve.fetch")
    by_phase = {p: sorted((e for e in spans if e["name"] == p),
                          key=lambda e: e["ts"]) for p in phases}
    for p in phases:
        assert len(by_phase[p]) == len(batches), p
    for i, batch in enumerate(batches):
        got = [by_phase[p][i] for p in phases]
        assert [e["ts"] for e in got] == sorted(e["ts"] for e in got)
        end = batch["ts"] + batch["dur"]
        for e in got:
            assert e["tid"] == batch["tid"]
            assert batch["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end


# ------------------------------------------------------------- pipeline
class _stalled_first_launch:
    """Hold the worker in its first launch until released, so what is
    submitted meanwhile queues up as full batches."""

    def __init__(self, eng):
        self.eng = eng
        self.started = threading.Event()
        self.release = threading.Event()

    def __enter__(self):
        real = self.eng._backend.launch
        calls = []

        def launch(prepared, payloads):
            calls.append(1)
            if len(calls) == 1:
                self.started.set()
                assert self.release.wait(30.0), "test released too late"
            return real(prepared, payloads)

        self.eng._backend.launch = launch
        return self.started, self.release

    def __exit__(self, *exc):
        self.release.set()
        return False


def test_queued_batches_overlap_and_match_serving_alone(cache):
    cfg = ServeConfig(graph="tiny", max_batch=4, workers=1, queue_capacity=32)
    with ServeEngine(cfg, cache=cache) as eng:
        samples = _samples(eng, 17, seed=5)
        with _stalled_first_launch(eng) as (started, release):
            tickets = [eng.submit(samples[0])]
            assert started.wait(30.0)
            tickets += [eng.submit(s) for s in samples[1:]]   # 4 full batches
            release.set()
            got = [t.result(timeout=30.0) for t in tickets]
        assert obs.counter_value("serve.batches_overlapped") >= 1
        for i, s in enumerate(samples):
            (alone,) = eng.serve([s])
            assert np.array_equal(got[i], alone), i


def test_lone_request_is_served_without_overlap(cache):
    cfg = ServeConfig(graph="tiny", max_batch=4, workers=1)
    with ServeEngine(cfg, cache=cache) as eng:
        out = eng.submit(_samples(eng, 1)[0]).result(timeout=30.0)
    assert out is not None and np.isfinite(out).all()
    assert obs.counter_value("serve.batches") == 1
    assert obs.counter_value("serve.batches_overlapped") == 0


def test_failed_launch_fails_its_batch_alone(cache, monkeypatch):
    from repro.plan.executor import PreparedNetwork

    real = PreparedNetwork.execute_requests
    started, release = threading.Event(), threading.Event()
    calls = []

    def flaky(self, samples, **kw):
        calls.append(1)
        if len(calls) == 1:
            started.set()
            assert release.wait(30.0), "test released too late"
        if len(calls) == 2:
            raise RuntimeError("planted launch fault")
        return real(self, samples, **kw)

    monkeypatch.setattr(PreparedNetwork, "execute_requests", flaky)
    cfg = ServeConfig(graph="tiny", max_batch=4, workers=1, queue_capacity=16)
    with ServeEngine(cfg, cache=cache) as eng:
        samples = _samples(eng, 9, seed=7)
        first = eng.submit(samples[0])
        assert started.wait(30.0)
        second = [eng.submit(s) for s in samples[1:5]]
        third = [eng.submit(s) for s in samples[5:]]
        release.set()
        assert np.isfinite(first.result(timeout=30.0)).all()
        for t in second:
            with pytest.raises(RuntimeError, match="planted launch fault"):
                t.result(timeout=30.0)
        for t in third:
            assert np.isfinite(t.result(timeout=30.0)).all()
    assert obs.counter_value("serve.batch_failed", type="RuntimeError") == 1
    assert obs.counter_value("serve.batches") == 2


def test_stop_with_a_batch_in_flight_resolves_every_ticket(cache):
    cfg = ServeConfig(graph="tiny", max_batch=2, workers=1, queue_capacity=16)
    eng = ServeEngine(cfg, cache=cache).start()
    real = eng._backend.launch
    second, release = threading.Event(), threading.Event()
    calls = []

    def launch(prepared, payloads):
        calls.append(1)
        if len(calls) == 2:      # the first batch is launched, unfinished
            second.set()
            assert release.wait(30.0), "test released too late"
        return real(prepared, payloads)

    eng._backend.launch = launch
    tickets = [eng.submit(s) for s in _samples(eng, 8, seed=9)]
    assert second.wait(30.0)
    stopper = threading.Thread(target=eng.stop)
    stopper.start()
    assert eng._stop.wait(30.0)
    release.set()
    stopper.join(timeout=60.0)
    assert not stopper.is_alive()
    for t in tickets:
        assert t.done()
    assert np.isfinite(tickets[0].result(timeout=0)).all()
    for t in tickets[1:]:
        try:
            assert np.isfinite(t.result(timeout=0)).all()
        except ServeError:
            pass        # still queued at the stop


# -------------------------------------------------------------- facade
def test_api_surface_complete_and_keyword_only():
    for name in api.__all__:
        assert hasattr(api, name), f"repro.api.{name} missing"
    for fn_name in ("plan_network", "resolve_plan", "upgrade_plan",
                    "execute_network"):
        sig = inspect.signature(getattr(api, fn_name))
        bad = [p.name for p in sig.parameters.values()
               if p.kind == p.POSITIONAL_OR_KEYWORD and p.default
               is not p.empty]
        assert not bad, f"{fn_name}: optional params must be keyword-only " \
                        f"(got {bad})"


def test_api_deprecation_warns_once():
    api._warned.discard("test.legacy")
    api.warn_deprecated("test.legacy", "the_new_name")
    api.warn_deprecated("test.legacy", "the_new_name")   # second is a no-op
    assert "test.legacy" in api._warned
