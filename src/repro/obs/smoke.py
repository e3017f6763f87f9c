"""End-to-end observability smoke: plan + execute a network with tracing on.

    PYTHONPATH=src python -m repro.obs.smoke [--graph tiny|resnet50|mobv3]
        [--out trace.jsonl] [--check-identical]

Plans a network, executes it through the Pallas path with the JSONL
recording on, flushes the trace and validates it against the trace schema.
Exits non-zero on any schema violation or on a trace missing the spans the
instrumentation promises (planner phases, cache counters, the
``exec.network`` dispatch), and on a program that lacks one ``exec.step``
scope per layer, in order.  ``--check-identical`` additionally re-executes with tracing off
and asserts the numeric outputs are bit-identical — tracing must observe,
never perturb.

This is the CI tier-1 smoke; the push-to-main job runs it with
``--graph resnet50`` and uploads the trace next to BENCH_*.json.
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional

import numpy as np


def build_graph(name: str):
    from repro.api import from_layers, mobilenet_v3_graph, resnet50_graph
    from repro.core.dataflow import ConvWorkload
    if name == "resnet50":
        return resnet50_graph()
    if name == "mobv3":
        return mobilenet_v3_graph()
    wls = [ConvWorkload(name=f"tiny-l{i}", N=1, M=128, C=16 if i == 0
                        else 128, P=8, Q=8, R=1, S=1, stride=1)
           for i in range(3)]
    return from_layers(wls, name="tiny")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs.smoke")
    ap.add_argument("--graph", default="tiny",
                    choices=["tiny", "resnet50", "mobv3"])
    ap.add_argument("--out", default="trace-smoke.jsonl")
    ap.add_argument("--check-identical", action="store_true",
                    help="re-execute with tracing off and assert "
                    "bit-identical outputs")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from repro import obs
    from repro.api import (EvalConfig, Layout, PlanCache, PlannerOptions,
                           execute_network, plan_network, prepare_network)
    from repro.plan.executor import step_scopes
    from repro.core.workloads import init_graph_weights

    graph = build_graph(args.graph)
    layouts = tuple(Layout.parse(s) for s in ("HWC_C32", "HWC_H32"))
    opts = PlannerOptions(switch_modes=("rir",), layouts=layouts,
                          parallel_dims=("C", "P", "Q"))
    cfg = EvalConfig()

    obs.reset()
    obs.enable(args.out)
    cache = PlanCache()
    plan = cache.get_or_plan(
        graph, cfg, lambda g, c: plan_network(g, c, opts=opts),
        extra_key=opts.key())
    # a second lookup exercises the hit counter
    assert cache.get_or_plan(
        graph, cfg, lambda g, c: plan_network(g, c, opts=opts),
        extra_key=opts.key()) is plan

    ws = init_graph_weights(list(graph.layers), seed=0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=graph.input_shape()), jnp.float32)
    y_on = np.asarray(execute_network(plan, graph, x, ws))
    path = obs.flush()
    obs.disable()

    if args.check_identical:
        y_off = np.asarray(execute_network(plan, graph, x, ws))
        if not (y_on == y_off).all():
            print("[smoke] FAIL: outputs differ with tracing on vs off",
                  file=sys.stderr)
            return 1

    events = obs.read_trace(path)
    errors = obs.validate_trace(events)
    spans = {e["name"] for e in events if e.get("ev") == "span"}
    counters = {e["name"] for e in events if e.get("ev") == "counter"}
    prepared = prepare_network(plan, graph, ws)
    scopes = step_scopes(prepared.program().lower(
        prepared.arrays, x).as_text(debug_info=True))
    n_steps = len(scopes)
    for want in ("planner.plan", "planner.lattice_build", "planner.dp_extend",
                 "planner.argmin", "exec.network", "plan_cache.plan"):
        if want not in spans:
            errors.append(f"missing span {want!r}")
    for want in ("plan_cache.miss", "plan_cache.hit{tier=mem}",
                 "planner.lattice_builds"):
        if want not in counters:
            errors.append(f"missing counter {want!r}")
    if scopes != list(range(len(plan.steps))):
        errors.append(f"exec.step scopes {scopes} for "
                      f"{len(plan.steps)}-step plan")
    if errors:
        for err in errors:
            print(f"[smoke] FAIL: {err}", file=sys.stderr)
        return 1

    print(f"[smoke] ok: {len(events)} events -> {path} "
          f"(graph={graph.name}, {n_steps} steps"
          + (", outputs bit-identical on/off" if args.check_identical
             else "") + ")")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
