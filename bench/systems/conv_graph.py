"""The conv-graph system: a planned layer graph served by
``repro.api.ServeEngine`` on the first device.

A configuration without a ``"system"`` key is served by this module.  It
reads the configuration's ``layers`` (Table 1 shapes), ``skip_edges``,
``input`` image, ``serve`` settings and ``precision``; weights and
requests come from ``reference.py``'s seeded generators, and the plan is
cached in the directory the harness gives.  A plan that the degradation
ladder could not produce in full is a different system: the run is
refused.  The number compared is ``reference.rel_err``, under the
configuration's ``check.max_rel_err``.
"""
from __future__ import annotations

import numpy as np

import reference as ref_mod
import work as work_mod

# one step below each precision a configuration may state
CONTROL_PRECISION = {"float32, highest": "bf16_3x"}


def _engine(config, weights, plan_dir):
    from repro.api import PlanCache, ServeConfig, ServeEngine, from_layers
    from repro.core.dataflow import ConvWorkload

    layers = [ConvWorkload(N=1, M=l["M"], C=1 if l.get("depthwise") else l["C"],
                           P=l["P"], Q=l["Q"], R=l["R"], S=l["S"],
                           stride=l["stride"], name=l["name"])
              for l in config["layers"]]
    graph = from_layers(layers, name=config["name"],
                        skip_edges=[tuple(e) for e in config["skip_edges"]])
    serve = config["serve"]
    sc = ServeConfig(graph=serve["graph"], max_batch=int(serve["max_batch"]),
                     queue_capacity=int(serve["queue_capacity"]),
                     plan_deadline=900.0)
    eng = ServeEngine(sc, cache=PlanCache(plan_dir), graph=graph,
                      weights=weights)
    if eng.resolved.degraded:
        raise work_mod.SetupError(
            f"plan resolved at tier {eng.resolved.tier_name} "
            f"({eng.resolved.reason}); a degraded plan is a different system")
    return eng


def start(config, mix, seed, chips, plan_dir):
    """Weights and ``mix["pool"]`` request images from ``seed``, and the
    engine over them (not yet started).  ``chips`` is unused: the engine
    runs on the first device."""
    layers = config["layers"]
    weights = ref_mod.init_weights(layers, seed)
    pool = ref_mod.make_inputs(layers, seed, int(mix["pool"]),
                               image=config.get("input"))
    eng = _engine(config, weights, plan_dir)
    facts = {"plan_tier": eng.resolved.tier_name,
             "plan_id": eng.resolved.plan.plan_id}
    return eng, list(pool), weights, facts


def reference(config, inputs, outputs, weights, precision):
    """The plain reference's outputs for ``inputs``, in blocks of
    ``max_batch`` rows; an image's reference does not depend on what was
    served (``outputs``)."""
    return ref_mod.reference_outputs(
        config["layers"], config["skip_edges"], np.stack(inputs), weights,
        int(config["serve"]["max_batch"]), precision=precision)


def gap(config, out, ref):
    return ref_mod.rel_err(out, ref)


def control_precision(config):
    return CONTROL_PRECISION[config["precision"]]


def work(config):
    return work_mod.network_work(config["layers"])
