"""The served-LM system: a decoder LM served by ``repro.api.ServeEngine``'s
LM backend, tensor-parallel over ``serve.model_axis`` chips.

A configuration names it with ``"system": "lm_serve"`` and gives the
program's ``arch``, the widths it is served at (``model``, which must be
the program's own), ``serve`` settings, ``precision`` (bfloat16) and one
``check`` entry, ``max_logit_deficit``.  The traffic mix gives each
request's ``prompt_len`` and the ``gen`` tokens generated for it; ``start``
records the two in ``config["lengths"]``, where ``work`` reads them, so
``work`` counts only after ``start``.  A request is a seeded prompt of
token ids drawn uniformly from the vocabulary (there is no tokenizer); the
program's answer is the ``gen`` tokens it chose greedily.  The weights are
drawn here from the seed (``lm_reference.draw_params``, in the program's
layout) and handed to the engine, and ``bench/lm_reference.py`` reads the
same arrays once the engine is freed.  The number compared is
``lm_reference.deficit``.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

import lm_reference
import work as work_mod

BF16 = 2


def _program_widths(cfg) -> dict:
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "rope_theta": cfg.rope_theta}


def start(config, mix, seed, chips, plan_dir):
    """The engine over ``chips`` chips (``serve.model_axis`` of them, or
    the run is refused), ``mix["pool"]`` seeded prompts, and the weights
    drawn from the seed that the engine serves."""
    from repro.api import ServeConfig, ServeEngine
    from repro.configs import get_config

    serve = config["serve"]
    axis = int(serve["model_axis"])
    if chips != axis:
        raise work_mod.SetupError(
            f"the configuration is served over model_axis={axis} chips; "
            f"the cell gives {chips}")
    cfg = get_config(config["arch"])
    program = _program_widths(cfg)
    stated = {k: config["model"][k] for k in program}
    if program != stated or cfg.dtype != "bfloat16":
        raise work_mod.SetupError(
            f"the program's {config['arch']} ({program}, {cfg.dtype}) is "
            f"not the configuration's ({stated}, bfloat16)")
    prompt_len, gen = int(mix["prompt_len"]), int(mix["gen"])
    config["lengths"] = {"prompt_len": prompt_len, "gen": gen}
    rng = np.random.default_rng(seed % 2 ** 64)
    # PRNGKey keeps only the low 32 bits of a seed: draw the weights' seed
    key = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
    pool = list(rng.integers(0, cfg.vocab, (int(mix["pool"]), prompt_len),
                             dtype=np.int32))
    draw = functools.partial(lm_reference.draw_params,
                             lm_reference.dims_of(config["model"]))
    drawn = {}

    def weights(shardings):
        drawn["params"] = jax.jit(draw, out_shardings=shardings)(key)
        return drawn["params"]

    eng = ServeEngine(ServeConfig(
        arch=config["arch"], max_batch=int(serve["max_batch"]),
        prompt_len=prompt_len, gen=gen, model_axis=axis,
        queue_capacity=int(serve["queue_capacity"]), plan=None),
        weights=weights)
    _, params, mesh = eng.lm
    if params is not drawn.get("params"):
        raise work_mod.SetupError(
            "the program does not serve the weights it was given")
    facts = {"arch": cfg.name, "model_axis": axis,
             "mesh_devices": int(mesh.devices.size),
             "params": int(sum(a.size for a in jax.tree.leaves(params)))}
    return eng, pool, params, facts


def reference(config, inputs, outputs, weights, precision):
    """``lm_reference.reference`` over each sampled prompt and the tokens
    served for it, in blocks of ``max_batch`` requests."""
    return lm_reference.reference(
        weights, lm_reference.dims_of(config["model"]), inputs, outputs,
        int(config["serve"]["max_batch"]), precision)


def gap(config, out, ref):
    return lm_reference.deficit(out, ref)


def control_precision(config):
    """fp8 e4m3, the step below the bfloat16 the configuration states."""
    if not config["precision"].startswith("bfloat16"):
        raise work_mod.SetupError(
            f"no control below precision {config['precision']!r}")
    return "fp8_e4m3"


def work(config):
    """One request's work at the lengths ``start`` recorded, counted from
    the widths, in bfloat16:

    * FLOPs: 2 x the layers' matrix parameters at each forward position
      (the prompt and every served token but the last), 2 x D x V at each
      position whose logits choose a token, and the attention's QK^T and
      PV over the positions each query may see (causal in prefill, the
      filled cache in decode);
    * activation bytes: each forward position's embedding row gathered and
      K and V written to the cache once, and each decode step's read of
      the cache's filled positions;
    * weight bytes (a batch): every weight but the embedding table read
      once a forward pass, the prefill and each of the ``gen - 1`` decode
      steps.
    """
    if "lengths" not in config:
        raise work_mod.SetupError("work counts at the mix's lengths, which "
                                  "start records: start the system first")
    m, lengths = config["model"], config["lengths"]
    D, L, F, V = m["d_model"], m["n_layers"], m["d_ff"], m["vocab"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    P, G = lengths["prompt_len"], lengths["gen"]
    per_layer = D * qd + D * 2 * kvd + qd * D + 2 * D * F
    positions = P + G - 1
    # keys each query sees: 1..P in prefill, P+1..P+G-1 in decode
    prefill_pairs = P * (P + 1) // 2
    decode_pairs = sum(range(P + 1, P + G))
    flops = (2.0 * L * per_layer * positions + 2.0 * D * V * G
             + 4.0 * L * qd * (prefill_pairs + decode_pairs))
    kv_row = L * 2 * kvd * BF16          # one position's K and V, all layers
    act = positions * (D * BF16 + kv_row) + decode_pairs * kv_row
    weights = (L * (per_layer + 4 * D) + D * V + 2 * D) * BF16
    return work_mod.Work(flops=flops, act_bytes=float(act),
                         weight_bytes=float(G * weights))
