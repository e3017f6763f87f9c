#!/usr/bin/env python3
"""Chip smoke: serve the main paths once on a TPU and check what comes out.

    python chip_smoke.py [--seed N] [--out DIR]   # one chip: both phases
    python chip_smoke.py --chips 4                # four chips: sharded LM only

Everything goes through ``repro.api.ServeEngine``, the path
``python -m repro.launch.serve`` takes, and is built from ``--seed``:

* **network** — ResNet-50 at its widths, planned fresh (tier ``cached`` or
  ``replanned``; a plan the ladder degraded fails the run), served on the
  Pallas path at ``max_batch`` 4: requests alone and packed.  Every output
  is compared with ``execute_network_reference`` under
  ``default_matmul_precision("highest")``; packed outputs are compared
  bit for bit with the same requests served alone.
* **lm** — llama3.2-3B at full width (bf16), 4 requests of 128 prompt
  tokens, 16 generated.  Decode runs the compiled ``gqa_decode``; the first
  decode step's logits are compared with the same step through the
  ``kernels/ref`` attention.
* **lm4** (``--chips 4``) — the same LM with ``model_axis=4``: parameters
  split over four chips by the ``distributed.sharding`` rules, compared
  with the same requests served on one of those chips: prefill and first
  decode logits (fed the same tokens) within the LM tolerance, and greedy
  tokens of those two steps equal wherever the one-chip top-2 gap exceeds
  what that tolerance allows.

A host without a TPU exits non-zero before any work.  Per-phase lines go
to stdout; the last line is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed.  A JSON report lands in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

HERE = pathlib.Path(__file__).resolve().parent

# Tolerances, fixed before any chip run.  Network: float32 operands with a
# float32-exact contraction, so the largest error over the largest
# reference magnitude stays ~1e-6..1e-5; a single bf16 pass (~4e-3) fails.
NET_REL_TOL = 1e-4
# LM: bf16 weights and activations through 28 layers; kernel and reference
# attention differ by a few bf16 ulps per layer (2^-8 = 3.9e-3 relative).
LM_REL_TOL = 3e-2


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                    1e-30))


def _line(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _cache_entries(path: str) -> int:
    p = pathlib.Path(path)
    return sum(1 for f in p.rglob("*") if f.is_file()) if p.is_dir() else 0


# ----------------------------------------------------------------- network
def network_phase(seed: int, out_dir: pathlib.Path) -> dict:
    import jax
    import numpy as np

    from repro import obs
    from repro.api import (ServeConfig, ServeEngine, execute_network_reference,
                           init_graph_weights, resnet50_graph)

    config = ServeConfig(graph="resnet50", max_batch=4, plan_deadline=900.0,
                         seed=seed)
    graph = resnet50_graph().with_batch(config.max_batch)
    weights = init_graph_weights(list(graph.layers), seed=seed)
    t0 = time.perf_counter()
    eng = ServeEngine(config, graph=graph, weights=weights)
    setup_s = time.perf_counter() - t0
    resolved = eng.resolved
    if resolved.tier > 1:
        raise AssertionError(f"plan degraded to tier {resolved.tier_name}: "
                             f"{resolved.reason!r}")
    rng = np.random.default_rng(seed)
    samples = [rng.standard_normal(eng.sample_shape).astype(np.float32)
               for _ in range(4)]
    trace = out_dir / "network_trace.jsonl"
    with eng:
        t0 = time.perf_counter()
        alone = [eng.serve([samples[0]])[0]]
        first_s = time.perf_counter() - t0
        latency_s = []
        for s in samples[1:]:
            t0 = time.perf_counter()
            alone.append(eng.serve([s])[0])
            latency_s.append(time.perf_counter() - t0)
        # packed: a blocker batch occupies the worker while the next three
        # requests queue, so they are assembled into one ragged batch
        obs.reset()
        obs.enable(str(trace))
        try:
            tickets = [eng.submit(s) for s in (samples[3], samples[0],
                                               samples[1], samples[2])]
            packed = [t.result(timeout=600.0) for t in tickets][1:]
            sizes = obs.hist_samples("serve.batch_size")
        finally:
            obs.disable()
    if max(sizes, default=0) < 2:
        raise AssertionError(f"no packed batch was assembled: {sizes}")
    bit_identical = all(np.array_equal(a, b)
                        for a, b in zip(packed, alone[:3]))

    ref_fn = jax.jit(lambda x: execute_network_reference(graph, x, weights))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ref_fn(np.stack(samples)))
    errs = [_rel_err(o, r) for o, r in zip(alone, ref)]
    errs += [_rel_err(o, r) for o, r in zip(packed, ref[:3])]
    out = dict(plan_tier=resolved.tier_name, plan_id=resolved.plan.plan_id,
               setup_s=setup_s, first_request_s=first_s,
               latency_s=latency_s, batch_sizes=sizes,
               max_rel_err=max(errs), rel_tol=NET_REL_TOL,
               bit_identical_alone_vs_packed=bit_identical,
               out_shape=list(alone[0].shape))
    _line("network", **out)
    if not all(np.isfinite(o).all() for o in alone + packed):
        raise AssertionError("non-finite network output")
    if max(errs) > NET_REL_TOL:
        raise AssertionError(f"network error {max(errs)} > {NET_REL_TOL}")
    return out


# ---------------------------------------------------------------------- LM
def _prompts(seed: int, n: int, length: int, vocab: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=length, dtype=np.int32)
            for _ in range(n)]


def _first_steps(lm, prompts, max_seq: int, *, kernels: bool, first=None):
    """Prefill logits, the first decode step's logits, and whether that
    step's program holds a Mosaic kernel — computed apart from the engine
    with the engine's own model, params and mesh.  The decode step is fed
    ``first`` when given, else the prefill's greedy tokens."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    model, params, mesh = lm
    toks = jnp.asarray(prompts)
    ops.use_kernels(kernels)
    try:
        with mesh:
            cache, logits0 = jax.jit(
                lambda p, t: model.prefill(p, t, max_seq))(params, toks)
            if first is None:
                first = jnp.argmax(logits0, axis=-1)
            first = jnp.asarray(first)
            decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
            _, logits1 = decode(params, cache, first)
            mosaic = "tpu_custom_call" in decode.lower(
                params, cache, first).as_text()
            return (*jax.device_get((logits0, logits1)), mosaic)
    finally:
        ops.use_kernels(True)


def _serve_lm(config, prompts):
    """Construct an engine and serve ``prompts`` twice (cold, then warm)."""
    import numpy as np

    from repro.api import ServeEngine

    t0 = time.perf_counter()
    eng = ServeEngine(config)
    setup_s = time.perf_counter() - t0
    with eng:
        t0 = time.perf_counter()
        cold = eng.serve(prompts)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = eng.serve(prompts)
        warm_s = time.perf_counter() - t0
    if not all(np.array_equal(a, b) for a, b in zip(cold, warm)):
        raise AssertionError("the same prompts generated different tokens")
    return eng, np.stack(warm), dict(setup_s=setup_s, first_request_s=first_s,
                                     batch_latency_s=warm_s)


def lm_phase(seed: int, out_dir: pathlib.Path) -> dict:
    import numpy as np

    from repro.api import ServeConfig, get_config

    config = ServeConfig(arch="llama3p2_3b", smoke=False, max_batch=4,
                         prompt_len=128, gen=16, seed=seed)
    cfg = get_config(config.arch)
    prompts = _prompts(seed, config.max_batch, config.prompt_len, cfg.vocab)
    eng, tokens, times = _serve_lm(config, prompts)
    max_seq = config.prompt_len + config.gen
    l0, l1, mosaic = _first_steps(eng.lm, prompts, max_seq, kernels=True)
    _, l1_ref, _ = _first_steps(eng.lm, prompts, max_seq, kernels=False,
                                first=np.argmax(l0, -1))
    err = _rel_err(l1, l1_ref)
    # the engine's first two tokens come from these very steps
    same = (np.array_equal(tokens[:, 0], np.argmax(l0, -1))
            and np.array_equal(tokens[:, 1], np.argmax(l1, -1)))
    out = dict(arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
               dtype=cfg.dtype, requests=len(prompts), gen=config.gen,
               **times, decode_runs_mosaic_kernel=mosaic,
               decode_max_rel_err=err, rel_tol=LM_REL_TOL,
               engine_tokens_match_steps=same,
               tokens_in_vocab=bool(((tokens >= 0)
                                     & (tokens < cfg.vocab)).all()),
               sample_tokens=tokens[0, :8].tolist())
    _line("lm", **out)
    if not (np.isfinite(l0).all() and np.isfinite(l1).all()):
        raise AssertionError("non-finite logits")
    if not mosaic:
        raise AssertionError("decode step holds no compiled gqa_decode")
    if not out["tokens_in_vocab"]:
        raise AssertionError("generated token outside [0, vocab)")
    if not same:
        raise AssertionError("engine tokens differ from its own first steps")
    if err > LM_REL_TOL:
        raise AssertionError(f"decode logits error {err} > {LM_REL_TOL}")
    return out


def _bytes_per_device(params) -> dict:
    import jax
    per = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            per[key] = per.get(key, 0) + shard.data.nbytes
    return per


def lm4_phase(seed: int, out_dir: pathlib.Path) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from repro.api import ServeConfig, get_config

    base = ServeConfig(arch="llama3p2_3b", smoke=False, max_batch=4,
                       prompt_len=128, gen=16, seed=seed)
    cfg = get_config(base.arch)
    prompts = _prompts(seed, base.max_batch, base.prompt_len, cfg.vocab)
    max_seq = base.prompt_len + base.gen
    eng4, tok4, t4 = _serve_lm(dataclasses.replace(base, model_axis=4),
                               prompts)
    _, params4, _ = eng4.lm
    per_dev = _bytes_per_device(params4)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params4))
    eng1, tok1, t1 = _serve_lm(base, prompts)
    l0_1, l1_1, _ = _first_steps(eng1.lm, prompts, max_seq, kernels=True)
    # both decode steps read the same first tokens, so their logits compare
    l0_4, l1_4, mosaic4 = _first_steps(eng4.lm, prompts, max_seq,
                                       kernels=True, first=tok1[:, 0])
    err0, err1 = _rel_err(l0_4, l0_1), _rel_err(l1_4, l1_1)
    # greedy tokens of the first two steps must agree, except where the
    # one-chip logits' top-2 gap is within what the tolerance allows (a
    # flip there is a near-tie, not a fault)
    flips, ties = 0, 0
    for l4, l1 in ((l0_4, l0_1), (l1_4, l1_1)):
        top2 = np.sort(np.asarray(l1, np.float64), axis=-1)[:, -2:]
        gap = top2[:, 1] - top2[:, 0]
        allowed = 2 * LM_REL_TOL * np.max(np.abs(np.asarray(l1, np.float64)))
        differ = np.argmax(l4, -1) != np.argmax(l1, -1)
        ties += int(np.sum(differ & (gap <= allowed)))
        flips += int(np.sum(differ & (gap > allowed)))
    steps_equal = int(np.argmin(np.append(
        np.all(tok4 == tok1, axis=0), False)))
    out = dict(arch=cfg.name, chips=len(per_dev), param_bytes=total,
               param_bytes_per_device=per_dev,
               max_device_share=max(per_dev.values()) / total,
               setup_s_4=t4["setup_s"], first_request_s_4=t4["first_request_s"],
               batch_latency_s_4=t4["batch_latency_s"],
               setup_s_1=t1["setup_s"], first_request_s_1=t1["first_request_s"],
               batch_latency_s_1=t1["batch_latency_s"],
               prefill_max_rel_err=err0, decode_max_rel_err=err1,
               rel_tol=LM_REL_TOL, decode_runs_mosaic_kernel_4=mosaic4,
               greedy_flips_first_2_steps=flips,
               near_tie_flips_first_2_steps=ties,
               engine_equal_greedy_steps=steps_equal, of_steps=base.gen,
               first_tokens_4=tok4[:, :2].tolist(),
               first_tokens_1=tok1[:, :2].tolist())
    _line("lm4", **out)
    if len(per_dev) != 4 or out["max_device_share"] > 0.3:
        raise AssertionError(f"params not split over four chips: {per_dev}")
    if max(err0, err1) > LM_REL_TOL:
        raise AssertionError(f"4-chip vs 1-chip logits error "
                             f"{max(err0, err1)} > {LM_REL_TOL}")
    if flips:
        raise AssertionError(f"{flips} greedy tokens of the first two steps "
                             f"differ beyond a near-tie")
    return out


# -------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(HERE / "smoke_out"),
                    help="directory for the JSON report")
    args = ap.parse_args()

    src = HERE / "src"
    if not (src / "repro").is_dir():
        _fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))
    from repro.api import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (platform {dev.platform!r}); this smoke "
              f"runs on the chip only")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _line("device", platform=dev.platform, kind=repr(dev.device_kind),
          count=len(devices), compile_cache=cache_dir,
          cache_entries_before=_cache_entries(cache_dir))

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    report = {"device": device, "seed": args.seed, "phases": {}}
    phases = [("lm4", lm4_phase)] if args.chips == 4 else \
        [("network", network_phase), ("lm", lm_phase)]
    t_all = time.perf_counter()
    ok = True
    for name, fn in phases:
        try:
            report["phases"][name] = fn(args.seed, out)
        except Exception as e:   # noqa: BLE001 — report every phase
            ok = False
            report["phases"][name] = {"error": f"{type(e).__name__}: {e}"}
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
    report["total_s"] = time.perf_counter() - t_all
    report["cache_entries_after"] = _cache_entries(cache_dir)
    _line("done", ok=ok, total_s=report["total_s"],
          cache_entries_after=report["cache_entries_after"])
    (out / f"report_chips{args.chips}.json").write_text(
        json.dumps(report, indent=2, default=str) + "\n")
    if not ok:
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
