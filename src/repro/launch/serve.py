"""Serving CLI: a thin front-end over ``repro.api.ServeEngine``.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3p2_3b --smoke \
        --batch 4 --prompt-len 32 --gen 16 --plan plan.json
    PYTHONPATH=src python -m repro.launch.serve --graph tiny --batch 4 \
        --workers 2

All knobs live on ``repro.api.ServeConfig`` (this module only parses argv
and prints a summary); the engine owns plan resolution, the bounded
admission queue, dynamic batch assembly and background tier upgrades.
Observability: console output goes through the ``repro.obs`` structured
logger (``--log-level`` / ``REPRO_LOG``); ``REPRO_TRACE=out.jsonl`` records
``serve.plan``/``serve.batch`` spans and the queue/latency histograms
(``serve.batch_size``, ``serve.time_in_queue_ms``, ``serve.e2e_ms``,
``serve.prefill_ms``, ``serve.decode_ms_per_token``) as JSONL; run it under
a JAX profiler session to see the same spans beside the device operations.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro import obs

log = obs.get_logger("serve")


def _plan_for(cfg, args):
    """Deprecated shim — kept so pre-facade callers keep working.

    The engine resolves plans itself now; import ``resolve_plan`` from
    ``repro.api`` instead.  Delegates to the same ladder with the same
    options and returns the ``ResolvedPlan``.
    """
    from repro import api

    api.warn_deprecated("repro.launch.serve._plan_for", "resolve_plan")
    graph = api.from_arch_config(cfg, seq=args.prompt_len + args.gen)
    opts = api.PlannerOptions(switch_modes=("rir",),
                              parallel_dims=("C", "P", "Q"))
    return api.resolve_plan(graph, api.EvalConfig(), opts=opts,
                            cache=api.PlanCache(), artifact=args.plan,
                            deadline_s=args.plan_deadline)


def _decode_block_hints(plan):
    """Distinct kernel (block_m, block_k) shapes the plan's steps ask for —
    advisory, logged so an operator can see what a plan-driven decode
    would use."""
    from repro.api import step_kernel_blocks

    return sorted({step_kernel_blocks(s) for s in plan.steps})


def main() -> None:
    from repro.api import ServeConfig, ServeEngine, enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ServeConfig.add_args(ap)
    args = ap.parse_args()

    obs.configure_from_env()          # REPRO_TRACE=path enables tracing
    config = ServeConfig.from_args(args)

    with ServeEngine(config) as eng:
        resolved = eng.resolved
        if resolved is not None:
            hints = _decode_block_hints(resolved.plan)
            log.info("plan %s tier=%s; decode kernel block hints %s",
                     resolved.plan.plan_id, resolved.tier_name, hints)
        if config.arch is not None:
            import jax

            from repro.api import get_config

            cfg = get_config(config.arch, smoke=config.smoke)
            _, data_key = jax.random.split(jax.random.PRNGKey(config.seed))
            prompts = jax.random.randint(
                data_key, (config.max_batch, config.prompt_len), 0, cfg.vocab)
            outs = eng.serve([np.asarray(prompts[i])
                              for i in range(config.max_batch)])
            log.info("arch=%s batch=%d prompt=%d gen=%d",
                     cfg.name, config.max_batch, config.prompt_len,
                     config.gen)
            log.info("sample tokens: %s", outs[0][:12].tolist())
        else:
            rng = np.random.default_rng(config.seed)
            samples = [rng.standard_normal(eng.sample_shape)
                       .astype(np.float32) for _ in range(config.max_batch)]
            outs = eng.serve(samples)
            log.info("graph=%s batch=%d out=%s checksum=%.6f",
                     config.graph, config.max_batch, outs[0].shape,
                     float(np.sum(np.stack(outs))))


if __name__ == "__main__":
    main()
