"""End-to-end training driver (CPU-scale smoke or real mesh).

Integrates: model zoo, per-layer layout co-switching, AdamW+WSD, deterministic
data pipeline, async checkpointing with resume, straggler monitor hooks.

    PYTHONPATH=src python -m repro.launch.train --arch llama3p2_3b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Console output goes through the ``repro.obs`` structured logger
(``--log-level`` / ``REPRO_LOG``); ``REPRO_TRACE=out.jsonl`` records
per-step spans and a ``train.step_ms`` histogram.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

log = obs.get_logger("train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3p2_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--layout-mode", default="coswitch")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-level", default=None,
                    choices=["debug", "info", "warning", "error"],
                    help="console log threshold (default: REPRO_LOG or info)")
    args = ap.parse_args()

    from repro.api import (CheckpointManager, DataConfig, SyntheticLMStream,
                           adamw_init, build_model, enable_compile_cache,
                           get_config, make_local_mesh, make_train_step,
                           wsd_schedule)

    enable_compile_cache()
    obs.configure_from_env()          # REPRO_TRACE=path enables tracing
    if args.log_level:
        obs.set_level(args.log_level)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    mesh = make_local_mesh(args.model_axis)

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt_state = adamw_init(params)
    sched = lambda s: wsd_schedule(
        s, peak_lr=args.lr, warmup=max(2, args.steps // 10),
        stable=args.steps // 2, decay=max(1, args.steps // 3))
    step_fn = jax.jit(make_train_step(model, mesh, accum=args.accum,
                                      layout_mode=args.layout_mode,
                                      schedule=sched),
                      donate_argnums=(0, 1))

    dcfg = DataConfig(vocab=cfg.vocab, global_batch=args.batch,
                      seq_len=args.seq,
                      frames_dim=cfg.d_model if cfg.family == "encdec" else 0,
                      frames_len=cfg.enc_frames)
    stream = SyntheticLMStream(dcfg)

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        s, restored = mgr.restore_latest({"params": params,
                                          "opt": opt_state})
        if s is not None:
            start, params, opt_state = s, restored["params"], restored["opt"]
            log.info("resumed from step %d", start)

    t0 = time.time()
    traced = obs.enabled()
    with mesh:
        for step in range(start, args.steps):
            if traced:
                step_t0 = obs.now_us()
            batch = {k: jnp.asarray(v) for k, v in
                     stream.batch_at(step).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if traced:
                metrics = jax.block_until_ready(metrics)
                obs.record_span("train.step", step_t0, {"step": step})
                obs.observe("train.step_ms",
                            (obs.now_us() - step_t0) / 1e3)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                log.info("step=%d loss=%.4f lr=%.2e (%.1fs)",
                         step, loss, float(metrics["lr"]), time.time() - t0)
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state})
        mgr.wait()
        mgr.close()
    log.info("done")


if __name__ == "__main__":
    main()
