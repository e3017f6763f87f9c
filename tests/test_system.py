"""End-to-end behaviour: train converges, resume is exact, serving decodes,
Layoutloop reproduces the paper's qualitative results."""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMStream
from repro.distributed.stepfn import make_train_step
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.optim import adamw_init


def _train(arch="minicpm_2b", steps=25, lr=1e-2, seed=0):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    mesh = make_local_mesh()
    params = model.init(jax.random.PRNGKey(seed))
    opt = adamw_init(params)
    step_fn = jax.jit(make_train_step(model, mesh, lr=lr),
                      donate_argnums=(0, 1))
    stream = SyntheticLMStream(DataConfig(vocab=cfg.vocab, global_batch=8,
                                          seq_len=64, seed=seed))
    losses = []
    with mesh:
        for s in range(steps):
            batch = {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
    return losses, params


def test_training_reduces_loss():
    losses, _ = _train(steps=40)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.5, (first, last)
    assert np.isfinite(losses).all()


def test_training_is_deterministic():
    l1, _ = _train(steps=6)
    l2, _ = _train(steps=6)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_train_driver_checkpoint_resume(tmp_path):
    """The train launcher resumes from its checkpoint (same final loss as an
    uninterrupted run — the data stream is step-addressed)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # the launcher's compile cache goes where the caller says
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    root = os.path.dirname(os.path.dirname(__file__))
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "minicpm_2b", "--smoke", "--batch", "4", "--seq", "32",
            "--log-every", "1"]

    def run(steps, ckpt):
        out = subprocess.run(base + ["--steps", str(steps), "--ckpt-dir",
                                     str(ckpt), "--ckpt-every", "5"],
                             capture_output=True, text=True, env=env,
                             cwd=root)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout

    log_full = run(10, tmp_path / "a")          # uninterrupted 0..10
    run(5, tmp_path / "b")                      # train 0..5, checkpoint
    log_resumed = run(10, tmp_path / "b")       # resume 5..10

    def final_loss(log):
        lines = [l for l in log.splitlines() if "loss=" in l]
        return float(lines[-1].split("loss=")[1].split()[0])

    assert "resumed from step 5" in log_resumed
    assert final_loss(log_full) == pytest.approx(final_loss(log_resumed),
                                                 rel=1e-4)


def test_serve_driver_generates(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    root = os.path.dirname(os.path.dirname(__file__))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "rwkv6_1p6b",
         "--smoke", "--batch", "2", "--prompt-len", "8", "--gen", "4"],
        capture_output=True, text=True, env=env, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "sample tokens" in out.stdout


def test_layoutloop_feather_beats_fixed_baselines():
    """Paper Fig. 13 direction: FEATHER (co-switch + RIR) achieves lower
    latency x energy than fixed-dataflow and fixed-layout baselines."""
    from repro.core.accel_models import (EYERISS_LIKE, FEATHER, NVDLA_LIKE,
                                         SIGMA_C32)
    from repro.core.workloads import resnet50_layers
    layers = resnet50_layers()[:6]
    feather = FEATHER.run(layers)
    for baseline in (NVDLA_LIKE, EYERISS_LIKE, SIGMA_C32):
        base = baseline.run(layers)
        f_cycles = sum(r.metrics.cycles for r in feather)
        b_cycles = sum(r.metrics.cycles for r in base)
        assert f_cycles <= b_cycles * 1.01, baseline.name
        f_edp = sum(r.metrics.edp for r in feather)
        b_edp = sum(r.metrics.edp for r in base)
        assert f_edp < b_edp, baseline.name


def test_feather_has_no_bank_conflicts():
    """Paper: RIR + dataflow selection => zero conflict slowdown."""
    from repro.core.accel_models import FEATHER
    from repro.core.workloads import mobilenet_v3_layers
    res = FEATHER.run(mobilenet_v3_layers()[:5])
    for r in res:
        assert r.metrics.slowdown == 1.0
        assert r.metrics.reorder_cycles == 0.0
