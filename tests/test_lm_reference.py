"""ServeEngine's LM path against the plain reference, on the CPU.

The reference is ``bench/lm_reference.py``, the one the benchmark's check
runs, and the number compared is its ``deficit`` under the limit of
``bench/configs/nemotron_4_15b.json``.  The model is that file's twin
(``bench/tests/lm_twin.py``): 48 query and 8 KV heads, 2 layers, narrow,
in bfloat16.  Its weights are the benchmark's: ``lm_reference.draw_params``
at a trained model's scales, handed to the engine in its own layout.
"""
import functools
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import ServeConfig, ServeEngine
from repro.serve.engine import ServeError

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load(path: pathlib.Path):
    """A module of ``bench/`` by its path, leaving ``sys.path`` alone."""
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _load(REPO / "bench" / "lm_reference.py")
lm_twin = _load(REPO / "bench" / "tests" / "lm_twin.py")

PROMPT, GEN, BATCH = 16, 32, 4
CONFIG = lm_twin.config(max_batch=BATCH)
LIMIT = CONFIG["check"]["max_logit_deficit"]
DIMS = R.dims_of(CONFIG["model"])
KEY = jax.random.PRNGKey(3)
# The program rounds activations to bfloat16 (2**-9 relative) after each
# of a layer's matrix products and returns bfloat16 logits; on the twin
# its logits lie within 0.03 of the reference's standard deviation over
# the vocabulary.  0.1 leaves three times that.
LOGIT_TOL = 0.1


def _weights(drawn, drop_mlp):
    """The engine's ``weights``: the draw in the program's layout, kept in
    ``drawn``; with ``drop_mlp`` the program gets a copy whose last layer's
    MLP adds nothing to the stream."""
    def weights(shardings):
        drawn["params"] = jax.jit(functools.partial(R.draw_params, DIMS),
                                  out_shardings=shardings)(KEY)
        if not drop_mlp:
            return drawn["params"]
        params = jax.tree.map(lambda a: a, drawn["params"])
        wd = params["layers"]["ffn"]["wd"]
        params["layers"]["ffn"]["wd"] = wd.at[-1].set(0)
        return params

    return weights


@pytest.fixture(scope="module")
def served():
    """Serve the same seeded prompts once per ``drop_mlp``: the prompts,
    the served tokens, the drawn parameters, and the engine's model and
    mesh."""
    prompts = list(np.random.default_rng(7).integers(
        0, DIMS.vocab, (2 * BATCH, PROMPT), dtype=np.int32))
    runs = {}

    def get(drop_mlp=False):
        if drop_mlp not in runs:
            drawn = {}
            with pytest.MonkeyPatch.context() as mp:
                lm_twin.patch_arch(mp.setattr)
                cfg = ServeConfig(arch="nemotron_4_15b", max_batch=BATCH,
                                  prompt_len=PROMPT, gen=GEN)
                with ServeEngine(cfg, weights=_weights(drawn, drop_mlp)) \
                        as eng:
                    tokens = eng.serve(prompts)
                    model, _, mesh = eng.lm
            runs[drop_mlp] = (prompts, tokens, drawn["params"], model, mesh)
        return runs[drop_mlp]

    return get


def test_prefill_and_first_decode_logits_match_the_reference(served):
    prompts, _, params, model, mesh = served()
    prompts = jnp.asarray(np.stack(prompts[:BATCH]))
    with mesh:
        cache, first = jax.jit(model.prefill, static_argnums=2)(
            params, prompts, PROMPT + GEN)
        token = jnp.argmax(first, axis=-1)
        _, second = jax.jit(model.decode_step)(params, cache, token)
    want_first = R.logits(params, DIMS, prompts, PROMPT - 1)[:, 0]
    want_second = R.logits(params, DIMS, jnp.concatenate(
        [prompts, token[:, None]], axis=1), PROMPT)[:, 0]
    for got, want in ((first, want_first), (second, want_second)):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        std = want.std(axis=-1, keepdims=True)
        assert np.max(np.abs(got - want) / std) < LOGIT_TOL


@pytest.mark.parametrize("side,over", [
    ("program", False),
    ("control", True),
    ("mlp_dropped", True),
])
def test_the_check_reads_each_side_of_its_limit(served, side, over):
    """The served tokens read under the limit; the reference at fp8 e4m3
    in the program's place, and a program whose last layer drops its MLP
    output, read over it."""
    prompts, tokens, params, _, _ = served(drop_mlp=side == "mlp_dropped")
    ref = R.reference(params, DIMS, prompts, tokens, BATCH)
    if side == "control":
        tokens = R.reference(params, DIMS, prompts, tokens, BATCH,
                             precision="fp8_e4m3")
    reading = max(R.deficit(t, r) for t, r in zip(tokens, ref))
    assert (reading > LIMIT) == over, reading


def test_the_engine_refuses_weights_unlike_the_models(monkeypatch):
    lm_twin.patch_arch(monkeypatch.setattr)
    cfg = ServeConfig(arch="nemotron_4_15b", max_batch=BATCH,
                      prompt_len=PROMPT, gen=GEN)

    def transposed(shardings):
        params = R.draw_params(DIMS, KEY)
        params["lm_head"] = params["lm_head"].T
        return params

    with pytest.raises(ServeError, match="lm_head"):
        ServeEngine(cfg, weights=transposed)


def test_the_batch_spans_add_up_to_it():
    prompts = [np.arange(PROMPT, dtype=np.int32)] * BATCH
    obs.reset()
    obs.enable()
    try:
        with pytest.MonkeyPatch.context() as mp:
            lm_twin.patch_arch(mp.setattr)
            cfg = ServeConfig(arch="nemotron_4_15b", max_batch=BATCH,
                              prompt_len=PROMPT, gen=GEN)
            with ServeEngine(cfg) as eng:
                eng.serve(prompts)
                eng.serve(prompts)
        spans = [e for e in obs.events() if e["ev"] == "span"]
    finally:
        obs.disable()
        obs.reset()
    batches = [e for e in spans if e["name"] == "serve.batch"]
    parts = [e for e in spans
             if e["name"] in ("lm.prefill", "lm.decode", "serve.fetch")]
    assert len(batches) == 2 and len(parts) == 6
    for b in batches:
        inside = [e for e in parts if b["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= b["ts"] + b["dur"]]
        assert sorted(e["name"] for e in inside) == \
            ["lm.decode", "lm.prefill", "serve.fetch"]
        assert sum(e["dur"] for e in inside) >= 0.99 * b["dur"]
    assert all(e["attrs"]["steps"] == GEN - 1 for e in parts
               if e["name"] == "lm.decode")


# Served through the benchmark's own system (``bench/systems/lm_serve.py``:
# weights drawn from the seed, the check's reference and deficit) over
# four virtual devices; prints each model_axis's deficit and tokens.
_SERVE_TP = textwrap.dedent("""
    import json, sys
    sys.path[:0] = ["bench", "bench/systems", "bench/tests"]
    import numpy as np
    import lm_serve, lm_twin
    lm_twin.patch_arch(setattr)
    mix = {"pool": 4, "prompt_len": 16, "gen": 8}
    for axis in map(int, sys.argv[1:]):
        cfg = lm_twin.config(model_axis=axis)
        eng, pool, params, facts = lm_serve.start(cfg, mix, 2 ** 33 + 5,
                                                  axis, None)
        with eng:
            toks = eng.serve(pool)
        assert facts["mesh_devices"] == axis
        assert len(params["layers"]["mixer"]["wq"].addressable_shards) == axis
        ref = lm_serve.reference(cfg, pool, toks, params, "highest")
        reading = max(lm_serve.gap(cfg, t, r) for t, r in zip(toks, ref))
        print(json.dumps({"axis": axis, "deficit": reading,
                          "tokens": np.stack(toks).tolist()}))
""")

# A program whose MLP leaves out the exchange between chips after the
# down-projection: each chip keeps its own partial sum over its d_ff
# columns as if it were the whole.
_SKIP_WD_REDUCTION = textwrap.dedent("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh
    from repro.models import lm
    from repro.models.common import activation, apply_norm, dense

    MESH = make_local_mesh(4, jax.devices()[:4])

    def mlp_unreduced(cfg, p, x):
        h = apply_norm(cfg.norm, x, p["norm"])
        up = activation(cfg.act, dense(h, p["wu"]))
        return jax.shard_map(
            dense, mesh=MESH, in_specs=(P(None, None, "model"),
                                        P("model", None)),
            out_specs=P(), check_vma=False)(up, p["wd"])

    lm.mlp_apply = mlp_unreduced
""")


def _serve_tp(*axes, fault=""):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", fault + _SERVE_TP, *map(str, axes)], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return {r["axis"]: r for r in map(json.loads, out.stdout.splitlines())}


def test_four_way_tensor_parallel_serves_the_same_tokens_and_passes():
    """``model_axis=4`` over four virtual devices: parameters split by the
    sharding rules, the decode kernel per head shard (2 KV heads each);
    its tokens are those of one device, and both pass the check."""
    runs = _serve_tp(4, 1)
    assert runs[4]["tokens"] == runs[1]["tokens"]
    for run in runs.values():
        assert run["deficit"] <= LIMIT, run["deficit"]


def test_a_skipped_down_projection_reduction_reads_over_the_limit():
    """Over four devices, an MLP that drops the all-reduce after ``wd``
    fails the check."""
    reading = _serve_tp(4, fault=_SKIP_WD_REDUCTION)[4]["deficit"]
    assert reading > LIMIT, reading
