"""The plain reference for a served decoder LM, and the number the check
compares: how far below the reference's best logit a served token lies.

Written from the layer equations the configuration file states (Nemotron-4,
arXiv:2402.16819 §2), for a prompt followed by the served tokens:

    x = E[t]                                       one row per position
    per layer l:
        h = LN(x; g, b)                            eps from the file
        q = h Wq  as H heads of dh
        k, v = h Wkv  as 2*Hkv heads, the first Hkv are k
        q, k rotated by RoPE at their positions (the whole head; element
             i pairs with i + dh/2 at frequency theta^(-2i/dh))
        o_j = softmax(q_j k_g^T / sqrt(dh), causal) v_g,  g = j // (H/Hkv)
        x = x + o Wo
        h = LN(x; g', b');  x = x + relu(h Wu)^2 Wd
    logits = LN(x; g_f, b_f) Wout

Plain ``jax.numpy``: no ``repro`` code, no cache, no kernel, no scan over
the stacked layers.  The parameter tree has these keys, every matrix
stacked over a leading layer axis of ``n_layers``:

    embed (V, D)   lm_head (D, V)   final_norm/{w, b} (D,)
    layers/mixer/norm/{w, b}  wq (D, H*dh)  wkv (D, 2*Hkv*dh)  wo (H*dh, D)
    layers/ffn/norm/{w, b}    wu (D, F)     wd (F, D)

``draw_params`` makes it from a key, in bfloat16, at a trained model's
scales: LayerNorm gains 1 and biases 0, embeddings of unit variance, each
other matrix of variance 1/fan-in.  The program serves that draw, and the
reference takes layer ``l`` of each, upcast to float32, one layer at a
time.  At
``highest`` every product is float32 at ``lax.Precision.HIGHEST``.  At
``fp8_e4m3``, the control, every matrix product's operands are first
rounded to 4 exponent and 3 mantissa bits (``lax.reduce_precision``, which
stays float32: a cast pair would be folded away by XLA on the TPU), the
precision below the served bfloat16.

The sampled requests go through in blocks of ``block`` (the last padded),
so one compiled program serves every block.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PRECISIONS = ("highest", "fp8_e4m3")
# reference logits kept a position: the best TOP_K and the served token's
TOP_K = 128


class Dims(NamedTuple):
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def dims_of(model: Dict) -> Dims:
    """The widths a configuration file states under ``"model"``."""
    return Dims(**{k: model[k] for k in Dims._fields})


def _matrices(dims: Dims) -> Dict[str, tuple]:
    L, D, F, V = dims.n_layers, dims.d_model, dims.d_ff, dims.vocab
    q, kv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    return {"embed": (V, D), "lm_head": (D, V),
            "layers/mixer/wq": (L, D, q), "layers/mixer/wkv": (L, D, 2 * kv),
            "layers/mixer/wo": (L, q, D),
            "layers/ffn/wu": (L, D, F), "layers/ffn/wd": (L, F, D)}


def _put(tree: Dict, name: str, value) -> None:
    *parents, leaf = name.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[leaf] = value


def draw_params(dims: Dims, key: jax.Array) -> Dict:
    """The parameter tree in bfloat16, drawn from ``key``: LayerNorm gains
    1 and biases 0; ``embed`` of unit variance (a row is the input, not a
    product); each other matrix normal of variance 1/fan-in, its input
    width.  Jit it with the program's shardings as ``out_shardings`` to
    draw it in place."""
    params: Dict = {}
    for i, (name, shape) in enumerate(sorted(_matrices(dims).items())):
        fan_in = 1 if name == "embed" else shape[-2]
        a = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        _put(params, name, (a / np.sqrt(fan_in)).astype(jnp.bfloat16))
    L, D = dims.n_layers, dims.d_model
    for norm, shape in (("final_norm", (D,)), ("layers/mixer/norm", (L, D)),
                        ("layers/ffn/norm", (L, D))):
        _put(params, f"{norm}/w", jnp.ones(shape, jnp.bfloat16))
        _put(params, f"{norm}/b", jnp.zeros(shape, jnp.bfloat16))
    return params


def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "highest":
        return x
    return lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _mm(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=lax.Precision.HIGHEST)


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, heads, dh) at positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq     # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _layer(x: jax.Array, layers: Dict, l: jax.Array, dims: Dims,
           precision: str) -> jax.Array:
    """x: (B, T, D) float32 through layer ``l`` of the stacked ``layers``."""
    p = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    B, T, _ = x.shape
    H, Hkv, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    att, ffn = p["mixer"], p["ffn"]

    h = _layernorm(x, att["norm"]["w"], att["norm"]["b"], dims.norm_eps)
    q = _mm("btd,de->bte", h, att["wq"], precision).reshape(B, T, H, dh)
    kv = _mm("btd,de->bte", h, att["wkv"], precision).reshape(
        B, T, 2 * Hkv, dh)
    k, v = kv[:, :, :Hkv], kv[:, :, Hkv:]
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    q = q.reshape(B, T, Hkv, H // Hkv, dh)
    s = _mm("bqhgd,bkhd->bhgqk", q, k, precision) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v, precision)
    x = x + _mm("bte,ed->btd", o.reshape(B, T, H * dh), att["wo"], precision)

    h = _layernorm(x, ffn["norm"]["w"], ffn["norm"]["b"], dims.norm_eps)
    up = jnp.square(jax.nn.relu(_mm("btd,df->btf", h, ffn["wu"], precision)))
    return x + _mm("btf,fd->btd", up, ffn["wd"], precision)


@jax.jit
def _embed(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "precision", "first"))
def _head(x, final_norm, lm_head, dims: Dims, precision: str, first: int):
    """Logits (B, P, V) at positions ``first``.. of ``x`` (B, T, D)."""
    h = _layernorm(x[:, first:], final_norm["w"], final_norm["b"],
                   dims.norm_eps)
    return _mm("bpd,dv->bpv", h, lm_head.astype(jnp.float32), precision)


def logits(params: Dict, dims: Dims, tokens: jax.Array, first: int,
           precision: str = "highest") -> jax.Array:
    """The reference's logits (B, T - first, V) at positions ``first`` to
    ``T - 1`` of ``tokens`` (B, T): each predicts the token after it."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    x = _embed(params["embed"], tokens)
    for l in range(dims.n_layers):
        x = _layer(x, params["layers"], jnp.int32(l), dims, precision)
    return _head(x, params["final_norm"], params["lm_head"], dims, precision,
                 first)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _summary(z: jax.Array, served: jax.Array, top_k: int):
    top_logits, top_ids = lax.top_k(z, top_k)
    chosen = jnp.take_along_axis(z, served[..., None], axis=-1)[..., 0]
    return top_ids, top_logits, chosen, jnp.std(z, axis=-1)


def reference(params: Dict, dims: Dims, prompts: Sequence[np.ndarray],
              served: Sequence[np.ndarray], block: int,
              precision: str = "highest") -> List:
    """For each request (a prompt and the ``gen`` tokens served for it),
    the reference over the prompt and the served tokens (teacher-forced)
    at the ``gen`` positions that chose a served token.

    At ``highest`` a request's result is a dict of arrays over those
    positions: ``served`` ids, ``served_logit``, the ``top_ids`` and
    ``top_logits`` of the best ``TOP_K``, and ``std``, the logits'
    standard deviation over the vocabulary.  Below it, the tokens the
    reference itself chooses there by argmax: what a program computing at
    that precision would serve on the same prefixes.
    """
    prompts = np.asarray(prompts, np.int32)
    served = np.asarray(served, np.int32)
    n, first = len(prompts), prompts.shape[1] - 1
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1)
    out: List = []
    for s in range(0, n, block):
        rows = slice(s, min(s + block, n))
        pad = block - (rows.stop - rows.start)
        toks = np.pad(seqs[rows], ((0, pad), (0, 0)))
        z = logits(params, dims, jnp.asarray(toks), first, precision)
        if precision != "highest":
            chosen = np.asarray(jnp.argmax(z, axis=-1), np.int32)
            out.extend(chosen[:block - pad])
            continue
        want = np.pad(served[rows], ((0, pad), (0, 0)))
        ids, top, chosen, std = (np.asarray(a) for a in _summary(
            z, jnp.asarray(want), min(TOP_K, dims.vocab)))
        out.extend({"served": want[i], "served_logit": chosen[i],
                    "top_ids": ids[i], "top_logits": top[i], "std": std[i]}
                   for i in range(block - pad))
    return out


def deficit(tokens: np.ndarray, ref: Dict) -> float:
    """The largest, over a request's generated positions, of the
    reference's best logit less its logit of the token ``tokens`` chose
    there, over the standard deviation of the reference's logits there.
    A token neither served nor among the best ``TOP_K`` reads the
    ``TOP_K``-th logit's deficit, which is a lower bound."""
    tokens = np.asarray(tokens)
    if tokens.shape != ref["served"].shape:
        return float("inf")
    hit = ref["top_ids"] == tokens[:, None]
    chosen = np.where(hit.any(axis=1),
                      np.max(np.where(hit, ref["top_logits"], -np.inf),
                             axis=1),
                      ref["top_logits"][:, -1])
    chosen = np.where(tokens == ref["served"], ref["served_logit"], chosen)
    gap = (ref["top_logits"][:, 0] - chosen) / ref["std"]
    return float(np.max(gap))
