"""Attention + MLP + MoE blocks with spec/apply pairs (scan-over-layers ready).

Every block provides ``*_specs(cfg)`` returning a ShapeDtypeStruct pytree for
ONE layer (the assembler stacks a leading layer axis for ``lax.scan``) and an
``apply`` taking the un-stacked layer params.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from .common import (activation, apply_norm, apply_rope, chunked_attention,
                     dense, norm_spec)


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# -------------------------------------------------------------------- attention
def attn_specs(cfg: ArchConfig, cross: bool = False) -> Dict:
    D, dh = cfg.d_model, cfg.head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dt(cfg)
    specs = {
        "norm": norm_spec(cfg.norm, D, dt),
        "wq": jax.ShapeDtypeStruct((D, H * dh), dt),
        "wkv": jax.ShapeDtypeStruct((D, 2 * Hkv * dh), dt),
        "wo": jax.ShapeDtypeStruct((H * dh, D), dt),
    }
    return specs


def _qkv(cfg: ArchConfig, p: Dict, x: jax.Array, kv_src: Optional[jax.Array]
         = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = apply_norm(cfg.norm, x, p["norm"])
    q = dense(h, p["wq"]).reshape(*x.shape[:-1], H, dh)
    src = apply_norm(cfg.norm, kv_src, p["norm"]) if kv_src is not None else h
    kv = dense(src, p["wkv"]).reshape(*src.shape[:-1], 2 * Hkv, dh)
    k, v = kv[..., :Hkv, :], kv[..., Hkv:, :]
    return q, k, v


def _shard_attn_heads(t: jax.Array, mesh) -> jax.Array:
    """(B, T, H, dh): full sequence, heads TP — entering this layout from a
    sequence-sharded residual stream costs an all-to-all (1/TP of the data)
    rather than an all-gather (the full tensor)."""
    if mesh is None:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = 1
    for a in data:
        dsize *= mesh.shape[a]
    if t.shape[0] % dsize or t.shape[2] % mesh.shape["model"]:
        return t
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(data, None, "model", None)))


def attn_train(cfg: ArchConfig, p: Dict, x: jax.Array,
               positions: Optional[jax.Array] = None,
               causal: bool = True, use_rope: bool = True,
               mesh=None) -> jax.Array:
    """x: (B, T, D) -> (B, T, D) residual delta."""
    B, T, D = x.shape
    q, k, v = _qkv(cfg, p, x)
    # NOTE (§Perf D3, refuted): constraining q/k/v to head-sharded layout
    # here made XLA reshard via all-gather+slice (not all-to-all), raising
    # collective bytes 5.7->9.7 TB/chip on chameleon train_4k — reverted.
    if use_rope:
        pos = positions if positions is not None else jnp.arange(T)
        pos = jnp.broadcast_to(pos, (B, T))
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=causal)
    return dense(o.reshape(B, T, -1), p["wo"])


def cross_attn_train(cfg: ArchConfig, p: Dict, x: jax.Array,
                     memory: jax.Array) -> jax.Array:
    B, T, D = x.shape
    q, k, v = _qkv(cfg, p, x, kv_src=memory)
    o = chunked_attention(q, k, v, causal=False)
    return dense(o.reshape(B, T, -1), p["wo"])


def attn_prefill(cfg: ArchConfig, p: Dict, x: jax.Array, use_rope: bool = True
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (residual delta, (k_cache, v_cache)) for the prompt, the
    caches head-major (B, Hkv, T, dh) as ``gqa_decode`` reads them."""
    B, T, D = x.shape
    q, k, v = _qkv(cfg, p, x)
    if use_rope:
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True)
    return dense(o.reshape(B, T, -1), p["wo"]), (k.transpose(0, 2, 1, 3),
                                                  v.transpose(0, 2, 1, 3))


def _gqa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                lengths: jax.Array, mesh) -> jax.Array:
    """``ops.gqa_decode``, run per (batch, head) shard on a multi-device
    mesh: XLA does not partition a Mosaic kernel by itself.  KV heads
    split over the model axis where they divide it; each shard keeps its
    queries' whole GQA group, since Hq is grouped KV-head-major."""
    if mesh is None or mesh.size == 1:
        return ops.gqa_decode(q, k, v, lengths)
    from jax.sharding import PartitionSpec as P
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = math.prod(mesh.shape[a] for a in data)
    batch = data if q.shape[0] % dsize == 0 else None
    heads = "model" if k.shape[1] % mesh.shape["model"] == 0 else None
    kv = P(batch, heads, None, None)
    return jax.shard_map(
        ops.gqa_decode, mesh=mesh,
        in_specs=(P(batch, heads, None), kv, kv, P(batch)),
        out_specs=P(batch, heads, None), check_vma=False)(q, k, v, lengths)


def attn_decode(cfg: ArchConfig, p: Dict, x: jax.Array,
                k_cache: jax.Array, v_cache: jax.Array, length: jax.Array,
                use_rope: bool = True, mesh=None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token step.  x: (B, D); caches: (B, Hkv, S, dh); length: (B,).

    Returns (residual delta (B, D), new k_cache, new v_cache).
    The new token attends over length+1 entries via the flash-decode kernel
    (per shard of ``mesh``, when one is given).
    """
    B, D = x.shape
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(cfg, p, x[:, None, :])
    if use_rope:
        q = apply_rope(q, length[:, None], cfg.rope_theta)
        k = apply_rope(k, length[:, None], cfg.rope_theta)
    # scatter the new kv at position `length` per row — a batched scatter
    # aliases in place under donation (the one-hot/where alternative
    # materializes full-cache temporaries)
    rows = jnp.arange(k_cache.shape[0])
    k_cache = k_cache.at[rows, :, length].set(k[:, 0])
    v_cache = v_cache.at[rows, :, length].set(v[:, 0])
    o = _gqa_decode(q[:, 0], k_cache, v_cache, length + 1, mesh)
    return dense(o.reshape(B, -1), p["wo"]), k_cache, v_cache


# ------------------------------------------------------------------------- MLP
def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    dt = _dt(cfg)
    s = {"norm": norm_spec(cfg.norm, D, dt),
         "wu": jax.ShapeDtypeStruct((D, F), dt),
         "wd": jax.ShapeDtypeStruct((F, D), dt)}
    if cfg.act == "swiglu":
        s["wg"] = jax.ShapeDtypeStruct((D, F), dt)
    return s


def mlp_apply(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, p["norm"])
    up = dense(h, p["wu"])
    gate = dense(h, p["wg"]) if cfg.act == "swiglu" else None
    return dense(activation(cfg.act, up, gate), p["wd"])


# ------------------------------------------------------------------------- MoE
def moe_specs(cfg: ArchConfig) -> Dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = _dt(cfg)
    s = {"norm": norm_spec(cfg.norm, D, dt),
         "router": jax.ShapeDtypeStruct((D, E), jnp.float32),
         "wu": jax.ShapeDtypeStruct((E, D, F), dt),
         "wd": jax.ShapeDtypeStruct((E, F, D), dt)}
    if cfg.act == "swiglu":
        s["wg"] = jax.ShapeDtypeStruct((E, D, F), dt)
    if cfg.shared_expert:
        s["shared"] = {k: v for k, v in mlp_specs(cfg).items() if k != "norm"}
    return s


def moe_apply(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    """Capacity-based top-k dispatch (sort-free scatter), EP-shardable.

    The dispatch is FEATHER's arbitrary-reduction-group pattern: each token's
    top-k expert outputs form a reduction group whose sum must land back at
    the token's position — the combine step *is* an RIR
    (reduce-while-reordering) over the expert axis.
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * T
    h = apply_norm(cfg.norm, x, p["norm"])
    flat = h.reshape(N, D)

    logits = flat.astype(jnp.float32) @ p["router"]          # (N, E)
    gates, idx = jax.lax.top_k(logits, K)                     # (N, K)
    gates = jax.nn.softmax(gates, axis=-1)

    C = int(math.ceil(N * K / E * cfg.capacity_factor / 8.0)) * 8
    C = min(C, N)
    flat_e = idx.reshape(-1)                                  # (N*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    ones = jnp.ones_like(sorted_e)
    counts = jax.ops.segment_sum(ones, sorted_e, num_segments=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(N * K) - starts[sorted_e]
    slot_sorted = jnp.where(pos_in_e < C, sorted_e * C + pos_in_e, E * C)
    slot = jnp.zeros((N * K,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))

    buf = jnp.zeros((E * C + 1, D), flat.dtype)
    dispatched = buf.at[slot_sorted].set(flat[order // K])
    dispatched = dispatched[:E * C].reshape(E, C, D)

    up = jnp.einsum("ecd,edf->ecf", dispatched, p["wu"],
                    preferred_element_type=jnp.float32).astype(flat.dtype)
    if cfg.act == "swiglu":
        gate_h = jnp.einsum("ecd,edf->ecf", dispatched, p["wg"],
                            preferred_element_type=jnp.float32
                            ).astype(flat.dtype)
        act = activation(cfg.act, up, gate_h)
    else:
        act = activation(cfg.act, up)
    out_e = jnp.einsum("ecf,efd->ecd", act, p["wd"],
                       preferred_element_type=jnp.float32).astype(flat.dtype)
    out_pad = jnp.concatenate(
        [out_e.reshape(E * C, D), jnp.zeros((1, D), flat.dtype)], axis=0)

    gathered = out_pad[slot.reshape(N, K)]                    # (N, K, D)
    combined = jnp.sum(gathered * gates[..., None].astype(flat.dtype), axis=1)
    if cfg.shared_expert:
        sp = p["shared"]
        up_s = dense(flat, sp["wu"])
        gate_s = dense(flat, sp["wg"]) if cfg.act == "swiglu" else None
        combined = combined + dense(activation(cfg.act, up_s, gate_s),
                                    sp["wd"])
    return combined.reshape(B, T, D)
