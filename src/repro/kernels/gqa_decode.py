"""Flash-decode GQA attention — the serving hot spot (decode_32k / long_500k).

One new query token attends over a long KV cache.  Grid: (batch, kv_head,
kv_blocks); the kv_blocks axis is sequential ("arbitrary") and carries the
online-softmax running (max, sum, acc) state in VMEM scratch.  The grouped
queries of one KV head (G = Hq/Hkv rows) ride the sublane axis — the same
grouped-reduction structure BIRRD exploits (a G:1 reduction group per KV
head), with the MXU doing the (G, D) x (D, bs) score tile.

The cache is head-major, (B, Hkv, S, D): a KV block is then a (block_s, D)
tile in the last two dims, which Mosaic requires to be (8, 128)-divisible
or full — a head-minor (B, S, Hkv, D) cache would put a 1-wide head block
in the second-minor dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_s: int, s_steps: int,
            scale: float):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)                 # (bs, Dv)
    scores = jax.lax.dot_general(                       # q @ k.T
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    length = len_ref[pl.program_id(0)]
    pos = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < length, scores, NEG_INF)

    m_prev = m_ref[...]                                 # (G, 1)
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)                         # (G, bs)
    alpha = jnp.exp(m_prev - m_new)                     # (G, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(sb == s_steps - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def gqa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
               lengths: jax.Array, *, block_s: int = 512,
               interpret: bool) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, Hkv, S, D); lengths: (B,) int32 -> (B, Hq, D).

    ``block_s`` must divide S (``ops.gqa_decode`` picks or pads for it).
    """
    B, Hq, D = q.shape
    _, Hkv, S, Dv = v.shape
    G = Hq // Hkv
    assert Hq == G * Hkv and k.shape == (B, Hkv, S, D)
    assert S % block_s == 0, (S, block_s)
    s_steps = S // block_s
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, G, D)

    grid = (B, Hkv, s_steps)
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, s_steps=s_steps,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_s, D),
                             lambda b, h, s, lens: (b, h, s, 0)),
                pl.BlockSpec((1, 1, block_s, Dv),
                             lambda b, h, s, lens: (b, h, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dv),
                                   lambda b, h, s, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, Dv)
