"""Chunked gated-linear-attention scan — the SSM hot spot (mamba2 / rwkv6).

Recurrence  h_t = exp(logw_t) (.) h_{t-1} + k_t^T v_t ;  y_t = q_t h_t
with per-(step, key-dim) log decay logw <= 0.

TPU adaptation: the sequential scan is reblocked into chunks of L steps so
the MXU does three (L x dk)x(dk x ...) GEMMs per chunk (intra-chunk causal
attention, inter-chunk state read, state update) instead of T rank-1
updates — the chunk axis of the grid is sequential and carries the (dk, dv)
state in VMEM scratch, which is NEST's local temporal reduction in SSM form.
Every exponent is kept <= 0 by factoring through a per-sub-chunk base, so
nothing overflows and no clamp is needed.  The prefix sum of the decays is
a lower-triangular matmul, since Mosaic does not lower ``cumsum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kernel(q_ref, k_ref, v_ref, w_ref, o_ref, h_ref, *, sub: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    f32 = jnp.float32
    q = q_ref[0].astype(f32)               # (L, dk)
    k = k_ref[0].astype(f32)               # (L, dk)
    v = v_ref[0].astype(f32)               # (L, dv)
    logw = w_ref[0].astype(f32)            # (L, dk)
    L = q.shape[0]

    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum); the column of ones gives the chunk total as a (dk, 1) column
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = (row >= col).astype(f32)
    cum = _dot(tril, logw)                                # (L, dk)
    cum_total = cum[L - 1:L, :]                           # (1, dk)
    total_col = _dot_tn(logw, jnp.ones((L, 1), f32))      # (dk, 1)
    k_in = k * jnp.exp(cum_total - cum)                   # exponents <= 0

    h = h_ref[...]
    # per row sub-chunk j: y_j = inter-chunk state read + strictly-earlier
    # columns + the exact diagonal block.  The base b_j (decay prefix at the
    # sub-chunk start) lies between s and t, so exp(cum_t - b_j) and
    # exp(b_j - cum_s) stay <= 1 (no overflow, no clamp)
    for j in range(L // sub):
        lo = j * sub
        q_j = q[lo:lo + sub]
        c_j = cum[lo:lo + sub]
        y = _dot(q_j * jnp.exp(c_j), h)
        if lo:
            b = cum[lo:lo + 1]                            # (1, dk)
            k_pre = k[:lo] * jnp.exp(jnp.minimum(b - cum[:lo], 0.0))
            pre = _dot_nt(q_j * jnp.exp(c_j - b), k_pre)  # (sub, lo)
            y = y + _dot(pre, v[:lo])
        diff = c_j[:, None, :] - c_j[None, :, :]          # (sub, sub, dk)
        blk = jnp.sum(q_j[:, None, :] * k[lo:lo + sub][None, :, :]
                      * jnp.exp(jnp.minimum(diff, 0.0)), axis=-1)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        blk = jnp.where(r_i >= c_i, blk, 0.0)
        y = y + _dot(blk, v[lo:lo + sub])
        o_ref[0, lo:lo + sub, :] = y.astype(o_ref.dtype)
    # state update
    h_ref[...] = jnp.exp(total_col) * h + _dot_tn(k_in, v)


_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def linear_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, *, chunk: int = 64, sub: int = 16,
                interpret: bool) -> jax.Array:
    """q/k: (B, H, T, dk); v: (B, H, T, dv); log_decay: (B, H, T, dk) <= 0."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    sub = min(sub, chunk)
    while chunk % sub:
        sub -= 1
    chunks = T // chunk
    qf = q.reshape(B * H, T, dk)
    kf = k.reshape(B * H, T, dk)
    vf = v.reshape(B * H, T, dv)
    wf = log_decay.reshape(B * H, T, dk)

    out = pl.pallas_call(
        functools.partial(_kernel, sub=sub),
        grid=(B * H, chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, wf)
    return out.reshape(B, H, T, dv)
