"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the semantic ground truth the kernels/ implementations are
asserted against (tests sweep shapes/dtypes with assert_allclose).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------- rir_matmul
def rir_matmul(a: jax.Array, b: jax.Array, out_block_perm: Sequence[int],
               block_n: int, residual: Optional[jax.Array] = None
               ) -> jax.Array:
    """GEMM whose output N-blocks are written in permuted order (RIR epilogue).

    out[:, perm[j]*bn : (perm[j]+1)*bn] = (a @ b)[:, j*bn : (j+1)*bn]

    ``residual`` (if given) is already stored in the *output* block order and
    is added in the epilogue — the fused skip-connection add of the plan
    executor (paper Fig. 9's accumulate-into-StaB path).
    """
    y = jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    n_blocks = y.shape[1] // block_n
    out = jnp.zeros_like(y)
    for j in range(n_blocks):
        pj = int(out_block_perm[j])
        out = out.at[:, pj * block_n:(pj + 1) * block_n].set(
            y[:, j * block_n:(j + 1) * block_n])
    if residual is not None:
        out = out + residual.astype(out.dtype)
    return out


# ----------------------------------------------------------- conv2d (+depthwise)
def conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """Valid (no-padding) NHWC convolution oracle.

    x: (N, H, W, C); w: (R, S, C, M).  Returns (N, P, Q, M) with
    P = (H - R)//stride + 1, Q = (W - S)//stride + 1 — the ``ConvWorkload``
    convention, where the workload's H/W already include any SAME padding.
    """
    N, H, W, C = x.shape
    R, S, _, M = w.shape
    P = (H - R) // stride + 1
    Q = (W - S) // stride + 1
    y = jnp.zeros((N, P, Q, M), jnp.float32)
    for r in range(R):
        for s in range(S):
            tap = x[:, r:r + (P - 1) * stride + 1:stride,
                    s:s + (Q - 1) * stride + 1:stride, :]
            y = y + jnp.einsum("npqc,cm->npqm", tap.astype(jnp.float32),
                               w[r, s].astype(jnp.float32))
    return y.astype(x.dtype)


def depthwise_conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """Valid NHWC depthwise convolution oracle.

    x: (N, H, W, M); w: (R, S, M) — one RxS filter per channel.
    """
    N, H, W, M = x.shape
    R, S, _ = w.shape
    P = (H - R) // stride + 1
    Q = (W - S) // stride + 1
    y = jnp.zeros((N, P, Q, M), jnp.float32)
    for r in range(R):
        for s in range(S):
            tap = x[:, r:r + (P - 1) * stride + 1:stride,
                    s:s + (Q - 1) * stride + 1:stride, :]
            y = y + tap.astype(jnp.float32) * w[r, s].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------- birrd_reduce
def birrd_reduce(x: jax.Array, group_ids: jax.Array, out_ports: jax.Array,
                 num_outputs: int) -> jax.Array:
    """Grouped reduction + scatter: the RIR semantic spec over rows of x."""
    from repro.core.rir import rir_reduce_reorder
    return rir_reduce_reorder(x, group_ids, out_ports, num_outputs)


# ----------------------------------------------------------------- gqa_decode
def gqa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
               lengths: Optional[jax.Array] = None,
               scale: Optional[float] = None) -> jax.Array:
    """Single-token GQA decode attention.

    q: (B, Hq, D); k/v: (B, Hkv, S, D) head-major cache; lengths: (B,)
    valid KV length.  Hq = G * Hkv.  Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, G, D)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if lengths is not None:
        mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------- linear_scan
def _intra_chunk_scores(qq, kk, cum, sub: int = 16):
    """Exact, overflow-free masked intra-chunk attention scores.

    S[t, s] = sum_d q[t,d] k[s,d] exp(cum[t,d] - cum[s,d]) for s <= t, else 0.

    Stability: for each row sub-chunk j, factor through the base b_j =
    decay-prefix at the sub-chunk start, which lies BETWEEN s and t, so both
    exponents (cum_t - b_j) and (b_j - cum_s) are <= 0 — no clamping needed.
    The diagonal sub-blocks use the direct (sub, sub, dk) form (also <= 0).
    """
    L, dk = qq.shape
    sub = min(sub, L)
    while L % sub:
        sub -= 1
    nsub = L // sub
    t_idx = jnp.arange(L)
    rows = []
    for j in range(nsub):
        lo = j * sub
        b = cum[lo] - 0.0                                   # (dk,)
        q_j = qq[lo:lo + sub] * jnp.exp(cum[lo:lo + sub] - b[None, :])
        # columns strictly before this sub-chunk
        k_pre = kk * jnp.exp(jnp.minimum(b[None, :] - cum, 0.0))
        pre = q_j @ k_pre.T                                 # (sub, L)
        col_mask = (t_idx < lo)[None, :]
        pre = jnp.where(col_mask, pre, 0.0)
        # exact diagonal block
        cd = cum[lo:lo + sub]
        diff = cd[:, None, :] - cd[None, :, :]              # (sub, sub, dk)
        blk = jnp.sum(qq[lo:lo + sub][:, None, :] * kk[lo:lo + sub][None, :, :]
                      * jnp.exp(jnp.minimum(diff, 0.0)), axis=-1)
        tri = jnp.tril(jnp.ones((sub, sub), bool))
        blk = jnp.where(tri, blk, 0.0)
        row = pre.at[:, lo:lo + sub].add(blk)
        rows.append(row)
    return jnp.concatenate(rows, axis=0)                    # (L, L)


def linear_scan_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                        log_decay: jax.Array, chunk: int = 64) -> jax.Array:
    """Pure-jnp chunked GLA scan — same algorithm as the Pallas kernel
    (GEMMs per chunk, state carried across chunks).  This is the XLA-lowered
    path the dry-run uses: T/chunk sequential steps instead of T.
    """
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, T)
    while T % chunk:
        chunk -= 1
    n = T // chunk
    f32 = jnp.float32

    def per_bh(qb, kb, vb, wb):
        qc = qb.reshape(n, chunk, dk).astype(f32)
        kc = kb.reshape(n, chunk, dk).astype(f32)
        vc = vb.reshape(n, chunk, dv).astype(f32)
        wc = wb.reshape(n, chunk, dk).astype(f32)

        # remat the intra-chunk scores: their (sub, sub, dk) intermediates
        # would otherwise be saved across every chunk step for the backward
        scores_fn = jax.checkpoint(
            lambda qq, kk, cum: _intra_chunk_scores(qq, kk, cum))

        def step(h, inp):
            qq, kk, vv, ww = inp
            cum = jnp.cumsum(ww, axis=0)
            tot = cum[-1:, :]
            q_in = qq * jnp.exp(cum)                        # <= 0 exponents
            k_in = kk * jnp.exp(tot - cum)                  # <= 0
            y = q_in @ h
            y = y + scores_fn(qq, kk, cum) @ vv
            h = jnp.exp(tot.T) * h + k_in.T @ vv
            return h, y

        h0 = jnp.zeros((dk, dv), f32)
        _, ys = jax.lax.scan(step, h0, (qc, kc, vc, wc))
        return ys.reshape(T, dv)

    out = jax.vmap(jax.vmap(per_bh))(q, k, v, log_decay)
    return out.astype(v.dtype)


def linear_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array) -> jax.Array:
    """Gated linear attention / SSM scan (mamba2, rwkv6 core).

    Recurrence over t (state h: (dk, dv) per (B, H)):
        h_t = exp(log_decay_t)[:, None] * h_{t-1} + k_t^T v_t
        y_t = q_t @ h_t

    q/k: (B, H, T, dk); v: (B, H, T, dv); log_decay: (B, H, T, dk) (<= 0).
    Returns (B, H, T, dv), computed in fp32.
    """
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    w = jnp.exp(log_decay.astype(jnp.float32))

    def step(h, inp):
        qt, kt, vt, wt = inp
        h = h * wt[:, None] + kt[:, None] * vt[None, :]
        return h, qt @ h

    def scan_bh(qb, kb, vb, wb):
        h0 = jnp.zeros((qb.shape[-1], vb.shape[-1]), jnp.float32)
        _, y = jax.lax.scan(step, h0, (qb, kb, vb, wb))
        return y

    f = jax.vmap(jax.vmap(scan_bh))
    return f(qf, kf, vf, w).astype(v.dtype)
