# check: ignore-file[api-boundary]  (paper-figure/perf benchmark: deliberately exercises core internals)
"""Kernel microbenches — wall time of the jit'd XLA reference paths on CPU
(the Pallas interpret path measures Python, not hardware) + arithmetic
intensity bookkeeping for the roofline narrative."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

from .common import emit, timeit


def run():
    rng = np.random.default_rng(0)
    rows = []

    # rir_matmul-shaped GEMM
    M, K, N = 512, 512, 512
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    perm = tuple(int(x) for x in rng.permutation(N // 128))
    f = jax.jit(lambda a, b: ref.rir_matmul(a, b, perm, 128))
    us = timeit(lambda: f(a, b))
    flops = 2 * M * K * N
    rows.append(("kern.rir_matmul_512", us,
                 f"gflops={flops/us/1e3:.1f}"))

    # gqa decode
    B, Hq, Hkv, D, S = 4, 16, 4, 128, 8192
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)
    f = jax.jit(ref.gqa_decode)
    us = timeit(lambda: f(q, k, v, lens))
    bytes_moved = 2 * B * S * Hkv * D * 4
    rows.append(("kern.gqa_decode_8k", us,
                 f"gbps={bytes_moved/us/1e3:.1f}"))

    # linear scan (chunked)
    B, H, T, dk, dv = 2, 8, 2048, 64, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, dk)), jnp.float32)
    k2 = jnp.asarray(rng.normal(size=(B, H, T, dk)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(B, H, T, dv)), jnp.float32)
    w = jnp.asarray(-np.abs(rng.normal(size=(B, H, T, dk)) * 0.1), jnp.float32)
    f = jax.jit(ref.linear_scan_chunked)
    us = timeit(lambda: f(q, k2, v2, w))
    rows.append(("kern.linear_scan_2k", us,
                 f"tokens_per_s={B*T/(us/1e6):.0f}"))

    # birrd_reduce via routing-matrix spec
    from repro.kernels import ops
    x = jnp.asarray(rng.normal(size=(16, 4096)), jnp.float32)
    gids = [i // 4 for i in range(16)]
    ports = [0, 4, 8, 12]
    us = timeit(lambda: ops.birrd_reduce(x, gids, ports))
    rows.append(("kern.birrd_reduce_16x4096", us, "staged-butterfly"))
    return rows


def main():
    rows = run()
    emit(rows)
    return rows


if __name__ == "__main__":
    main()
