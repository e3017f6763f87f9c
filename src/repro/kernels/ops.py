"""Public jit'd wrappers for the Pallas kernels.

The platform picks the mode: on a TPU the kernels compile to Mosaic; on the
CPU (the test suite) they run in ``interpret=True`` mode, which executes the
kernel bodies for correctness.  Any other backend raises — a TPU that failed
to initialize must not quietly run every kernel interpreted.
``use_kernels(False)`` swaps in the pure-jnp references (used by the dry-run
so lowering stays pure XLA).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from . import ref
from .birrd_reduce import birrd_reduce as _birrd_reduce
from .gqa_decode import gqa_decode as _gqa_decode
from .linear_scan import linear_scan as _linear_scan
from .rir_matmul import rir_matmul as _rir_matmul

_KERNELS_ENABLED = True


def use_kernels(enabled: bool) -> None:
    global _KERNELS_ENABLED
    _KERNELS_ENABLED = enabled


def kernels_enabled() -> bool:
    return _KERNELS_ENABLED


def _interpret(backend: Optional[str] = None) -> bool:
    """Interpret mode on the CPU, compiled kernels on a TPU; nothing else."""
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or interpreted "
                       f"on 'cpu'; the {backend!r} backend has neither")


def rir_matmul(a: jax.Array, b: jax.Array,
               out_block_perm: Optional[Sequence[int]] = None, *,
               residual: Optional[jax.Array] = None,
               block_m: int = 128, block_n: int = 128, block_k: int = 128
               ) -> jax.Array:
    if not _KERNELS_ENABLED:
        return ref.rir_matmul(a, b, out_block_perm or
                              tuple(range(b.shape[1] // block_n)), block_n,
                              residual=residual)
    perm = tuple(out_block_perm) if out_block_perm is not None else None
    return _rir_matmul(a, b, perm, residual=residual, block_m=block_m,
                       block_n=block_n, block_k=block_k,
                       interpret=_interpret())


def birrd_reduce(x: jax.Array, group_ids: Sequence[int],
                 out_ports: Sequence[int], *, block_d: int = 128) -> jax.Array:
    if not _KERNELS_ENABLED:
        gi = jnp.asarray(list(group_ids), jnp.int32)
        op = jnp.asarray(list(out_ports), jnp.int32)
        return ref.birrd_reduce(x, gi, op, x.shape[0])
    return _birrd_reduce(x, tuple(group_ids), tuple(out_ports),
                         block_d=block_d, interpret=_interpret())


def _decode_block(S: int, block_s: int) -> int:
    """KV block for a length-S cache: the whole cache if it fits one block,
    else the largest multiple of 8 in [block_s / 4, block_s] dividing S,
    else ``block_s`` (the caller pads S up to it)."""
    if S <= block_s:
        return S
    for bs in range(block_s - block_s % 8, max(8, block_s // 4) - 1, -8):
        if S % bs == 0:
            return bs
    return block_s


def gqa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
               lengths: jax.Array, *, block_s: int = 512) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, Hkv, S, D) head-major cache; lengths: (B,)."""
    if not _KERNELS_ENABLED:
        return ref.gqa_decode(q, k, v, lengths)
    S = k.shape[2]
    bs = _decode_block(S, block_s)
    pad = -S % bs
    if pad:
        # positions past S are masked by ``lengths`` like any unfilled slot
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    return _gqa_decode(q, k, v, lengths, block_s=bs,
                       interpret=_interpret())


@jax.custom_vjp
def _linear_scan_ad(q, k, v, log_decay):
    return _linear_scan(q, k, v, log_decay, interpret=_interpret())


def _ls_fwd(q, k, v, log_decay):
    return _linear_scan_ad(q, k, v, log_decay), (q, k, v, log_decay)


def _ls_bwd(res, g):
    # backward through the pure-XLA chunked path (same math; a dedicated
    # backward kernel is future work — on TPU this recomputes fwd in XLA)
    _, vjp = jax.vjp(ref.linear_scan_chunked, *res)
    return vjp(g)


_linear_scan_ad.defvjp(_ls_fwd, _ls_bwd)


def linear_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, *, chunk: int = 64) -> jax.Array:
    if not _KERNELS_ENABLED:
        # pure-XLA path: chunked (not per-step) so the dry-run lowers the
        # same three-GEMM structure the Pallas kernel executes
        import os
        ck = int(os.environ.get('REPRO_SCAN_CHUNK', chunk))
        return ref.linear_scan_chunked(q, k, v, log_decay, chunk=ck)
    return _linear_scan_ad(q, k, v, log_decay)


__all__ = ["rir_matmul", "birrd_reduce", "gqa_decode",
           "linear_scan", "use_kernels", "kernels_enabled"]
