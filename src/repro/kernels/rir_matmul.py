"""RIR matmul — GEMM with the Reorder-In-Reduction epilogue (paper §II-E2).

The TPU-native transposition of FEATHER's key idea: the *producing* matmul
writes each output tile directly at the position the *consumer's* dataflow
wants (an arbitrary permutation of N-blocks), so switching the next layer's
layout costs zero extra passes over HBM — the reorder rides the reduction.

Mechanics: the K grid dimension accumulates partial products in a VMEM
scratch accumulator (NEST's local temporal reduction); on the last K step the
tile is emitted through a permuted output BlockSpec index map (BIRRD's output
port routing).  The permutation is a scalar-prefetch operand — the runtime
analogue of FEATHER's Instruction Buffer: the layout program can change per
layer without recompiling the kernel.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b):
    # float32 operands get a float32-exact MXU contraction, pinned rather
    # than left to the compiler's default precision
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _kernel(perm_ref, a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    del perm_ref  # consumed by the output index map
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_res(perm_ref, a_ref, b_ref, r_ref, o_ref, acc_ref, *,
                k_steps: int):
    """Residual-fused variant: the skip tensor rides the epilogue write.

    ``r_ref`` is blocked with the SAME permuted index map as the output, so
    the residual is consumed in its stored (boundary-layout) order — the
    fused skip-connection add costs no extra pass over the activation.
    """
    del perm_ref
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = (acc_ref[...]
                      + r_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def rir_matmul_p(a: jax.Array, b: jax.Array, out_block_perm: jax.Array, *,
                 residual: jax.Array | None = None,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 interpret: bool) -> jax.Array:
    """``(a @ b)`` with output N-blocks scattered per ``out_block_perm``.

    a: (M, K), b: (K, N); out_block_perm: int32[(N//block_n,)] permutation
    (a *dynamic* operand — the RIR "instruction buffer").  ``residual``
    (optional, (M, N), stored in the *output* block order) is added in the
    epilogue on the last K step — the executor's fused residual-join path.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "shapes must tile", a.shape, b.shape, (block_m, block_n, block_k))
    n_blocks = N // block_n
    k_steps = K // block_k
    grid = (M // block_m, n_blocks, k_steps)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k, perm: (i, k)),
        pl.BlockSpec((block_k, block_n), lambda i, j, k, perm: (k, j)),
    ]
    operands = [a, b]
    kernel = _kernel
    if residual is not None:
        assert residual.shape == (M, N), (residual.shape, (M, N))
        # the residual is read through the same permuted map the output is
        # written through: both live in the consumer's boundary layout
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, j, k, perm: (i, perm[j])))
        operands.append(residual)
        kernel = _kernel_res

    return pl.pallas_call(
        functools.partial(kernel, k_steps=k_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            # RIR: the output tile index is permuted — layout switching
            # happens in the write, not as a separate pass.
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, k, perm: (i, perm[j])),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(out_block_perm.astype(jnp.int32), *operands)


def rir_matmul(a: jax.Array, b: jax.Array,
               out_block_perm: Sequence[int] | None = None, *,
               residual: jax.Array | None = None,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               interpret: bool) -> jax.Array:
    n_blocks = b.shape[1] // block_n
    if out_block_perm is None:
        out_block_perm = tuple(range(n_blocks))
    assert sorted(int(p) for p in out_block_perm) == list(range(n_blocks)), \
        "not a permutation"
    perm = jnp.asarray(list(out_block_perm), jnp.int32)
    return rir_matmul_p(a, b, perm, residual=residual, block_m=block_m,
                        block_n=block_n, block_k=block_k, interpret=interpret)
