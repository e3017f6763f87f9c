"""BIRRD reduce — staged butterfly grouped-reduction + reorder kernel.

Executes the 2*log2(AW)-stage Egg-switch network (paper Fig. 8) with wires on
the sublane axis and the feature dimension on lanes.  Each stage s is lowered
to a tiny stage matrix

    M_s = W_s @ (diag(alpha_s) + diag(beta_s) @ E)

where E is the switch-partner exchange, (alpha, beta) encode the Egg config
(Pass/Swap/Add-Left/Add-Right) per wire and W_s is the Alg. 1 inter-stage
wiring — so a stage is one (aw x aw) x (aw x d) MXU matmul and the whole
network is an O(n log n)-structured product, the systolic twin of the RTL.
The stage matrices are passed as a kernel operand (FEATHER's Instruction
Buffer analogue): reconfiguring the dataflow/layout per layer swaps the
program, not the kernel.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.birrd import ADD_LEFT, ADD_RIGHT, PASS, SWAP, Birrd


@functools.lru_cache(maxsize=64)
def _birrd(aw: int) -> Birrd:
    """One shared (stateless-after-init) network model per width."""
    return Birrd(aw)


def compile_switch_program(aw: int, configs: Sequence[Sequence[int]]
                           ) -> np.ndarray:
    """Lower per-stage Egg configs to stacked stage matrices (S, aw, aw).

    Memoized per ``(aw, configs)``: a layer's switch program is compiled
    once and reused by every subsequent call (FEATHER reprograms the
    Instruction Buffer per layer, not per tile).  Callers must not mutate
    the returned array.
    """
    return _compile_switch_program(aw, tuple(tuple(row) for row in configs))


@functools.lru_cache(maxsize=1024)
def _compile_switch_program(aw: int, configs: Tuple[Tuple[int, ...], ...]
                            ) -> np.ndarray:
    net = _birrd(aw)
    mats = []
    for stage, row in enumerate(configs):
        alpha = np.zeros(aw, np.float32)
        beta = np.zeros(aw, np.float32)
        for sw, cfg in enumerate(row):
            l, r = 2 * sw, 2 * sw + 1
            if cfg == PASS:
                alpha[l] = alpha[r] = 1.0
            elif cfg == SWAP:
                beta[l] = beta[r] = 1.0
            elif cfg == ADD_LEFT:   # left out = l + r; right out = r
                alpha[l], beta[l] = 1.0, 1.0
                alpha[r] = 1.0
            elif cfg == ADD_RIGHT:  # right out = l + r; left out = l
                alpha[l] = 1.0
                alpha[r], beta[r] = 1.0, 1.0
            else:
                raise ValueError(f"bad config {cfg}")
        sw_mat = np.diag(alpha)
        for w in range(aw):
            sw_mat[w, w ^ 1] += beta[w]
        wiring = np.zeros((aw, aw), np.float32)
        for j in range(aw):
            wiring[net.perms[stage][j], j] = 1.0
        mats.append(wiring @ sw_mat)
    return np.stack(mats)


def _kernel(m_ref, x_ref, o_ref, *, num_stages: int):
    vals = x_ref[...].astype(jnp.float32)
    for s in range(num_stages):
        vals = jnp.dot(m_ref[s], vals, preferred_element_type=jnp.float32)
    o_ref[...] = vals.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def birrd_apply_p(x: jax.Array, stage_mats: jax.Array, *, block_d: int = 128,
                  interpret: bool) -> jax.Array:
    """Push ``x`` (aw, d) through a compiled BIRRD switch program."""
    aw, d = x.shape
    S = stage_mats.shape[0]
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)
    return pl.pallas_call(
        functools.partial(_kernel, num_stages=S),
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((S, aw, aw), lambda j: (0, 0, 0)),
            pl.BlockSpec((aw, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((aw, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((aw, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(stage_mats, x)


def birrd_apply(x: jax.Array, configs, *, block_d: int = 128,
                interpret: bool) -> jax.Array:
    """Route ``x`` (aw, d) through BIRRD configured by ``configs``."""
    mats = jnp.asarray(compile_switch_program(x.shape[0], configs))
    return birrd_apply_p(x, mats, block_d=block_d, interpret=interpret)


@functools.lru_cache(maxsize=1024)
def _routed_stage_mats(aw: int, group_ids: Tuple[int, ...],
                       out_ports: Tuple[int, ...]) -> jax.Array:
    """Route + lower + upload, memoized per reduction/reorder pattern: the
    backtracking search, stage-matrix lowering AND the host->device transfer
    run once per ``(aw, group_ids, out_ports)``; repeat calls are dict hits."""
    cfg = _birrd(aw).route(list(group_ids), list(out_ports))
    if cfg is None:
        raise ValueError("BIRRD routing failed for the requested pattern")
    return jnp.asarray(_compile_switch_program(aw, tuple(tuple(r)
                                                         for r in cfg)))


@functools.lru_cache(maxsize=1024)
def _out_port_mask(aw: int, out_ports: Tuple[int, ...]) -> np.ndarray:
    mask = np.zeros((aw, 1), np.bool_)
    for p in out_ports:
        mask[int(p)] = True
    return mask


def birrd_reduce(x: jax.Array, group_ids: Sequence[int],
                 out_ports: Sequence[int], *, block_d: int = 128,
                 interpret: bool) -> jax.Array:
    """Route + execute: grouped reduction with arbitrary output reorder.

    x: (aw, d).  Returns (aw, d) with group sums at their target ports and
    zeros elsewhere (junk/bubble ports are masked, as the OB write-enable
    does in hardware).
    """
    aw = x.shape[0]
    mats = _routed_stage_mats(aw, tuple(int(g) for g in group_ids),
                              tuple(int(p) for p in out_ports))
    y = birrd_apply_p(x, mats, block_d=block_d, interpret=interpret)
    mask = _out_port_mask(aw, tuple(int(p) for p in out_ports))
    return jnp.where(jnp.asarray(mask), y, jnp.zeros_like(y))
