"""Plan-driven execution: run an ``ExecutionPlan`` through the Pallas kernels.

The executor is the TPU realization of the planner's promise: every layer's
output is written by the ``rir_matmul`` epilogue *directly in the layout the
next layer wants* (RIR — the reorder rides the reduction), so no standalone
relayout pass ever runs between layers.  Concretely:

* A boundary layout reduces, at kernel granularity, to a permutation of
  128-wide feature blocks (``plan.layout_block_perm``).
* The epilogue permutation of step *i* is derived from consecutive plan
  entries: it is the block order of ``steps[i].out_layout`` — which the plan
  guarantees equals ``steps[i+1].in_layout``.
* Weights are static, so each layer's weight matrix is pre-arranged offline
  (`permute_weight_blocks`) to contract correctly against an activation
  stored in the incoming boundary layout — the consumer reads concordantly,
  for free.

Per-boundary gather indices are memoized per ``(perm, block)``, and
``prepare_plan`` hoists everything that depends only on ``(plan, shapes)`` —
boundary perms, gather indices, pre-permuted weights — out of the per-call
path, so a served plan pays the index/weight setup once, not per batch.

The executor's output (returned in canonical block order) is bit-identical
to the plain ``x @ W1 @ ... @ Wn`` chain; tests assert this against the
``kernels/ref.py`` oracles.

Beyond GEMM chains, ``execute_network`` runs COMPLETE ``LayerGraph``s —
convolutions and residual joins included — through the same Pallas path:

* Convolutions lower to implicit GEMM: an im2col patch gather whose row map
  composes the boundary adapter with the tap offsets, and whose column
  order is the *producer's stored (boundary-layout) order*, so the consumer
  reads the discordant-free layout directly.  The layout choice is folded
  into the effective weight (per-tap K-block alignment), never into a
  standalone relayout pass.  Depthwise layers use the block-diagonal dense
  form of the same GEMM.
* Skip edges (``LayerGraph.skip_edges``) buffer the source activation in
  its boundary layout; at the join the planner-recorded relayout
  (``PlanStep.joins``) is applied, and when the two boundary layouts agree
  the residual add is FUSED into the consumer's ``rir_matmul`` epilogue
  (the kernel's ``residual`` operand) — no separate pass.
* Fused layer groups (``PlanStep.fused_with``, schema v4) are validated
  (each member chains into the next layer) and dispatched step by step,
  with the math bit-identical to the unfused schedule; each step's output
  is still a separate device array.

All of it validates against the canonical ``execute_network_reference``
oracle built on ``kernels/ref.py`` conv/depthwise references.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.workloads import input_channels, is_depthwise, weight_shape
from repro.kernels import ops, ref
from repro.runtime import faults

from .graph import LayerGraph
from .plan import RIR_BLOCK, ExecutionPlan, PlanStep, layout_block_perm

# the smallest kernel block the tile-derived grid may shrink to: below this
# the grid bookkeeping dwarfs the MXU work (and interpret-mode test time)
MIN_KERNEL_BLOCK = 64


def _plan_provenance(plan: ExecutionPlan) -> Dict[str, object]:
    """Span attributes joining a measured interval back to its plan artifact."""
    return {"plan_id": plan.plan_id, "graph_hash": plan.graph_hash,
            "schema_version": plan.version, "graph": plan.graph_name}


def _step_attrs(prov: Dict[str, object], i: int, step: PlanStep
                ) -> Dict[str, object]:
    """Per-step span attributes: provenance, the step's index and layer.

    Under a profiler session they ride on the step's annotation, so a host
    interval in the device trace names the plan step that dispatched it.
    """
    return dict(prov, step=i, layer=step.layer)


class PlanError(ValueError):
    """A plan is internally inconsistent or doesn't fit the given tensors."""


def _pow2_floor(x: int) -> int:
    return 1 << (max(1, int(x)).bit_length() - 1)


def _pow2_ceil(x: int) -> int:
    return 1 << (max(1, int(x)) - 1).bit_length()


# the smallest row block the tile-derived grid may shrink to when the tile
# itself is tiny: the f32 sublane tile height (Pallas min tile is (8, 128))
_SUBLANE_MIN = 8


def _clamp_block(extent: int, block: int) -> int:
    """Kernel block for one axis of a tiled extent.

    Extents at or above ``MIN_KERNEL_BLOCK`` keep the old rule — the
    largest power of two under the extent, clamped into
    ``[MIN_KERNEL_BLOCK, block]``.  Extents BELOW it used to be silently
    rounded UP to ``MIN_KERNEL_BLOCK`` (a 4-row depthwise tile got a
    64-row block: 16x zero padding per grid cell); now they get the
    smallest power of two covering the extent, floored at the f32 sublane
    minimum, so the grid matches what the tile actually keeps resident.
    """
    return max(_SUBLANE_MIN,
               min(block, _pow2_ceil(extent),
                   max(MIN_KERNEL_BLOCK, _pow2_floor(extent))))


# Mosaic tiles a block's last (lane) dim in 128s: the A operand's K block
# must be a multiple of this, or span the whole (padded) K
_LANE = 128


def _align_k_block(bk: int, k: int) -> int:
    """Raise a tile-derived K block to what the TPU compiler accepts: keep
    it when it already covers K (the executor pads K up to it) or is
    lane-aligned, else round it up to the next multiple of ``_LANE``."""
    if bk >= k or bk % _LANE == 0:
        return bk
    return _LANE * -(-bk // _LANE)


def step_kernel_blocks(step: PlanStep, block: int = RIR_BLOCK, *,
                       k: Optional[int] = None) -> Tuple[int, int]:
    """(block_m, block_k) the kernel grid should use for this step.

    The plan's on-chip tiling bounds how many GEMM rows (``N*P*Q`` tile) and
    reduction elements (``C`` tile x taps) one pass keeps resident, so the
    kernel's block/grid shape follows the artifact instead of a hardcoded
    constant (``_clamp_block`` per axis).  A double-buffered step (schema
    v3) only keeps HALF the tile resident per ping-pong phase, so the row
    extent absorbs one halving before the clamp (halving a single axis
    halves the block footprint, matching the cost model's halved
    capacity); a per-tensor allocation (schema v4) halves the rows only
    when the iActs are among the ping-pong'd tensors — single-buffered
    iActs keep their full tile resident.  Tile-less single-buffered
    steps (v1 artifacts, untiled plans) keep the full ``block`` — the
    pre-tiling behaviour.  The output feature axis always stays at
    ``block``: epilogue permutations are defined over ``RIR_BLOCK``-wide
    boundary-layout blocks.

    The K block is then aligned for the TPU (``_align_k_block``) against
    ``k``, the GEMM's actual reduction length — the executor's im2col
    width, which follows the producer's channels rather than the
    workload's ``C`` where the boundary adapter pads or truncates.  It
    defaults to the workload's ``C * R * S``.
    """
    wl = step.workload
    k = wl.C * wl.R * wl.S if k is None else k
    if not step.tiles and not step.double_buffer:
        return block, _align_k_block(block, k)
    t = dict(step.tiles)

    def ext(d: str, size: int) -> int:
        return max(1, min(size, t.get(d, size)))

    rows = ext("N", wl.N) * ext("P", wl.P) * ext("Q", wl.Q)
    kdim = ext("C", wl.C) * wl.R * wl.S
    db_iact = ("iact" in step.buffer_alloc) if step.buffer_alloc \
        else step.double_buffer
    if db_iact:
        rows = max(1, rows // 2)
    return (_clamp_block(rows, block),
            _align_k_block(_clamp_block(kdim, block), k))


def fold_batchnorm(w: jax.Array, gamma, beta, mean, var,
                   eps: float = 1e-5, conv_bias=None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fold inference batch-norm (+ optional conv bias) into the weights.

    ``BN(conv(x, w) + conv_bias)`` == ``conv(x, w * s) + b`` with
    ``s = gamma / sqrt(var + eps)`` (per output channel) and
    ``b = beta + (conv_bias - mean) * s``.  The scaled weight feeds the
    executor's effective-weight pipeline unchanged (the ``w_eff`` hook
    point); the returned bias vector goes in via ``biases=`` on
    ``prepare_network`` / ``execute_network``.  Works for both dense
    ``(R, S, C, M)`` and depthwise ``(R, S, M)`` weights — the output
    channel is the last axis of each.
    """
    w = jnp.asarray(w, jnp.float32)
    gamma, beta, mean, var = (jnp.asarray(a, jnp.float32)
                              for a in (gamma, beta, mean, var))
    scale = gamma / jnp.sqrt(var + eps)
    bias = beta - mean * scale
    if conv_bias is not None:
        bias = bias + jnp.asarray(conv_bias, jnp.float32) * scale
    return w * scale, bias


@functools.lru_cache(maxsize=4096)
def _gather_indices(perm: Tuple[int, ...], block: int) -> np.ndarray:
    """Flat gather such that ``x[..., idx]`` stores canonical block j at slot
    ``perm[j]`` (equivalently: prepares weights stored per ``perm``)."""
    n = len(perm)
    cols = np.zeros(n, np.int64)
    cols[np.asarray(perm)] = np.arange(n)
    return (cols[:, None] * block + np.arange(block)[None, :]).reshape(-1)


@functools.lru_cache(maxsize=4096)
def _scatter_indices(perm: Tuple[int, ...], block: int) -> np.ndarray:
    """Flat gather recovering canonical order from a ``perm``-stored tensor."""
    return (np.asarray(perm)[:, None] * block
            + np.arange(block)[None, :]).reshape(-1)


def apply_block_perm(x: jax.Array, perm: Sequence[int],
                     block: int = RIR_BLOCK) -> jax.Array:
    """Store canonical column-block j at slot ``perm[j]`` (RIR write order)."""
    n = len(perm)
    if n * block != x.shape[-1]:
        raise PlanError(f"perm of {n} blocks x {block} != dim {x.shape[-1]}")
    return x[..., _gather_indices(tuple(perm), block)]


def invert_block_perm(x: jax.Array, perm: Sequence[int],
                      block: int = RIR_BLOCK) -> jax.Array:
    """Recover canonical order from a ``perm``-stored tensor."""
    return x[..., _scatter_indices(tuple(perm), block)]


def permute_weight_blocks(w: jax.Array, in_perm: Sequence[int],
                          block: int = RIR_BLOCK) -> jax.Array:
    """Offline weight prep: scatter K-blocks so ``w_eff`` contracts against an
    activation stored in the incoming boundary layout."""
    n = len(in_perm)
    if n * block != w.shape[0]:
        raise PlanError(f"in_perm of {n} blocks x {block} != K {w.shape[0]}")
    return w[_gather_indices(tuple(in_perm), block), :]


def _derive_boundary_perms(plan: ExecutionPlan, dims: Sequence[int],
                           block: int) -> List[tuple]:
    """Derive every boundary's block permutation from consecutive entries.

    ``dims[b]`` is the feature width of boundary ``b`` (network input for
    b=0, layer b-1's output after).  Shared by the GEMM-chain and
    whole-network prepared paths so the perm rules can never diverge.
    """
    steps = plan.steps
    for i in range(len(steps) - 1):
        if steps[i].out_layout != steps[i + 1].in_layout:
            raise PlanError(
                f"plan discontinuity at {steps[i].layer} -> "
                f"{steps[i + 1].layer}: {steps[i].out_layout} != "
                f"{steps[i + 1].in_layout}")
    perms = []
    for b, dim in enumerate(dims):
        name = steps[b].in_layout if b < len(steps) else steps[-1].out_layout
        n_blocks = dim // block if dim % block == 0 else 1
        if n_blocks <= 1:
            perms.append((0,))
            continue
        # honour the perm the artifact recorded (boundary b is written by
        # step b-1's epilogue) when it fits this tensor's block count;
        # otherwise derive it from the boundary layout name
        recorded = steps[b - 1].epilogue_perm if b > 0 else None
        if recorded is not None and len(recorded) == n_blocks:
            perms.append(tuple(recorded))
        else:
            perms.append(layout_block_perm(name, n_blocks))
    return perms


def _boundary_perms(plan: ExecutionPlan, x_dim: int,
                    weights: Sequence[jax.Array],
                    block: int) -> List[tuple]:
    """GEMM-chain form: boundary widths come from the 2D weight shapes."""
    return _derive_boundary_perms(
        plan, [x_dim] + [w.shape[1] for w in weights], block)


class PreparedPlan:
    """Everything ``execute_plan`` derives from ``(plan, shapes)`` alone.

    Boundary perms, gather indices, and the pre-permuted (effective) weight
    matrices are computed once here; calling the object runs only the
    per-batch matmul chain.  Reuse one instance across ``execute_plan`` calls
    that share the plan and weights (e.g. every serving batch).
    """

    def __init__(self, plan: ExecutionPlan, x_dim: int,
                 weights: Sequence[jax.Array], *, block: int = RIR_BLOCK):
        if len(weights) != len(plan.steps):
            raise PlanError(
                f"{len(weights)} weights for {len(plan.steps)} steps")
        for i, w in enumerate(weights):
            k_prev = x_dim if i == 0 else weights[i - 1].shape[1]
            if w.shape[0] != k_prev:
                raise PlanError(
                    f"weight {i} K={w.shape[0]} != producer M={k_prev}")
        self.plan = plan
        self.block = block
        self.x_dim = x_dim
        self.weights = tuple(weights)
        self.perms = _boundary_perms(plan, x_dim, weights, block)
        # per-step kernel blocking, derived from the plan's tiling
        self.blocks = [step_kernel_blocks(s, block, k=w.shape[0])
                       for s, w in zip(plan.steps, weights)]
        self.w_eff = [
            permute_weight_blocks(w, self.perms[i], block)
            if len(self.perms[i]) > 1 else w
            for i, w in enumerate(weights)]
        self._prov: Optional[Dict[str, object]] = None

    def _provenance(self) -> Dict[str, object]:
        if self._prov is None:
            self._prov = _plan_provenance(self.plan)
        return self._prov

    def __call__(self, x: jax.Array, *,
                 activation: Optional[Callable[[jax.Array], jax.Array]] = None,
                 use_pallas: bool = True) -> jax.Array:
        plan, block, perms = self.plan, self.block, self.perms
        # spans time host dispatch only: nothing here waits for the device
        active = obs.active()
        with obs.span("exec.chain",
                      dict(self._provenance(), pallas=bool(use_pallas),
                           rows=int(x.shape[0])) if active else None):
            cur = apply_block_perm(x, perms[0], block) \
                if len(perms[0]) > 1 else x
            for i, (step, w_eff) in enumerate(zip(plan.steps, self.w_eff)):
                faults.site(faults.EXEC_DISPATCH)
                with obs.span("exec.step", _step_attrs(
                        self._provenance(), i, step) if active else None):
                    out_perm = perms[i + 1]
                    bm, bk = self.blocks[i]
                    tiled = (cur.shape[0] % bm == 0
                             and w_eff.shape[0] % bk == 0
                             and w_eff.shape[1] % block == 0)
                    if use_pallas and tiled and step.kernel == "rir_matmul":
                        cur = ops.rir_matmul(cur, w_eff, out_perm
                                             if len(out_perm) > 1 else None,
                                             block_m=bm, block_n=block,
                                             block_k=bk)
                    else:
                        y = jnp.dot(cur, w_eff,
                                    preferred_element_type=jnp.float32)
                        y = y.astype(cur.dtype)
                        cur = apply_block_perm(y, out_perm, block) \
                            if len(out_perm) > 1 else y
                    if activation is not None and i < len(plan.steps) - 1:
                        # elementwise: commutes with block perms
                        cur = activation(cur)
            out = invert_block_perm(cur, perms[-1], block) \
                if len(perms[-1]) > 1 else cur
        return out


def prepare_plan(plan: ExecutionPlan, x_dim: int,
                 weights: Sequence[jax.Array], *,
                 block: int = RIR_BLOCK) -> PreparedPlan:
    """Hoist boundary perms + effective weights out of the per-call path."""
    return PreparedPlan(plan, x_dim, weights, block=block)


def _prepared_is_stale(prepared, plan: ExecutionPlan, block: int,
                       weights: Sequence[jax.Array]) -> bool:
    """Shared (plan, block, weights-identity) staleness test for prepared
    objects — a stale one must fail loudly, never compute with old state."""
    return (prepared.plan != plan or prepared.block != block
            or len(prepared.weights) != len(weights)
            or any(got is not want for got, want
                   in zip(prepared.weights, weights)))


def execute_plan(plan: ExecutionPlan, x: jax.Array,
                 weights: Sequence[jax.Array], *, block: int = RIR_BLOCK,
                 activation: Optional[Callable[[jax.Array], jax.Array]] = None,
                 use_pallas: bool = True,
                 prepared: Optional[PreparedPlan] = None) -> jax.Array:
    """Execute a planned GEMM chain end-to-end; returns canonical output.

    x: (tokens, K0); weights[i]: (K_i, M_i) with M_i == K_{i+1}.  Each step
    runs the RIR matmul with the epilogue permutation derived from the plan's
    consecutive boundary layouts; intermediate activations only ever exist in
    their planned boundary layouts.  ``use_pallas=False`` swaps in the
    ``kernels/ref.py`` oracle per step (the verification path).  Pass a
    ``prepared`` ``PreparedPlan`` to skip the per-call index/weight setup —
    it must have been built from THIS plan and these weights (checked, so a
    stale prepared object fails loudly instead of computing with old
    weights).
    """
    if prepared is None:
        prepared = PreparedPlan(plan, x.shape[-1], weights, block=block)
    elif _prepared_is_stale(prepared, plan, block, weights) \
            or prepared.x_dim != x.shape[-1]:
        raise PlanError("prepared= was built from a different "
                        "(plan, weights, block) than this call's arguments")
    return prepared(x, activation=activation, use_pallas=use_pallas)


def execute_plan_reference(plan: ExecutionPlan, x: jax.Array,
                           weights: Sequence[jax.Array], *,
                           block: int = RIR_BLOCK,
                           activation: Optional[Callable] = None
                           ) -> jax.Array:
    """Same schedule through the ``kernels/ref.py`` oracle — the ground truth
    the Pallas path is asserted against."""
    perms = _boundary_perms(plan, x.shape[-1], weights, block)
    cur = apply_block_perm(x, perms[0], block) if len(perms[0]) > 1 else x
    for i, (step, w) in enumerate(zip(plan.steps, weights)):
        in_perm, out_perm = perms[i], perms[i + 1]
        w_eff = permute_weight_blocks(w, in_perm, block) \
            if len(in_perm) > 1 else w
        if len(out_perm) > 1:
            cur = ref.rir_matmul(cur, w_eff, out_perm, block)
        else:
            cur = jnp.dot(cur, w_eff,
                          preferred_element_type=jnp.float32).astype(cur.dtype)
        if activation is not None and i < len(plan.steps) - 1:
            cur = activation(cur)
    return invert_block_perm(cur, perms[-1], block) \
        if len(perms[-1]) > 1 else cur


# =========================================================================
# Whole-network execution: convolutions + residual joins through Pallas
# =========================================================================
def adapt_activation(a: jax.Array, H: int, W: int, C: int) -> jax.Array:
    """Deterministic boundary adapter between sampled (non-chaining) layers.

    The evaluation graphs sample one layer per stage, so consecutive
    workloads need not tile exactly: spatial dims shrink across stages
    (pooling is not modeled as a layer) and SAME-padded 3x3/5x5 layers want
    an input slightly LARGER than the previous output.  The adapter is the
    fixed semantic both the executor and the reference oracle implement:

    * spatial larger-than-wanted: integer-stride subsample then crop
      (the pooling stand-in),
    * spatial smaller-than-wanted: symmetric zero pad (SAME padding),
    * channels: truncate or zero-pad at the end (projection-free bridge).
    """
    N, h, w, c = a.shape
    if h > H:
        a = a[:, ::h // H, :, :][:, :H]
    elif h < H:
        lo = (H - h) // 2
        a = jnp.pad(a, ((0, 0), (lo, H - h - lo), (0, 0), (0, 0)))
    if w > W:
        a = a[:, :, ::w // W, :][:, :, :W]
    elif w < W:
        lo = (W - w) // 2
        a = jnp.pad(a, ((0, 0), (0, 0), (lo, W - w - lo), (0, 0)))
    if c > C:
        a = a[..., :C]
    elif c < C:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, C - c)))
    return a


def _adapt_src_coords(coords: np.ndarray, have: int, want: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Index form of the spatial half of ``adapt_activation``: for canvas
    coordinates in [0, want) return (source index, in-bounds mask)."""
    if have > want:
        return coords * (have // want), np.ones_like(coords, bool)
    if have < want:
        lo = (want - have) // 2
        c = coords - lo
        return np.clip(c, 0, have - 1), (c >= 0) & (c < have)
    return coords, np.ones_like(coords, bool)


@functools.lru_cache(maxsize=1024)
def _patch_row_map(N: int, h_in: int, w_in: int, H: int, W: int,
                   P: int, Q: int, R: int, S: int, stride: int) -> np.ndarray:
    """Fused (boundary adapter ∘ im2col) row gather.

    Maps each output position x tap to a flat row of the producer's stored
    2D activation ``(N*h_in*w_in, F)``; out-of-bounds (SAME-pad) taps point
    at the appended zero row ``N*h_in*w_in``.  Returns (N*P*Q, R*S) int32.
    """
    h = np.arange(P)[:, None] * stride + np.arange(R)[None, :]      # (P, R)
    w = np.arange(Q)[:, None] * stride + np.arange(S)[None, :]      # (Q, S)
    src_h, ok_h = _adapt_src_coords(h, h_in, H)
    src_w, ok_w = _adapt_src_coords(w, w_in, W)
    n = np.arange(N)[:, None, None, None, None]
    rows = ((n * h_in + src_h[None, :, None, :, None]) * w_in
            + src_w[None, None, :, None, :])                # (N, P, Q, R, S)
    ok = ok_h[None, :, None, :, None] & ok_w[None, None, :, None, :]
    rows = np.where(ok, rows, N * h_in * w_in)
    return np.ascontiguousarray(
        rows.reshape(N * P * Q, R * S).astype(np.int32))


def _stored_col_canon(perm: Tuple[int, ...], width: int,
                      block: int) -> np.ndarray:
    """Canonical channel held by each stored column of a boundary tensor."""
    if len(perm) > 1:
        return _gather_indices(perm, block)
    return np.arange(width, dtype=np.int64)


def _effective_conv_weight(wl, w: jax.Array, in_width: int,
                           in_perm: Tuple[int, ...], block: int) -> jax.Array:
    """Dense (taps*in_width, M) weight aligned to the producer's stored cols.

    Folds three things into one offline tensor: the im2col weight reshape,
    the boundary-layout K-block alignment (the stored column j holds
    canonical channel ``gidx[j]``), and the channel half of the boundary
    adapter (stored channels beyond the layer's fan-in get zero rows, so
    truncation costs nothing at runtime; missing channels simply have no
    column).  Depthwise layers use the block-diagonal dense form.
    """
    taps = wl.R * wl.S
    c_eff = input_channels(wl)
    w = jnp.asarray(w, jnp.float32)
    if is_depthwise(wl):
        flat = w.reshape(taps, wl.M)                        # (taps, M)
        canon = jnp.zeros((taps, c_eff, wl.M), jnp.float32)
        idx = jnp.arange(wl.M)
        canon = canon.at[:, idx, idx].set(flat)
    else:
        if w.ndim == 2:                                     # squeezed 1x1
            w = w.reshape(wl.R, wl.S, wl.C, wl.M)
        canon = w.reshape(taps, c_eff, wl.M)
    gidx = _stored_col_canon(in_perm, in_width, block)
    valid = gidx < c_eff
    safe = np.where(valid, np.minimum(gidx, c_eff - 1), 0)
    w_eff = canon[:, safe, :] * jnp.asarray(valid, jnp.float32)[None, :, None]
    return w_eff.reshape(taps * in_width, wl.M)


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@dataclasses.dataclass
class _JoinExec:
    """Resolved execution of one skip join at a step's output boundary."""

    src: int
    fused: bool                    # stored shapes+perms agree: epilogue add
    src_perm: Tuple[int, ...]
    src_shape: Tuple[int, int, int, int]       # (N, P, Q, M) of the source


@dataclasses.dataclass
class _NetStep:
    """Everything layer execution needs, derived once at prepare time."""

    wl: object
    row_map: Optional[jax.Array]   # None = pure GEMM passthrough
    w_eff: jax.Array               # (K_pad, M_pad) kernel-ready weight
    k_width: int                   # taps * in_width (pre-pad)
    rows_out: int
    out_perm: Tuple[int, ...]
    joins: Tuple[_JoinExec, ...]
    out_shape: Tuple[int, int, int, int]       # (N, P, Q, M)
    block_m: int = RIR_BLOCK       # kernel grid blocks from the plan's tile
    block_k: int = RIR_BLOCK
    bias: Optional[jax.Array] = None   # (M,), stored in out_perm block order


class PreparedNetwork:
    """``execute_network``'s per-(plan, graph, weights) setup, hoisted.

    Derives every boundary's block permutation, every layer's fused
    (adapter ∘ im2col) patch-gather row map, the layout-aligned effective
    weights, and the resolved join strategy — so a serving loop pays only
    the per-batch gathers and matmuls.
    """

    def __init__(self, plan: ExecutionPlan, graph: LayerGraph,
                 weights: Sequence[jax.Array], *, block: int = RIR_BLOCK,
                 biases: Optional[Sequence[Optional[jax.Array]]] = None):
        if len(plan.steps) != len(graph.layers):
            raise PlanError(f"plan has {len(plan.steps)} steps for "
                            f"{len(graph.layers)}-layer graph")
        if len(weights) != len(graph.layers):
            raise PlanError(f"{len(weights)} weights for "
                            f"{len(graph.layers)} layers")
        if biases is not None and len(biases) != len(graph.layers):
            raise PlanError(f"{len(biases)} biases for "
                            f"{len(graph.layers)} layers")
        for step, wl in zip(plan.steps, graph.layers):
            if step.workload.dims() != wl.dims() or \
                    step.workload.stride != wl.stride:
                raise PlanError(f"plan step {step.layer} does not match "
                                f"graph layer {wl.name}")
        self.plan = plan
        self.graph = graph
        self.block = block
        self.weights = tuple(weights)
        self.biases = None if biases is None else tuple(biases)
        self.input_shape = graph.input_shape()

        # boundary feature widths + block perms: boundary 0 is the network
        # input, boundary i+1 carries layer i's output
        widths = [input_channels(graph.layers[0])] + \
            [wl.M for wl in graph.layers]
        self.perms: List[Tuple[int, ...]] = \
            _derive_boundary_perms(plan, widths, block)

        self.steps: List[_NetStep] = []
        for i, (step, wl, w) in enumerate(zip(plan.steps, graph.layers,
                                              weights)):
            in_width = widths[i]
            shape = weight_shape(wl)
            got = tuple(jnp.shape(w))
            if got not in (shape, shape[-2:] if wl.R == wl.S == 1 else shape):
                raise PlanError(f"layer {wl.name}: weight shape {got} != "
                                f"expected {shape}")
            prev_wl = graph.layers[i - 1] if i > 0 else None
            h_in, w_in = (prev_wl.P, prev_wl.Q) if prev_wl else \
                (wl.H, wl.W)
            passthrough = (wl.R == 1 and wl.S == 1 and wl.stride == 1
                           and h_in == wl.H and w_in == wl.W)
            row_map = None if passthrough else jnp.asarray(_patch_row_map(
                wl.N, h_in, w_in, wl.H, wl.W, wl.P, wl.Q, wl.R, wl.S,
                wl.stride))
            bm, bk = step_kernel_blocks(step, block,
                                        k=wl.R * wl.S * in_width)
            w_eff = _effective_conv_weight(wl, w, in_width, self.perms[i],
                                           block)
            w_eff = _pad_axis(_pad_axis(w_eff, bk, 0), block, 1)
            out_perm = self.perms[i + 1]
            rows_out = wl.N * wl.P * wl.Q
            bias = None
            if biases is not None and biases[i] is not None:
                bias = jnp.asarray(biases[i], jnp.float32)
                if bias.shape != (wl.M,):
                    raise PlanError(f"layer {wl.name}: bias shape "
                                    f"{bias.shape} != ({wl.M},)")
                if len(out_perm) > 1:
                    # the bias joins the output in its stored (boundary-
                    # layout) block order, like the fused residual
                    bias = apply_block_perm(bias, out_perm, block)
            joins = []
            for j in step.joins:
                src = j.src
                if not 0 <= src < i:
                    raise PlanError(f"step {step.layer}: bad join src {src}")
                swl = graph.layers[src]
                fused = (swl.P, swl.Q) == (wl.P, wl.Q) and swl.M == wl.M \
                    and self.perms[src + 1] == out_perm and swl.N == wl.N
                joins.append(_JoinExec(
                    src=src, fused=fused, src_perm=self.perms[src + 1],
                    src_shape=(swl.N, swl.P, swl.Q, swl.M)))
            self.steps.append(_NetStep(
                wl=wl, row_map=row_map, w_eff=w_eff,
                k_width=wl.R * wl.S * in_width, rows_out=rows_out,
                out_perm=out_perm, joins=tuple(joins),
                out_shape=(wl.N, wl.P, wl.Q, wl.M),
                block_m=bm, block_k=bk, bias=bias))
        self._buffer_set = set(graph.buffer_sources())
        # fused groups (schema v4): ``fused_with`` chains a step into its
        # immediate consumer, which must be the next layer
        for i, step in enumerate(plan.steps):
            if step.fused_with is not None and step.fused_with != i + 1:
                raise PlanError(f"step {step.layer}: fused_with="
                                f"{step.fused_with} is not the next layer")
        if plan.steps and plan.steps[-1].fused_with is not None:
            raise PlanError("last step cannot fuse into a consumer")
        self._prov: Optional[Dict[str, object]] = None

    def _provenance(self) -> Dict[str, object]:
        if self._prov is None:
            self._prov = _plan_provenance(self.plan)
        return self._prov

    # ------------------------------------------------- batch assembly hooks
    # The serving engine's contract: requests are single samples, the plan
    # is built at the serve batch extent, and a partial batch is padded with
    # zero samples.  Convolution, residual joins, bias and activation are
    # all per-sample operations (every gathered patch row of sample ``b``
    # reads only sample ``b``'s stored rows, and a matmul output row is a
    # function of its own input row alone), so request ``b``'s output is
    # bit-identical whether it shares the batch with real samples, zero
    # padding, or nothing — the property the serve tests assert.
    @property
    def max_batch(self) -> int:
        """The plan tile's batch extent — the most requests one batch holds."""
        return self.input_shape[0]

    def assemble_batch(self, samples: Sequence[jax.Array]) -> jax.Array:
        """Stack 1..max_batch single samples, zero-padded to the plan's N.

        Each sample must match the planned per-sample shape
        ``input_shape()[1:]`` exactly (the engine's admission check) — the
        boundary adapter is a planned semantic, not a request-shape fixup.
        """
        n = self.max_batch
        k = len(samples)
        if not 1 <= k <= n:
            raise PlanError(f"{k} samples for max_batch={n}")
        shp = self.input_shape[1:]
        with obs.span("exec.assemble"):
            arrs = []
            for i, s in enumerate(samples):
                a = jnp.asarray(s, jnp.float32)
                if a.shape != shp:
                    raise PlanError(f"sample {i} shape {a.shape} != planned "
                                    f"per-sample shape {shp}")
                arrs.append(a)
            x = jnp.stack(arrs)
            if k < n:
                x = jnp.concatenate(
                    [x, jnp.zeros((n - k,) + shp, jnp.float32)])
        return x

    def execute_requests(self, samples: Sequence[jax.Array], *,
                         activation: Optional[Callable] = None,
                         use_pallas: bool = True) -> List[jax.Array]:
        """Run a padded request batch; return each request's own output."""
        y = self(self.assemble_batch(samples), activation=activation,
                 use_pallas=use_pallas)
        with obs.span("exec.split"):
            return [y[i] for i in range(len(samples))]

    # ------------------------------------------------------------- execution
    def _join_term(self, st: _NetStep, je: _JoinExec, buf: jax.Array,
                   block: int) -> jax.Array:
        """Bring a buffered skip tensor into this step's output layout.

        Fused joins return the buffer unchanged (already concordant); the
        relayout path canonicalizes, runs the boundary adapter, and re-stores
        in the consumer's layout — the pass the planner costed as
        ``JoinSpec.relayout``.
        """
        if je.fused:
            return buf
        canon = invert_block_perm(buf, je.src_perm, block) \
            if len(je.src_perm) > 1 else buf
        canon = canon.reshape(je.src_shape)
        N, P, Q, M = st.out_shape
        canon = adapt_activation(canon, P, Q, M).reshape(N * P * Q, M)
        return apply_block_perm(canon, st.out_perm, block) \
            if len(st.out_perm) > 1 else canon

    def __call__(self, x: jax.Array, *,
                 activation: Optional[Callable[[jax.Array], jax.Array]] = None,
                 use_pallas: bool = True) -> jax.Array:
        block = self.block
        N, H, W, C = self.input_shape
        # spans time host dispatch only: nothing here waits for the device
        active = obs.active()
        with obs.span("exec.network",
                      dict(self._provenance(), batch=int(N),
                           pallas=bool(use_pallas)) if active else None):
            a = adapt_activation(jnp.asarray(x, jnp.float32), H, W, C)
            if a.shape[0] != N:
                raise PlanError(f"batch {a.shape[0]} != planned N={N}")
            cur = a.reshape(N * H * W, C)
            if len(self.perms[0]) > 1:
                cur = apply_block_perm(cur, self.perms[0], block)
            buffers: Dict[int, jax.Array] = {}
            last = len(self.steps) - 1
            for i, st in enumerate(self.steps):
                faults.site(faults.EXEC_DISPATCH)
                with obs.span("exec.step", _step_attrs(
                        self._provenance(), i, self.plan.steps[i])
                        if active else None):
                    cur = self._step(st, cur, buffers, block,
                                     activation if i < last else None,
                                     use_pallas)
                if i in self._buffer_set:
                    buffers[i] = cur
            out_perm = self.perms[-1]
            if len(out_perm) > 1:
                cur = invert_block_perm(cur, out_perm, block)
            out = cur.reshape(self.steps[-1].out_shape)
        return out

    def _step(self, st: _NetStep, cur: jax.Array,
              buffers: Dict[int, jax.Array], block: int,
              activation: Optional[Callable[[jax.Array], jax.Array]],
              use_pallas: bool) -> jax.Array:
        """Dispatch one plan step: gather, pad, kernel, crop, bias, joins."""
        if st.row_map is None:
            patches = cur
        else:
            padded = jnp.concatenate(
                [cur, jnp.zeros((1, cur.shape[1]), cur.dtype)])
            patches = padded[st.row_map].reshape(st.rows_out, st.k_width)
        patches = _pad_axis(_pad_axis(patches, st.block_m, 0),
                            st.block_k, 1)
        fused_res = None
        for je in st.joins:
            if not je.fused:
                continue
            term = buffers[je.src]
            fused_res = term if fused_res is None else fused_res + term
        out_perm = st.out_perm if len(st.out_perm) > 1 else None
        if use_pallas:
            res_pad = None
            if fused_res is not None:
                res_pad = _pad_axis(
                    _pad_axis(fused_res, st.block_m, 0), block, 1)
            y = ops.rir_matmul(patches, st.w_eff, out_perm,
                               residual=res_pad, block_m=st.block_m,
                               block_n=block, block_k=st.block_k)
        else:
            y = jnp.dot(patches, st.w_eff,
                        preferred_element_type=jnp.float32)
            if out_perm is not None:
                y = apply_block_perm(y, out_perm, block)
            if fused_res is not None:
                y = y + _pad_axis(
                    _pad_axis(fused_res, st.block_m, 0), block, 1)
        y = y[:st.rows_out, :st.wl.M]
        if st.bias is not None:
            y = y + st.bias[None, :]
        for je in st.joins:
            if je.fused:
                continue
            y = y + self._join_term(st, je, buffers[je.src], block)
        if activation is not None:
            y = activation(y)
        return y


def prepare_network(plan: ExecutionPlan, graph: LayerGraph,
                    weights: Sequence[jax.Array], *,
                    block: int = RIR_BLOCK,
                    biases: Optional[Sequence[Optional[jax.Array]]] = None
                    ) -> PreparedNetwork:
    """Hoist gathers/weights/join strategy out of the per-batch path."""
    return PreparedNetwork(plan, graph, weights, block=block, biases=biases)


def _biases_stale(prepared_biases, biases) -> bool:
    want = None if biases is None else tuple(biases)
    if (prepared_biases is None) != (want is None):
        return True
    if want is None:
        return False
    return len(prepared_biases) != len(want) or any(
        a is not b for a, b in zip(prepared_biases, want))


def execute_network(plan: ExecutionPlan, graph: LayerGraph, x: jax.Array,
                    weights: Sequence[jax.Array], *, block: int = RIR_BLOCK,
                    activation: Optional[Callable] = None,
                    use_pallas: bool = True,
                    prepared: Optional[PreparedNetwork] = None,
                    biases: Optional[Sequence[Optional[jax.Array]]] = None
                    ) -> jax.Array:
    """Execute a complete planned ``LayerGraph`` — convs, depthwise layers
    and residual joins included; no layer falls back to the reference path.

    x: canonical NHWC input (run through the boundary adapter if it does not
    match ``graph.input_shape()`` exactly).  Returns the last layer's output
    in canonical NHWC order.  Intermediate activations only ever exist in
    their planned boundary layouts; each conv's patch gather reads the
    producer's stored order directly and each epilogue writes the consumer's.
    ``biases`` (per-layer, e.g. from ``fold_batchnorm``) are added to each
    layer's output before joins and activation.
    """
    if prepared is None:
        prepared = PreparedNetwork(plan, graph, weights, block=block,
                                   biases=biases)
    elif _prepared_is_stale(prepared, plan, block, weights) \
            or prepared.graph != graph \
            or _biases_stale(prepared.biases, biases):
        raise PlanError("prepared= was built from a different "
                        "(plan, graph, weights, biases, block) than this "
                        "call")
    return prepared(x, activation=activation, use_pallas=use_pallas)


def execute_network_reference(graph: LayerGraph, x: jax.Array,
                              weights: Sequence[jax.Array], *,
                              activation: Optional[Callable] = None,
                              biases: Optional[Sequence[Optional[jax.Array]]]
                              = None) -> jax.Array:
    """Canonical-layout oracle for ``execute_network``.

    Pure ``kernels/ref.py`` conv/depthwise semantics plus the same boundary
    adapter, per-layer biases and residual joins; no layouts, no plans —
    every valid plan for ``graph`` must reproduce this function's output.
    """
    outs: List[jax.Array] = []
    cur = jnp.asarray(x, jnp.float32)
    last = len(graph.layers) - 1
    for i, (wl, w) in enumerate(zip(graph.layers, weights)):
        a = adapt_activation(cur, wl.H, wl.W, input_channels(wl))
        w = jnp.asarray(w, jnp.float32)
        if is_depthwise(wl):
            y = ref.depthwise_conv2d(a, w, wl.stride)
        else:
            if w.ndim == 2:
                w = w.reshape(wl.R, wl.S, wl.C, wl.M)
            y = ref.conv2d(a, w, wl.stride)
        if biases is not None and biases[i] is not None:
            y = y + jnp.asarray(biases[i], jnp.float32)[None, None, None, :]
        for src in graph.skips_into(i):
            y = y + adapt_activation(outs[src], wl.P, wl.Q, wl.M)
        if activation is not None and i < last:
            y = activation(y)
        outs.append(y)
        cur = y
    return outs[-1]
