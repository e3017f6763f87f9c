"""Per-layer sharding plans — FEATHER's (dataflow, layout) co-switching on a
TPU mesh.

Terminology mapping (DESIGN.md §2): on a pod, a layer's *dataflow* is which
mesh axes parallelize which tensor dims (TP over heads/ffn, EP over experts,
SP over sequence, DP over batch), and its *layout* is the sharding layout of
the activations it reads/writes.  Discordance = a producer writing a layout
the consumer's dataflow cannot consume without an extra collective on the
critical path (the "bank conflict" analogue).  The co-switching plan makes
every producer write its output in the next layer's preferred layout (RIR):
``out_shardings(layer_i) == in_shardings(layer_{i+1})``.

Rules are path-pattern based; GSPMD propagates everything unconstrained.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

Pytree = Any

# data axes for batch-parallel dims: the pod axis joins DP (unless pipelining)
DATA = ("pod", "data")


def _axes(mesh: Mesh) -> Tuple:
    data = tuple(a for a in DATA if a in mesh.axis_names)
    return data, "model"


# ------------------------------------------------------------- parameter rules
# path-regex -> partition spec builder (axis names resolved against the mesh)
_PARAM_RULES = (
    # embeddings / heads: vocab over model (Megatron vocab-parallel)
    (r"embed$", lambda d: P("model", None)),
    (r"lm_head$", lambda d: P(None, "model")),
    (r"pos_embed$|enc_pos$", lambda d: P(None, None)),
    # attention: head dim over model
    (r"wq$|wkv$", lambda d: P(None, None, "model") if d == 3
        else P(None, "model")),
    (r"wo$", lambda d: P(None, "model", None) if d == 3 else P("model", None)),
    # moe shared expert: FSDP over data (consumed inside the EP shard_map)
    (r"ffn/shared/w[ug]$", lambda d: {3: P(None, None, "data"),
                                      2: P(None, "data")}.get(d, P())),
    (r"ffn/shared/wd$", lambda d: {3: P(None, "data", None),
                                   2: P("data", None)}.get(d, P())),
    # mlp/moe: dense tensors are TP over ffn dim; 4D stacked expert tensors
    # are EP over the expert dim (the per-layer dataflow choice) + FSDP over
    # data on the ffn dim (expert weights dominate MoE memory)
    (r"(ffn|shared)/w[ug]$", lambda d: {
        4: P(None, "model", None, "data"), 3: P(None, None, "model"),
        2: P(None, "model")}.get(d, P())),
    (r"(ffn|shared)/wd$", lambda d: {
        4: P(None, "model", "data", None), 3: P(None, "model", None),
        2: P("model", None)}.get(d, P())),
    (r"router$", lambda d: P(None, None)),
    # ssm: inner channels over model
    (r"in_proj$|wr$|wk$|wv$|wg$|w1$", lambda d: P(None, None, "model")
        if d == 3 else P(None, "model")),
    (r"out_proj$|wo$|w2$", lambda d: P(None, "model", None) if d == 3
        else P("model", None)),
    (r"conv_w$", lambda d: P(None, None, "model") if d == 3
        else P(None, "model")),
    (r"conv_b$|w0$|u$", lambda d: P(None, "model") if d == 2 else P("model")),
    (r"A_log$|D_skip$|dt_bias$", lambda d: P(None, "model") if d == 2
        else P("model")),
    (r"mu$", lambda d: P(None, None, None) if d == 3 else P(None, None)),
    (r"concat_proj$", lambda d: P(None, "model")),
    # norms replicated
    (r"norm|ln_x|/w$|/b$", lambda d: P(*([None] * d))),
)


def _spec_for_path(path: str, ndim: int) -> P:
    # MoE expert tensors: distinguish from dense ffn by dimensionality
    for pat, fn in _PARAM_RULES:
        if re.search(pat, path):
            spec = fn(ndim)
            if len(spec) < ndim:   # stacked-layer leading axis
                spec = P(*((None,) * (ndim - len(spec)) + tuple(spec)))
            if len(spec) != ndim:
                spec = P(*([None] * ndim))
            return spec
    return P(*([None] * ndim))


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return "/".join(out)


def param_shardings(mesh: Mesh, specs: Pytree, fsdp: bool = False) -> Pytree:
    """NamedSharding pytree for a model's parameter specs.

    ``fsdp=True`` additionally shards every large tensor over the data axes
    on its largest unsharded dim (weights all-gathered per layer inside the
    scan) — enabled automatically for >8B-param models by the step builders.
    """
    def one(path, leaf):
        spec = _spec_for_path(_path_str(path), len(leaf.shape))
        sh = _guard(mesh, leaf.shape, spec)
        if not fsdp or math.prod(leaf.shape) < 4_000_000:
            return sh
        return _add_data_axis(mesh, sh, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, specs)


def _add_data_axis(mesh: Mesh, sh: NamedSharding,
                   shape: Tuple[int, ...]) -> NamedSharding:
    data, _ = _axes(mesh)
    dsize = 1
    for a in (data if isinstance(data, tuple) else (data,)):
        dsize *= mesh.shape[a]
    pspec = list(sh.spec) + [None] * (len(shape) - len(sh.spec))
    used = set()
    for ax in pspec:
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            if a is not None:
                used.add(a)
    names = set(data if isinstance(data, tuple) else (data,))
    if used & names:
        return sh
    best, best_dim = None, 0
    for i, (ax, dim) in enumerate(zip(pspec, shape)):
        if ax is None and dim % dsize == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best is not None:
        pspec[best] = data
    return NamedSharding(mesh, P(*pspec))


# ------------------------------------------------------- activation layer plans
@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """The (dataflow, layout) choice for a block's activations."""
    name: str
    hidden: P       # (B, T, D) layout this block wants to READ
    describe: str = ""


def plans_for(cfg: ArchConfig, mesh: Mesh, mode: str) -> Dict[str, LayerPlan]:
    """Per-block-type activation plans.

    mode == "fixed":    one global layout (baseline; discordant consumers pay
                        resharding collectives on the critical path).
    mode == "coswitch": each block type reads its preferred layout and
                        producers write it directly (RIR) — attention wants
                        batch-sharded/replicated-D, MoE wants token-sharded
                        for dispatch, the loss wants vocab-ready layouts.
    """
    data, model = _axes(mesh)
    dp = P(data, None, None)
    if mode == "fixed":
        plan = LayerPlan("fixed", dp, "global batch-sharded layout")
        return {"attn": plan, "ffn": plan, "moe": plan, "loss": plan}
    seq = P(data, "model", None)
    return {
        "attn": LayerPlan("attn", dp, "batch-sharded, heads TP inside"),
        "ffn": LayerPlan("ffn", seq, "sequence-sharded around FFN (SP)"),
        "moe": LayerPlan("moe", seq, "token-sharded for expert dispatch"),
        "loss": LayerPlan("loss", seq, "sequence-sharded softmax"),
    }


def hidden_sharding(mesh: Mesh, mode: str = "coswitch") -> Callable:
    """Hook applied between layers in the scan: constrain the hidden layout.

    In coswitch mode this is where RIR manifests: the layer-boundary (saved-
    for-backward) activations live SEQUENCE-SHARDED over the model axis and
    the producing block's last matmul emits them via reduce-scatter (the
    reorder rides the reduction); each consumer block all-gathers what its
    own dataflow needs.  In fixed mode the boundary layout is the
    batch-sharded/replicated layout every block can read directly — no
    resharding collectives, but model-axis memory is wasted (the discordant
    baseline trades memory and TP-collective efficiency away).
    """
    data, model = _axes(mesh)

    def coswitch(x):
        if x.ndim == 3 and x.shape[1] % mesh.shape["model"] == 0:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(data, "model", None)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(data, None, None)))

    def fixed(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(data, None, None)))

    return coswitch if mode == "coswitch" else fixed


def batch_sharding(mesh: Mesh) -> NamedSharding:
    data, _ = _axes(mesh)
    return NamedSharding(mesh, P(data, None))


def _guard(mesh: Mesh, shape: Tuple[int, ...], spec: P) -> NamedSharding:
    """Drop any sharded axis that does not divide its dimension (jit-boundary
    shardings require exact divisibility, unlike internal constraints)."""
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        fixed.append(ax if dim % size == 0 else None)
    return NamedSharding(mesh, P(*fixed))


def cache_shardings(mesh: Mesh, cache_specs: Pytree) -> Pytree:
    """KV/SSM cache shardings for serving: batch over data axes; attention KV
    over heads when divisible, else sequence-parallel KV (model axis on S);
    SSM states over heads/channels."""
    data, model = _axes(mesh)
    msize = mesh.shape["model"]

    def one(path, leaf):
        shape = leaf.shape
        nd = len(shape)
        p = _path_str(path)
        if p.endswith("length"):
            return NamedSharding(mesh, P(*([None] * nd)))
        stacked = "layers" in p or "attn_" in p  # leading n_layers/n_inv dim
        core = shape[1:] if stacked else shape
        if len(core) == 4 and ("k" in p.split("/")[-1] or
                               "v" in p.split("/")[-1]) and "conv" not in p:
            # attn kv, head-major (B, Hkv, S, dh)
            if core[1] % msize == 0:
                spec = P(data, "model", None, None)
            else:
                spec = P(data, None, "model", None)
        elif len(core) == 4:    # ssm (B, H, state, hd) / rwkv (B, H, dk, dv)
            spec = P(data, "model", None, None)
        elif len(core) == 3:    # conv cache (B, W-1, C)
            spec = P(data, None, "model")
        elif len(core) == 2:    # x_prev (B, D)
            spec = P(data, "model")
        else:
            spec = P(*([None] * len(core)))
        if stacked:
            spec = P(*((None,) + tuple(spec)))
        return _guard(mesh, shape, spec)

    return jax.tree_util.tree_map_with_path(one, cache_specs)


def opt_shardings(mesh: Mesh, param_sh: Pytree, specs: Pytree) -> Pytree:
    """ZeRO-1: optimizer moments/master copies additionally sharded over the
    data axes on the largest still-unsharded divisible dim.  XLA materializes
    this as reduce-scattered grads + all-gathered updated params around the
    optimizer, keeping the 12-bytes/param fp32 state off every replica."""
    data, _ = _axes(mesh)
    dsize = 1
    for a in (data if isinstance(data, tuple) else (data,)):
        dsize *= mesh.shape[a]

    data_names = set(data if isinstance(data, tuple) else (data,))

    def one(sh, spec):
        pspec = list(sh.spec) + [None] * (len(spec.shape) - len(sh.spec))
        used = set()
        for ax in pspec:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    used.add(a)
        if used & data_names:   # FSDP already put data axes on the params
            return NamedSharding(mesh, P(*pspec))
        # choose the largest unsharded dim divisible by the data size
        best, best_dim = None, 0
        for i, (ax, dim) in enumerate(zip(pspec, spec.shape)):
            if ax is None and dim % dsize == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best is not None:
            pspec[best] = data
        return NamedSharding(mesh, P(*pspec))

    return jax.tree.map(one, param_sh, specs)
