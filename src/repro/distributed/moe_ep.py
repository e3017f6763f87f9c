"""Expert-parallel MoE via shard_map: explicit all-to-all token routing.

The GSPMD-propagated scatter/gather dispatch replicates its buffers; at
dbrx-132b scale that is tens of GB per device.  This module hand-shards the
dispatch instead:

* tokens arrive sequence-sharded over the *model* axis (the coswitch layout);
* each shard routes its local tokens, builds a local (E, C_loc, D) dispatch,
  and ``all_to_all``s over the model axis so each chip receives the tokens
  for ITS resident experts from every peer — FEATHER's RIR pattern at mesh
  scale: the combine is a reduction (top-k weighted sum) whose results land
  back at each token's home position (the reorder);
* expert weights are E-sharded over the model axis and FSDP-sharded over the
  data axes, all-gathered (data axes) just-in-time inside the block.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.common import activation, apply_norm, dense


def _data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def moe_apply_ep(cfg: ArchConfig, p: Dict, x: jax.Array,
                 mesh: Mesh) -> jax.Array:
    """x: (B, T, D) with T divisible by the model axis; returns (B, T, D)."""
    E, K = cfg.n_experts, cfg.top_k
    data = _data_axes(mesh)
    m = mesh.shape["model"]
    E_loc = E // m

    x_spec = P(data, "model", None)
    router_spec = P(None, None)
    # expert weights: (E, D, F) sharded E over model, F (or D) over data
    wu_spec = P("model", None, "data")
    wd_spec = P("model", "data", None)
    norm_spec_ = jax.tree.map(lambda _: P(None), p["norm"])
    shared_specs = None
    if cfg.shared_expert:
        shared_specs = {k: P(None, "data") if k in ("wu", "wg")
                        else P("data", None) for k in p["shared"]}

    in_specs = ({"norm": norm_spec_, "router": router_spec,
                 "wu": wu_spec, "wd": wd_spec},)
    if cfg.act == "swiglu":
        in_specs[0]["wg"] = wu_spec
    if shared_specs is not None:
        in_specs[0]["shared"] = shared_specs
    p_in = {k: p[k] for k in in_specs[0]}

    def local(p_loc, xb):
        B_loc, T_loc, D = xb.shape
        N = B_loc * T_loc
        h = apply_norm(cfg.norm, xb, p_loc["norm"])
        flat = h.reshape(N, D)
        logits = flat.astype(jnp.float32) @ p_loc["router"]
        gates, idx = jax.lax.top_k(logits, K)
        gates = jax.nn.softmax(gates, axis=-1)

        C = int(math.ceil(N * K / E * cfg.capacity_factor / 8.0)) * 8
        C = min(C, max(8, N))
        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                     num_segments=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(N * K) - starts[sorted_e]
        slot_sorted = jnp.where(pos < C, sorted_e * C + pos, E * C)
        slot = jnp.zeros((N * K,), jnp.int32).at[order].set(
            slot_sorted.astype(jnp.int32))
        buf = jnp.zeros((E * C + 1, D), flat.dtype)
        disp = buf.at[slot_sorted].set(flat[order // K])[:E * C]
        disp = disp.reshape(E, C, D)

        # route tokens to expert owners over the model axis (EP all-to-all);
        # each chip ends with (E_loc, m*C, D): its experts, everyone's tokens
        disp = jax.lax.all_to_all(disp, "model", split_axis=0, concat_axis=1,
                                  tiled=True)

        # FSDP: gather the F (or D) shards of the local expert weights
        wu = jax.lax.all_gather(p_loc["wu"], data, axis=2, tiled=True)
        wd = jax.lax.all_gather(p_loc["wd"], data, axis=1, tiled=True)
        up = jnp.einsum("ecd,edf->ecf", disp, wu,
                        preferred_element_type=jnp.float32).astype(flat.dtype)
        if cfg.act == "swiglu":
            wg = jax.lax.all_gather(p_loc["wg"], data, axis=2, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", disp, wg,
                           preferred_element_type=jnp.float32
                           ).astype(flat.dtype)
            act = activation(cfg.act, up, g)
        else:
            act = activation(cfg.act, up)
        out_e = jnp.einsum("ecf,efd->ecd", act, wd,
                           preferred_element_type=jnp.float32
                           ).astype(flat.dtype)

        # send results home (reverse all-to-all) — the RIR combine
        out_e = jax.lax.all_to_all(out_e, "model", split_axis=1,
                                   concat_axis=0, tiled=True)
        out_e = out_e.reshape(E * C, D)
        out_pad = jnp.concatenate(
            [out_e, jnp.zeros((1, D), flat.dtype)], axis=0)
        gathered = out_pad[slot.reshape(N, K)]
        combined = jnp.sum(gathered * gates[..., None].astype(flat.dtype),
                           axis=1)
        if cfg.shared_expert:
            sp = p_loc["shared"]
            wu_s = jax.lax.all_gather(sp["wu"], data, axis=1, tiled=True)
            wd_s = jax.lax.all_gather(sp["wd"], data, axis=0, tiled=True)
            up_s = dense(flat, wu_s)
            if cfg.act == "swiglu":
                wg_s = jax.lax.all_gather(sp["wg"], data, axis=1, tiled=True)
                act_s = activation(cfg.act, up_s, dense(flat, wg_s))
            else:
                act_s = activation(cfg.act, up_s)
            combined = combined + dense(act_s, wd_s)
        return combined.reshape(B_loc, T_loc, D)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs[0], x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(p_in, x)


def ep_applicable(cfg: ArchConfig, mesh: Mesh, x: jax.Array) -> bool:
    if mesh is None or "model" not in mesh.axis_names:
        return False
    m = mesh.shape["model"]
    if cfg.n_experts % m or x.shape[1] % m:
        return False
    dsize = 1
    for a in _data_axes(mesh):
        dsize *= mesh.shape[a]
    if x.shape[0] % dsize:
        return False
    return cfg.d_ff % dsize == 0
