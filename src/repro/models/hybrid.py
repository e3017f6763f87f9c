"""Zamba2-style hybrid: Mamba2 backbone + one SHARED attention block invoked
every ``shared_attn_every`` backbone layers (params reused, Zamba2's global
shared transformer block).  The shared block consumes concat(x, x_embed0)
through a down-projection, per the Zamba design.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .blocks import attn_decode, attn_specs, attn_train, mlp_apply, mlp_specs
from .common import apply_norm, dense, norm_spec
from .lm import LMModel, _stack_specs, init_from_specs
from .ssm import (mamba2_cache_specs, mamba2_decode, mamba2_specs,
                  mamba2_train)


@dataclasses.dataclass
class HybridModel(LMModel):
    """cfg.family == "hybrid" (zamba2)."""

    @property
    def n_invocations(self) -> int:
        k = self.cfg.shared_attn_every
        return (self.cfg.n_layers + k - 1) // k

    def param_specs(self) -> Dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        return {
            "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), dt),
            "final_norm": norm_spec(cfg.norm, cfg.d_model, dt),
            "layers": _stack_specs({"mixer": mamba2_specs(cfg)}, cfg.n_layers),
            "shared": {
                "concat_proj": jax.ShapeDtypeStruct(
                    (2 * cfg.d_model, cfg.d_model), dt),
                "attn": attn_specs(cfg),
                "ffn": mlp_specs(cfg),
            },
            "lm_head": jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), dt),
        }

    def init(self, key: jax.Array) -> Dict:
        return init_from_specs(self.param_specs(), key)

    def _shared_train(self, params: Dict, x: jax.Array, x0: jax.Array
                      ) -> jax.Array:
        sp = params["shared"]
        h = dense(jnp.concatenate([x, x0], axis=-1), sp["concat_proj"])
        h = h + attn_train(self.cfg, sp["attn"], h)
        h = h + mlp_apply(self.cfg, sp["ffn"], h)
        return h

    def hidden_states(self, params: Dict, tokens: jax.Array,
                      hook=None, remat: bool = True) -> jax.Array:
        cfg = self.cfg
        x0 = jnp.take(params["embed"], tokens, axis=0)
        k = cfg.shared_attn_every

        def body(carry, scanned):
            x, i = carry
            layer = scanned
            x = x + mamba2_train(cfg, layer["mixer"], x, mesh=self.mesh)

            def with_attn(x):
                return x + self._shared_train(params, x, x0)

            x = jax.lax.cond((i + 1) % k == 0, with_attn, lambda x: x, x)
            if hook is not None:
                x = hook(x)
            return (x, i + 1), None

        if remat:
            body = jax.checkpoint(body)
        (x, _), _ = jax.lax.scan(body, (x0, jnp.int32(0)), params["layers"])
        return apply_norm(cfg.norm, x, params["final_norm"])

    # ---------------------------------------------------------------- serving
    def cache_specs(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        dh, dt = cfg.head_dim, jnp.dtype(cfg.dtype)
        return {
            "layers": _stack_specs(mamba2_cache_specs(cfg, batch),
                                   cfg.n_layers),
            "attn_k": jax.ShapeDtypeStruct(
                (self.n_invocations, batch, cfg.n_kv_heads, max_seq, dh), dt),
            "attn_v": jax.ShapeDtypeStruct(
                (self.n_invocations, batch, cfg.n_kv_heads, max_seq, dh), dt),
            "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }

    def decode_step(self, params: Dict, cache: Dict, tokens: jax.Array
                    ) -> Tuple[Dict, jax.Array]:
        cfg = self.cfg
        x0 = jnp.take(params["embed"], tokens, axis=0)
        length = cache["length"]
        k = cfg.shared_attn_every
        attn_k, attn_v = cache["attn_k"], cache["attn_v"]

        def body(carry, scanned):
            x, i, ak, av = carry
            layer, layer_cache = scanned
            delta, new_cache = mamba2_decode(cfg, layer["mixer"], x,
                                             layer_cache)
            x = x + delta

            def with_attn(args):
                x, ak, av = args
                inv = i // k
                sp = params["shared"]
                h = dense(jnp.concatenate([x, x0], axis=-1),
                          sp["concat_proj"])
                kc = jax.lax.dynamic_index_in_dim(ak, inv, 0, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(av, inv, 0, keepdims=False)
                d, kc, vc = attn_decode(cfg, sp["attn"], h, kc, vc, length,
                                        mesh=self.mesh)
                h = h + d
                h = h + mlp_apply(cfg, sp["ffn"], h[:, None])[:, 0]
                ak = jax.lax.dynamic_update_index_in_dim(ak, kc, inv, 0)
                av = jax.lax.dynamic_update_index_in_dim(av, vc, inv, 0)
                return x + h, ak, av

            x, ak, av = jax.lax.cond((i + 1) % k == 0, with_attn,
                                     lambda a: a, (x, ak, av))
            return (x, i + 1, ak, av), new_cache

        (x, _, attn_k, attn_v), new_layer_caches = jax.lax.scan(
            body, (x0, jnp.int32(0), attn_k, attn_v),
            (params["layers"], cache["layers"]))
        x = apply_norm(cfg.norm, x, params["final_norm"])
        logits = self.logits(params, x)
        new_cache = {"layers": new_layer_caches, "attn_k": attn_k,
                     "attn_v": attn_v, "length": length + 1}
        return new_cache, logits

    def prefill(self, params: Dict, tokens: jax.Array, max_seq: int
                ) -> Tuple[Dict, jax.Array]:
        B, T = tokens.shape
        hidden = self.hidden_states(params, tokens, remat=False)
        logits = self.logits(params, hidden[:, -1])
        cache = self.init_cache(B, max_seq)
        cache["length"] = jnp.full((B,), T, jnp.int32)
        return cache, logits
