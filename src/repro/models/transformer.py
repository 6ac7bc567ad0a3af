"""Decoder-only transformer LM covering dense / MoE / audio / VLM families.

Functional, scan-over-layers (compact HLO), KV-cache prefill/decode, optional
cross-attention groups (VLM) and MoE FF (dbrx/arctic).  Parameters are plain
nested dicts; layer params carry a leading stacked dimension consumed by
``lax.scan``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.runtime.train import (ATTENTION_SCOPE, EMBED_SCOPE, HEAD_SCOPE,
                                 MLP_SCOPE)


def _stack_init(fn, rng, n, *args):
    return jax.vmap(lambda k: fn(k, *args))(jax.random.split(rng, n))


@dataclass
class TransformerLM:
    cfg: ModelConfig
    policy: L.Policy = field(default_factory=L.Policy)
    constrain: L.Constrain = L.null_constrain
    mesh: Any = None  # for MoE expert sharding
    attn_impl: str = "auto"  # auto | direct | chunked | folded
    remat: str = "none"  # none | full | dots
    fold_depth: int = 4
    q_chunk: int = 1024
    kv_chunk: int = 512

    # ------------------------------------------------------------------ #
    @property
    def is_moe(self) -> bool:
        return self.cfg.num_experts > 0

    @property
    def n_cross(self) -> int:
        c = self.cfg.cross_attn_every
        return self.cfg.num_layers // (c + 1) if c else 0

    @property
    def n_self(self) -> int:
        return self.cfg.num_layers - self.n_cross

    # ------------------------------------------------------------------ #
    # Init
    # ------------------------------------------------------------------ #
    def _layer_init(self, rng) -> dict:
        cfg, pd = self.cfg, self.policy.param_dtype
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        p = {
            "ln1": L.rmsnorm_init(cfg.d_model, pd),
            "ln2": L.rmsnorm_init(cfg.d_model, pd),
            "attn": attn_lib.attention_init(
                k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, pd, qkv_bias=cfg.qkv_bias),
        }
        if self.is_moe:
            p["moe"] = moe_lib.moe_init(k2, cfg, pd)
            if cfg.moe_dense_residual:
                p["mlp"] = L.mlp_init(k3, cfg.d_model, cfg.d_ff, pd)
        else:
            p["mlp"] = L.mlp_init(k3, cfg.d_model, cfg.d_ff, pd)
        return p

    def _cross_layer_init(self, rng) -> dict:
        cfg, pd = self.cfg, self.policy.param_dtype
        k1, k2 = jax.random.split(rng, 2)
        return {
            "ln1": L.rmsnorm_init(cfg.d_model, pd),
            "ln2": L.rmsnorm_init(cfg.d_model, pd),
            "attn": attn_lib.attention_init(
                k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, pd, with_gate=True),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, pd),
            "gate_mlp": jnp.zeros((), pd),
            "kv_proj": L.normal_init(
                k2, (cfg.vision_d, cfg.d_model), cfg.vision_d ** -0.5, pd),
        }

    def init(self, rng) -> dict:
        cfg, pd = self.cfg, self.policy.param_dtype
        ks = jax.random.split(rng, 4)
        params = {
            "embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, pd),
            "final_norm": L.rmsnorm_init(cfg.d_model, pd),
        }
        if not cfg.tie_embeddings:
            params["head"] = L.head_init(ks[1], cfg.d_model, cfg.vocab_size, pd)
        if self.n_cross:
            g = self.n_cross
            per = cfg.cross_attn_every
            params["layers"] = _stack_init(
                lambda k: _stack_init(self._layer_init, k, per), ks[2], g)
            params["cross"] = _stack_init(self._cross_layer_init, ks[3], g)
        else:
            params["layers"] = _stack_init(
                self._layer_init, ks[2], cfg.num_layers)
        return params

    # ------------------------------------------------------------------ #
    # Blocks
    # ------------------------------------------------------------------ #
    def _self_block(self, p, x, positions, cache=None, pos=None):
        """Pre-norm block. Returns (x, new_kv or (k,v))."""
        cfg = self.cfg
        with jax.named_scope(ATTENTION_SCOPE):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            if cache is None:
                q, k, v = attn_lib.project_qkv(
                    p["attn"], h, positions=positions,
                    rope_theta=cfg.rope_theta, constrain=self.constrain)
                if self.attn_impl == "cp" and self.mesh is not None:
                    o = attn_lib.context_parallel_attention(
                        q, k, v, self.mesh, causal=True,
                        q_chunk=self.q_chunk, kv_chunk=self.kv_chunk)
                else:
                    o = attn_lib.attention(
                        q, k, v, causal=True, impl=self.attn_impl,
                        fold_depth=self.fold_depth, q_chunk=self.q_chunk,
                        kv_chunk=self.kv_chunk)
                new_kv = (k, v)
            else:
                k_cache, v_cache = cache
                q, k, v = attn_lib.project_qkv(
                    p["attn"], h, positions=positions,
                    rope_theta=cfg.rope_theta, constrain=self.constrain)
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k, pos, 1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v, pos, 1)
                o = attn_lib.decode_attention(q, k_cache, v_cache, pos)
                new_kv = (k_cache, v_cache)
            x = x + attn_lib.project_out(p["attn"], o, self.constrain)
            x = self.constrain(x, ("batch", "seq", "embed"))

        with jax.named_scope(MLP_SCOPE):
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            aux = jnp.zeros((), jnp.float32)
            if self.is_moe:
                y, aux = moe_lib.moe_apply(
                    p["moe"], h, cfg, mesh=self.mesh, constrain=self.constrain)
                if cfg.moe_dense_residual:
                    y = y + L.mlp_apply(p["mlp"], h, self.constrain)
            else:
                y = L.mlp_apply(p["mlp"], h, self.constrain)
            x = x + y
            return self.constrain(x, ("batch", "seq", "embed")), new_kv, aux

    def _cross_block(self, p, x, vis_kv, cache=None):
        """Gated cross-attention block (vision). vis_kv [B,Tv,D_model]."""
        cfg = self.cfg
        with jax.named_scope(ATTENTION_SCOPE):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            q, k, v = attn_lib.project_qkv(p["attn"], h, kv_x=vis_kv,
                                           constrain=self.constrain)
            if cache is not None:  # decode: reuse cached cross K/V
                k, v = cache
            o = attn_lib.attention(
                q, k, v, causal=False, impl="direct"
                if q.shape[1] * k.shape[1] <= 1 << 22 else "chunked")
            gate = jnp.tanh(p["attn"]["gate"].astype(x.dtype))
            x = x + gate * attn_lib.project_out(p["attn"], o, self.constrain)
        with jax.named_scope(MLP_SCOPE):
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            gate2 = jnp.tanh(p["gate_mlp"].astype(x.dtype))
            x = x + gate2 * L.mlp_apply(p["mlp"], h, self.constrain)
        return x, (k, v)

    def _head(self, params, x):
        """Final norm and the tied or untied head: x -> logits."""
        x = L.rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return L.tied_head_apply(params["embed"], x)
        return L.head_apply(params["head"], x)

    def _maybe_remat(self, fn):
        if self.remat == "full":
            return jax.checkpoint(fn)
        if self.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return fn

    # ------------------------------------------------------------------ #
    # Forward (train / prefill)
    # ------------------------------------------------------------------ #
    def apply(self, params, tokens, vision_embeds=None, collect_kv=False,
              q_offset=0):
        """tokens [B,S] -> logits [B,S,V].  collect_kv returns per-layer K/V."""
        cfg = self.cfg
        cd = self.policy.compute_dtype
        B, S = tokens.shape
        with jax.named_scope(EMBED_SCOPE):
            x = L.embed_apply(params["embed"], tokens, cd)
            x = self.constrain(x, ("batch", "seq", "embed"))
        positions = jnp.arange(S)[None, :] + q_offset

        vis = None
        if self.n_cross:
            assert vision_embeds is not None, "VLM requires vision embeddings"
            vis = vision_embeds.astype(cd)

        aux_total = jnp.zeros((), jnp.float32)

        if self.n_cross:
            def group(x, gp):
                def inner(x, lp):
                    x, kv, aux = self._self_block(lp, x, positions)
                    return x, (kv, aux)
                inner = self._maybe_remat(inner)
                x, (kvs, auxs) = jax.lax.scan(inner, x, gp["layers"])
                vkv = jnp.einsum("btd,dm->btm", vis,
                                 gp["cross"]["kv_proj"].astype(cd))
                x, cross_kv = self._cross_block(gp["cross"], x, vkv)
                return x, (kvs, cross_kv, jnp.sum(auxs))

            group = self._maybe_remat(group)
            stacked = {"layers": params["layers"], "cross": params["cross"]}
            x, (kvs, cross_kvs, auxs) = jax.lax.scan(group, x, stacked)
            aux_total = jnp.sum(auxs)
            kv_out = {"self": kvs, "cross": cross_kvs}
        else:
            def body(x, lp):
                x, kv, aux = self._self_block(lp, x, positions)
                return x, (kv, aux)
            body = self._maybe_remat(body)
            x, (kvs, auxs) = jax.lax.scan(body, x, params["layers"])
            aux_total = jnp.sum(auxs)
            kv_out = {"self": kvs}

        with jax.named_scope(HEAD_SCOPE):
            logits = self._head(params, x)
            logits = self.constrain(logits, ("batch", "seq", "vocab"))
        if collect_kv:
            return logits, kv_out, aux_total
        return logits, aux_total

    def loss(self, params, batch, vision_embeds=None):
        logits, aux = self.apply(params, batch["tokens"],
                                 vision_embeds=vision_embeds)
        with jax.named_scope(HEAD_SCOPE):
            ce = L.cross_entropy(logits, batch["labels"])
        loss = ce + 0.01 * aux if self.is_moe else ce
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------ #
    # KV cache serving
    # ------------------------------------------------------------------ #
    def init_cache(self, batch: int, max_seq: int) -> dict:
        cfg = self.cfg
        cd = self.policy.compute_dtype
        kv_shape = (cfg.num_layers if not self.n_cross else None)
        cache = {}
        if self.n_cross:
            g, per = self.n_cross, cfg.cross_attn_every
            cache["k"] = jnp.zeros(
                (g, per, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), cd)
            cache["v"] = jnp.zeros_like(cache["k"])
            cache["cross_k"] = jnp.zeros(
                (g, batch, cfg.vision_tokens, cfg.num_kv_heads, cfg.head_dim), cd)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        else:
            cache["k"] = jnp.zeros(
                (cfg.num_layers, batch, max_seq, cfg.num_kv_heads,
                 cfg.head_dim), cd)
            cache["v"] = jnp.zeros_like(cache["k"])
        return cache

    def prefill(self, params, tokens, cache, vision_embeds=None):
        """Run full-sequence forward, fill cache. Returns (last_logits, cache)."""
        S = tokens.shape[1]
        logits, kv, _ = self.apply(params, tokens, vision_embeds=vision_embeds,
                                   collect_kv=True)
        k, v = kv["self"]
        if self.n_cross:
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, 3)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, 3)
            ck, cv = kv["cross"]
            cache["cross_k"], cache["cross_v"] = ck, cv
        else:
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, 2)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, 2)
        return logits[:, -1], cache

    def decode_step(self, params, token, cache, pos):
        """token [B,1]; pos: scalar int32 index of the new token."""
        cfg = self.cfg
        cd = self.policy.compute_dtype
        with jax.named_scope(EMBED_SCOPE):
            x = L.embed_apply(params["embed"], token, cd)
        positions = jnp.full((token.shape[0], 1), pos, jnp.int32)

        if self.n_cross:
            def group(x, gp):
                lp, kc, vc, cp, ck, cv = gp

                def inner(x, xs):
                    lpi, kci, vci = xs
                    x, (knew, vnew), _ = self._self_block(
                        lpi, x, positions, cache=(kci, vci), pos=pos)
                    return x, (knew, vnew)

                x, (kn, vn) = jax.lax.scan(inner, x, (lp, kc, vc))
                x, _ = self._cross_block(cp, x, None, cache=(ck, cv))
                return x, (kn, vn)

            x, (kn, vn) = jax.lax.scan(
                group, x,
                (params["layers"], cache["k"], cache["v"], params["cross"],
                 cache["cross_k"], cache["cross_v"]))
            new_cache = dict(cache, k=kn, v=vn)
        else:
            def body(x, xs):
                lp, kc, vc = xs
                x, (kn, vn), _ = self._self_block(
                    lp, x, positions, cache=(kc, vc), pos=pos)
                return x, (kn, vn)
            x, (kn, vn) = jax.lax.scan(body, x, (params["layers"],
                                                 cache["k"], cache["v"]))
            new_cache = dict(cache, k=kn, v=vn)

        with jax.named_scope(HEAD_SCOPE):
            logits = self._head(params, x)
        return logits[:, 0], new_cache
