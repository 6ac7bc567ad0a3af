"""Zamba2-style hybrid: Mamba2 backbone + ONE weight-shared attention block
applied after every ``attn_every`` SSM layers.

The shared block's weights are a single (non-scanned) copy; each application
keeps its own KV cache during serving.  Simplification vs. the released
Zamba2 (noted in DESIGN.md): we use the hidden state directly as the shared
block input rather than concat(hidden, embedding) + per-application LoRA.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.runtime.train import SSM_SCOPE


def _stack_init(fn, rng, n):
    return jax.vmap(fn)(jax.random.split(rng, n))


@dataclass
class Zamba2LM:
    cfg: ModelConfig
    policy: L.Policy = field(default_factory=L.Policy)
    constrain: L.Constrain = L.null_constrain
    mesh: Any = None
    attn_impl: str = "auto"
    remat: str = "none"
    fold_depth: int = 4

    @property
    def n_groups(self) -> int:
        return self.cfg.num_layers // self.cfg.attn_every

    def init(self, rng) -> dict:
        cfg, pd = self.cfg, self.policy.param_dtype
        ks = jax.random.split(rng, 5)
        g, per = self.n_groups, cfg.attn_every

        def mamba_layer(k):
            return {"ln": L.rmsnorm_init(cfg.d_model, pd),
                    "mamba": M.mamba_init(k, cfg, pd)}

        params = {
            "embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, pd),
            "final_norm": L.rmsnorm_init(cfg.d_model, pd),
            "head": L.head_init(ks[1], cfg.d_model, cfg.vocab_size, pd),
            "layers": _stack_init(
                lambda k: _stack_init(mamba_layer, k, per), ks[2], g),
            "shared_attn": {
                "ln1": L.rmsnorm_init(cfg.d_model, pd),
                "ln2": L.rmsnorm_init(cfg.d_model, pd),
                "attn": attn_lib.attention_init(
                    ks[3], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.head_dim, pd),
                "mlp": L.mlp_init(ks[4], cfg.d_model, cfg.d_ff, pd),
            },
        }
        return params

    def _maybe_remat(self, fn):
        if self.remat == "full":
            return jax.checkpoint(fn)
        if self.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return fn

    def _shared_block(self, sp, x, positions, cache=None, pos=None):
        cfg = self.cfg
        h = L.rmsnorm(sp["ln1"], x, cfg.norm_eps)
        q, k, v = attn_lib.project_qkv(
            sp["attn"], h, positions=positions, rope_theta=cfg.rope_theta,
            constrain=self.constrain)
        if cache is None:
            o = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl,
                                   fold_depth=self.fold_depth)
            new_kv = (k, v)
        else:
            kc, vc = cache
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, 1)
            o = attn_lib.decode_attention(q, kc, vc, pos)
            new_kv = (kc, vc)
        x = x + attn_lib.project_out(sp["attn"], o, self.constrain)
        h = L.rmsnorm(sp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp_apply(sp["mlp"], h, self.constrain)
        return self.constrain(x, ("batch", "seq", "embed")), new_kv

    def _head_out(self, params, x):
        x = L.rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return L.head_apply(params["head"], x)

    # ------------------------------------------------------------------ #
    def apply(self, params, tokens, vision_embeds=None, collect_kv=False,
              q_offset=0):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        B, S = tokens.shape
        x = L.embed_apply(params["embed"], tokens, cd)
        x = self.constrain(x, ("batch", "seq", "embed"))
        positions = jnp.arange(S)[None, :] + q_offset
        sp = params["shared_attn"]

        def group(x, gp):
            def inner(x, lp):
                with jax.named_scope(SSM_SCOPE):
                    h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                    return x + M.mamba_apply(lp["mamba"], h, cfg,
                                             self.constrain), None
            x, _ = jax.lax.scan(inner, x, gp)
            x, kv = self._shared_block(sp, x, positions)
            return x, kv

        group = self._maybe_remat(group)
        x, kvs = jax.lax.scan(group, x, params["layers"])
        logits = self._head_out(params, x)
        logits = self.constrain(logits, ("batch", "seq", "vocab"))
        if collect_kv:
            return logits, {"shared": kvs}, jnp.zeros((), jnp.float32)
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params, batch, vision_embeds=None):
        logits, _ = self.apply(params, batch["tokens"])
        ce = L.cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce}

    # ------------------------------------------------------------------ #
    def init_cache(self, batch: int, max_seq: int) -> dict:
        cfg = self.cfg
        cd = self.policy.compute_dtype
        g, per = self.n_groups, cfg.attn_every
        di, n = cfg.d_inner, cfg.ssm_state
        return {
            "state": jnp.zeros(
                (g, per, batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                jnp.float32),
            "conv": jnp.zeros(
                (g, per, batch, cfg.conv_width - 1, di + 2 * n), cd),
            "k": jnp.zeros((g, batch, max_seq, cfg.num_kv_heads,
                            cfg.head_dim), cd),
            "v": jnp.zeros((g, batch, max_seq, cfg.num_kv_heads,
                            cfg.head_dim), cd),
        }

    def prefill(self, params, tokens, cache, vision_embeds=None):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        B, S = tokens.shape
        x = L.embed_apply(params["embed"], tokens, cd)
        positions = jnp.arange(S)[None, :]
        sp = params["shared_attn"]

        def group(x, gp):
            def inner(x, lp):
                with jax.named_scope(SSM_SCOPE):
                    h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                    out, c = M.mamba_apply(lp["mamba"], h, cfg,
                                           self.constrain, return_state=True)
                    return x + out, c
            x, caches = jax.lax.scan(inner, x, gp)
            x, kv = self._shared_block(sp, x, positions)
            return x, (caches, kv)

        x, (mcaches, kvs) = jax.lax.scan(group, x, params["layers"])
        logits = self._head_out(params, x)
        k, v = kvs
        new_cache = dict(cache)
        new_cache["state"] = mcaches["state"]
        new_cache["conv"] = mcaches["conv"].astype(cd)
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cd), 0, 2)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cd), 0, 2)
        return logits[:, -1], new_cache

    def decode_step(self, params, token, cache, pos):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        x = L.embed_apply(params["embed"], token, cd)
        positions = jnp.full((token.shape[0], 1), pos, jnp.int32)
        sp = params["shared_attn"]

        def group(x, xs):
            gp, st, cv, kc, vc = xs

            def inner(x, ys):
                lp, sti, cvi = ys
                with jax.named_scope(SSM_SCOPE):
                    h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                    out, c = M.mamba_decode_step(
                        lp["mamba"], h, {"state": sti, "conv": cvi}, cfg,
                        self.constrain)
                    return x + out, (c["state"], c["conv"])

            x, (st2, cv2) = jax.lax.scan(inner, x, (gp, st, cv))
            x, (k2, v2) = self._shared_block(sp, x, positions,
                                             cache=(kc, vc), pos=pos)
            return x, (st2, cv2, k2, v2)

        x, (st, cv, k2, v2) = jax.lax.scan(
            group, x, (params["layers"], cache["state"], cache["conv"],
                       cache["k"], cache["v"]))
        logits = self._head_out(params, x)
        return logits[:, 0], {"state": st, "conv": cv, "k": k2, "v": v2}
