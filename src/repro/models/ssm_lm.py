"""Pure-SSM (Mamba2) language model: embed -> N x (norm + SSD block) -> head.

Named scopes on the device work: ``embed`` (the lookup), ``ssm``
(each layer's norm through the mixer's residual add, with the scan's own
``ssd`` inside), ``head`` (final norm, head and cross-entropy).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.runtime.train import EMBED_SCOPE, HEAD_SCOPE, SSM_SCOPE


def _stack_init(fn, rng, n, *args):
    return jax.vmap(lambda k: fn(k, *args))(jax.random.split(rng, n))


@dataclass
class MambaLM:
    cfg: ModelConfig
    policy: L.Policy = field(default_factory=L.Policy)
    constrain: L.Constrain = L.null_constrain
    mesh: Any = None
    attn_impl: str = "auto"  # unused (attention-free)
    remat: str = "none"
    fold_depth: int = 4

    def init(self, rng) -> dict:
        cfg, pd = self.cfg, self.policy.param_dtype
        ks = jax.random.split(rng, 3)
        params = {
            "embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, pd),
            "final_norm": L.rmsnorm_init(cfg.d_model, pd),
            "layers": _stack_init(
                lambda k: {"ln": L.rmsnorm_init(cfg.d_model, pd),
                           "mamba": M.mamba_init(k, cfg, pd)},
                ks[1], cfg.num_layers),
        }
        if not cfg.tie_embeddings:
            params["head"] = L.head_init(ks[2], cfg.d_model, cfg.vocab_size, pd)
        return params

    def _maybe_remat(self, fn):
        if self.remat == "full":
            return jax.checkpoint(fn)
        if self.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return fn

    def _head(self, params, x):
        x = L.rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return L.tied_head_apply(params["embed"], x)
        return L.head_apply(params["head"], x)

    def apply(self, params, tokens, vision_embeds=None, collect_kv=False,
              q_offset=0):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        with jax.named_scope(EMBED_SCOPE):
            x = L.embed_apply(params["embed"], tokens, cd)
            x = self.constrain(x, ("batch", "seq", "embed"))

        def body(x, lp):
            with jax.named_scope(SSM_SCOPE):
                h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                x = x + M.mamba_apply(lp["mamba"], h, cfg, self.constrain)
            return x, None

        body = self._maybe_remat(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        with jax.named_scope(HEAD_SCOPE):
            logits = self._head(params, x)
            logits = self.constrain(logits, ("batch", "seq", "vocab"))
        if collect_kv:
            return logits, {}, jnp.zeros((), jnp.float32)
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params, batch, vision_embeds=None):
        logits, _ = self.apply(params, batch["tokens"])
        with jax.named_scope(HEAD_SCOPE):
            ce = L.cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce}

    # ------------------------------------------------------------------ #
    def init_cache(self, batch: int, max_seq: int) -> dict:
        cfg = self.cfg
        cd = self.policy.compute_dtype
        di, n = cfg.d_inner, cfg.ssm_state
        return {
            "state": jnp.zeros(
                (cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                jnp.float32),
            "conv": jnp.zeros(
                (cfg.num_layers, batch, cfg.conv_width - 1, di + 2 * n), cd),
        }

    def prefill(self, params, tokens, cache, vision_embeds=None):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        with jax.named_scope(EMBED_SCOPE):
            x = L.embed_apply(params["embed"], tokens, cd)

        def body(x, lp):
            with jax.named_scope(SSM_SCOPE):
                h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                out, c = M.mamba_apply(lp["mamba"], h, cfg, self.constrain,
                                       return_state=True)
                return x + out, c

        x, caches = jax.lax.scan(body, x, params["layers"])
        with jax.named_scope(HEAD_SCOPE):
            logits = self._head(params, x)
        new_cache = {"state": caches["state"],
                     "conv": caches["conv"].astype(cd)}
        return logits[:, -1], new_cache

    def decode_step(self, params, token, cache, pos):
        cfg = self.cfg
        cd = self.policy.compute_dtype
        with jax.named_scope(EMBED_SCOPE):
            x = L.embed_apply(params["embed"], token, cd)

        def body(x, xs):
            lp, st, cv = xs
            with jax.named_scope(SSM_SCOPE):
                h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
                out, c = M.mamba_decode_step(lp["mamba"], h,
                                             {"state": st, "conv": cv},
                                             cfg, self.constrain)
                return x + out, (c["state"], c["conv"])

        x, (st, cv) = jax.lax.scan(
            body, x, (params["layers"], cache["state"], cache["conv"]))
        with jax.named_scope(HEAD_SCOPE):
            logits = self._head(params, x)
        return logits[:, 0], {"state": st, "conv": cv}
