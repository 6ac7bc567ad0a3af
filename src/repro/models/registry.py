"""Model registry: ModelConfig -> model instance, plus input-spec stubs."""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import Constrain, Policy, null_constrain


def build_model(cfg: ModelConfig, *, policy: Policy | None = None,
                constrain: Constrain = null_constrain, mesh: Any = None,
                remat: str = "none"):
    """Instantiate the right family for a config."""
    policy = policy or Policy()
    kw = dict(cfg=cfg, policy=policy, constrain=constrain, mesh=mesh,
              remat=remat)
    if cfg.family == "ssm":
        from repro.models.ssm_lm import MambaLM
        return MambaLM(**kw)
    if cfg.family == "hybrid":
        from repro.models.zamba2 import Zamba2LM
        return Zamba2LM(**kw)
    # dense / moe / audio / vlm all share TransformerLM
    from repro.models.transformer import TransformerLM
    return TransformerLM(**kw)


def modality_inputs(cfg: ModelConfig, batch: int, compute_dtype=jnp.bfloat16):
    """Shapes of stubbed modality-frontend inputs (assignment: frontends are
    stubs providing precomputed patch/frame embeddings)."""
    if cfg.family == "vlm":
        return {"vision_embeds": (batch, cfg.vision_tokens, cfg.vision_d)}
    return {}
