"""Shared layers: norms, rotary embeddings, SwiGLU MLP, dtype policy."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.scopes import ATTENTION_LSE, ATTENTION_OUT

Constrain = Callable[[jax.Array, tuple], jax.Array]


def null_constrain(x: jax.Array, axes: tuple) -> jax.Array:  # noqa: ARG001
    return x


@dataclass(frozen=True)
class Policy:
    """Mixed-precision policy: storage vs compute dtype."""

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def cast(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )


def normal_init(rng, shape, stddev, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * stddev).astype(dtype)


def stack_init(fn, rng, n: int):
    """``n`` parameter trees of ``fn(key)`` stacked on a leading axis, the
    layout ``lax.scan`` over layers consumes."""
    return jax.vmap(fn)(jax.random.split(rng, n))


def maybe_remat(fn, remat: str):
    """``fn`` under the remat policy: ``full`` recomputes its forward in
    the backward except the flash attention forward's named outputs
    (``ATTENTION_OUT``, ``ATTENTION_LSE``), ``dots`` saves the matmuls with
    no batch dims, ``none`` saves everything."""
    if remat == "full":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                ATTENTION_OUT, ATTENTION_LSE))
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


# --------------------------------------------------------------------------- #
# RMSNorm
# --------------------------------------------------------------------------- #
def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def gated_rmsnorm(params: dict, x: jax.Array, z: jax.Array, eps: float = 1e-5):
    """Mamba2-style norm: RMSNorm(x * silu(z))."""
    return rmsnorm(params, x * jax.nn.silu(z.astype(x.dtype)), eps)


# --------------------------------------------------------------------------- #
# Rotary position embeddings
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: [..., S] (int)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# SwiGLU MLP
# --------------------------------------------------------------------------- #
def mlp_init(rng, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "wi_gate": normal_init(k1, (d_model, d_ff), s_in, dtype),
        "wi_up": normal_init(k2, (d_model, d_ff), s_in, dtype),
        "wo": normal_init(k3, (d_ff, d_model), s_out, dtype),
    }


def mlp_apply(params: dict, x: jax.Array, constrain: Constrain = null_constrain):
    # preferred_element_type pins dot OUTPUTS to the compute dtype so the
    # TP psum that follows moves bf16, not f32 (the MXU still accumulates
    # in f32 internally) — halves collective wire bytes at 405B scale.
    dt = x.dtype
    gate = jnp.einsum("...d,df->...f", x, params["wi_gate"].astype(dt),
                      preferred_element_type=dt)
    up = jnp.einsum("...d,df->...f", x, params["wi_up"].astype(dt),
                    preferred_element_type=dt)
    h = jax.nn.silu(gate) * up
    h = constrain(h, ("batch", "seq", "ff"))
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dt),
                      preferred_element_type=dt)


# --------------------------------------------------------------------------- #
# Embedding / LM head
# --------------------------------------------------------------------------- #
def embed_init(rng, vocab: int, d_model: int, dtype) -> dict:
    return {"embedding": normal_init(rng, (vocab, d_model), 1.0, dtype)}


def embed_apply(params: dict, tokens: jax.Array, compute_dtype) -> jax.Array:
    return params["embedding"][tokens].astype(compute_dtype)


def head_init(rng, d_model: int, vocab: int, dtype) -> dict:
    return {"w": normal_init(rng, (d_model, vocab), d_model ** -0.5, dtype)}


def head_apply(params: dict, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,dv->...v", x, params["w"].astype(x.dtype))


def tied_head_apply(embed_params: dict, x: jax.Array) -> jax.Array:
    w = embed_params["embedding"].astype(x.dtype)
    return jnp.einsum("...d,vd->...v", x, w)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token CE. logits [..., V] fp32-upcast; labels int [...].

    The label pick uses a one-hot contraction, NOT take_along_axis: a gather
    along a model-sharded vocab axis makes GSPMD all-gather the full logits
    (hundreds of GiB/device at 405B scale); the one-hot contraction
    partitions cleanly."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = labels[..., None] == jnp.arange(V, dtype=labels.dtype)
    ll = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(lse - ll)
