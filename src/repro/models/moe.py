"""Mixture-of-Experts FF with sort-based (dropping) dispatch.

Design notes
------------
Dispatch is **sort-based** rather than GShard one-hot-einsum: the one-hot
dispatch matmul adds O(T*k*cf*S_g*D) fake FLOPs to the compiled HLO, which
would poison the roofline compute term (and real TPU time).  Sort+scatter
dispatch keeps HLO FLOPs ≈ active-expert FLOPs.

Expert parallelism: experts are sharded over the ``model`` mesh axis.  The
layer is wrapped in ``shard_map`` over that axis; each shard dispatches the
(model-replicated) token block to its local experts and the shard outputs
are combined with one ``psum`` — the same collective volume as a Megatron
TP FF.  (The all-to-all EP variant is a §Perf hillclimb option.)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import Constrain, normal_init, null_constrain


def moe_init(rng, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(rng, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "router": normal_init(ks[0], (d, e), s_in, dtype),
        "wi_gate": normal_init(ks[1], (e, d, f), s_in, dtype),
        "wi_up": normal_init(ks[2], (e, d, f), s_in, dtype),
        "wo": normal_init(ks[3], (e, f, d), s_out, dtype),
    }


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.capacity_factor
            // max(cfg.num_experts, 1)) + 1
    return max(c, 4)


def expert_ff_local(x_flat, eids, weights, wi_gate, wi_up, wo,
                    expert_offset: int, capacity: int):
    """Dispatch -> per-expert SwiGLU -> combine, for E_loc local experts.

    x_flat  [T, D]   tokens (model-replicated block)
    eids    [T, k]   global expert ids chosen per token
    weights [T, k]   router combine weights
    wi_*    [E_loc, D, F], wo [E_loc, F, D]
    """
    T, D = x_flat.shape
    k = eids.shape[1]
    E_loc = wi_gate.shape[0]
    C = capacity
    dt = x_flat.dtype

    flat_e = eids.reshape(-1) - expert_offset  # [T*k] local ids
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(-1)
    local = (flat_e >= 0) & (flat_e < E_loc)
    key = jnp.where(local, flat_e, E_loc)  # junk bucket E_loc
    order = jnp.argsort(key, stable=True)
    se, st, sw = key[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(key, length=E_loc + 1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - offsets[se]
    keep = (se < E_loc) & (pos < C)
    dest = jnp.where(keep, se * C + pos, E_loc * C)  # overflow slot

    buf = jnp.zeros((E_loc * C + 1, D), dt)
    buf = buf.at[dest].add(x_flat[st] * keep[:, None].astype(dt))
    buf = buf[: E_loc * C].reshape(E_loc, C, D)

    g = jnp.einsum("ecd,edf->ecf", buf, wi_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, wi_up.astype(dt))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt)).reshape(E_loc * C, D)
    out = jnp.concatenate([out, jnp.zeros((1, D), dt)], axis=0)

    gathered = out[dest] * (sw * keep)[:, None].astype(dt)
    y = jnp.zeros((T, D), dt).at[st].add(gathered)
    return y


def route(params, x_flat, cfg: ModelConfig):
    """Router top-k. Returns (eids [T,k], weights [T,k], aux_loss scalar)."""
    dt = x_flat.dtype
    logits = jnp.einsum("td,de->te", x_flat, params["router"].astype(dt))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, eids = jax.lax.top_k(probs, cfg.experts_per_token)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    E = cfg.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return eids, w.astype(dt), aux


def moe_apply(params, x, cfg: ModelConfig, mesh=None, model_axis="model",
              constrain: Constrain = null_constrain):
    """x [B,S,D] -> ([B,S,D], aux_loss). Experts sharded over `model_axis`
    when a mesh is provided; pure local computation otherwise."""
    B, S, D = x.shape
    x_flat = x.reshape(B * S, D)
    eids, w, aux = route(params, x_flat, cfg)
    C = _capacity(B * S, cfg)

    if mesh is None or model_axis not in getattr(mesh, "axis_names", ()):
        y = expert_ff_local(x_flat, eids, w, params["wi_gate"],
                            params["wi_up"], params["wo"], 0, C)
        return y.reshape(B, S, D), aux

    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[model_axis]
    E_loc = cfg.num_experts // n_shards
    dp_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    C = _capacity((B * S) // dp, cfg)  # capacity per data shard

    def shard_fn(xf, ei, wi, wg, wu, wo):
        shard = jax.lax.axis_index(model_axis)
        y = expert_ff_local(xf, ei, wi, wg, wu, wo, shard * E_loc, C)
        return jax.lax.psum(y, model_axis)

    y = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(dp_axes), P(dp_axes), P(dp_axes),
                  P(model_axis), P(model_axis), P(model_axis)),
        out_specs=P(dp_axes),
        check_vma=False,
    )(x_flat, eids, w, params["wi_gate"], params["wi_up"], params["wo"])
    return y.reshape(B, S, D), aux
