"""Mamba2 / SSD (state-space duality) blocks.

Chunked SSD algorithm (arXiv:2405.21060): within-chunk quadratic term +
inter-chunk state recurrence, both expressed with einsums + one lax.scan so
the compiled HLO is compact and TPU-friendly.  ``ssd_sequential`` is the
step-by-step recurrence oracle used by tests and the decode path.
The scan carries the ``ssd`` named scope (``SSD_SCOPE``) in each HLO op's
``op_name``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import (
    Constrain, gated_rmsnorm, normal_init, null_constrain, rmsnorm_init,
)
from repro.runtime.train import SSD_SCOPE


# --------------------------------------------------------------------------- #
# Core SSD math (head-dim P, state N). All fp32 internally.
# --------------------------------------------------------------------------- #
def ssd_chunk_len(L: int, chunk: int) -> int:
    """The chunk ``ssd_chunked`` runs over a sequence of ``L``: ``chunk``,
    or the whole sequence where it is shorter or ``chunk`` does not
    divide it."""
    Q = min(chunk, L)
    return Q if L % Q == 0 else L


def ssd_pairs(L: int, chunk: int) -> tuple[int, int]:
    """(causal, computed) (t, s) pairs of one sequence's chunks: the pairs
    with s <= t that the within-chunk term needs, ``chunks x Q(Q+1)/2``,
    and the ``chunks x Q^2`` that ``ssd_chunked`` forms."""
    Q = ssd_chunk_len(L, chunk)
    nc = L // Q
    return nc * Q * (Q + 1) // 2, nc * Q * Q


@jax.named_scope(SSD_SCOPE)
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x  [B,L,H,P]   inputs (already head-split)
    dt [B,L,H]     positive step sizes
    A  [H]         negative decay rates
    Bm [B,L,N]     input projections (shared across heads, ngroups=1)
    Cm [B,L,N]     output projections
    Returns (y [B,L,H,P], final_state [B,H,P,N]).
    """
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    Q = ssd_chunk_len(L, chunk)
    nc = L // Q
    f32 = jnp.float32

    xc = x.reshape(Bsz, nc, Q, H, P).astype(f32)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(f32)
    Bc = Bm.reshape(Bsz, nc, Q, N).astype(f32)
    Cc = Cm.reshape(Bsz, nc, Q, N).astype(f32)

    dA = dtc * A.astype(f32)[None, None, None, :]  # [B,nc,Q,H], <= 0
    cum = jnp.cumsum(dA, axis=2)  # inclusive within-chunk cumulative decay

    # ---- intra-chunk (quadratic in Q) ---------------------------------- #
    # scores[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * dt_s   for s <= t
    # Above the diagonal cum_t - cum_s is positive and overflows exp over a
    # long chunk; masked to -inf there, exp gives 0 forward and backward.
    cb = jnp.einsum("bctn,bcsn->bcts", Cc, Bc)  # [B,nc,Q,Q]
    segsum = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(tri[None, None, :, :, None], segsum, -jnp.inf))
    scores = cb[..., None] * decay
    scores = scores * dtc[:, :, None, :, :]  # weight by dt_s
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", scores, xc)

    # ---- chunk states + inter-chunk recurrence -------------------------- #
    # S_c = sum_s exp(cum_last - cum_s) * dt_s * (B_s ⊗ x_s)   [B,H,P,N]
    last = cum[:, :, -1:, :]  # [B,nc,1,H]
    w = jnp.exp(last - cum) * dtc  # [B,nc,Q,H]
    S_c = jnp.einsum("bcsh,bcsn,bcshp->bchpn", w, Bc, xc)
    chunk_decay = jnp.exp(last[:, :, 0, :])  # [B,nc,H]

    def body(S_prev, inputs):
        S_chunk, decay_c = inputs  # [B,H,P,N], [B,H]
        S_next = S_prev * decay_c[:, :, None, None] + S_chunk
        return S_next, S_prev

    S0 = (jnp.zeros((Bsz, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))
    S_final, S_prevs = jax.lax.scan(
        body, S0, (S_c.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    S_prevs = S_prevs.swapaxes(0, 1)  # [B,nc,H,P,N] state at chunk start

    # y_inter[t] = exp(cum_t) * C_t . S_prev
    y_inter = jnp.einsum("bcth,bctn,bchpn->bcthp", jnp.exp(cum), Cc, S_prevs)

    y = (y_intra + y_inter).reshape(Bsz, L, H, P)
    return y.astype(x.dtype), S_final


def ssd_sequential(x, dt, A, Bm, Cm, initial_state=None):
    """Step-recurrence oracle: S_t = exp(dt_t A) S_{t-1} + dt_t B_t ⊗ x_t."""
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    S0 = (jnp.zeros((Bsz, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))

    def body(S, inputs):
        xt, dtt, Bt, Ct = inputs  # [B,H,P],[B,H],[B,N],[B,N]
        decay = jnp.exp(dtt * A[None, :])  # [B,H]
        S = S * decay[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dtt, Bt, xt)
        y = jnp.einsum("bn,bhpn->bhp", Ct, S)
        return S, y

    xs = (x.swapaxes(0, 1).astype(f32), dt.swapaxes(0, 1).astype(f32),
          Bm.swapaxes(0, 1).astype(f32), Cm.swapaxes(0, 1).astype(f32))
    S, ys = jax.lax.scan(body, S0, xs)
    return ys.swapaxes(0, 1).astype(x.dtype), S


def ssd_decode_step(state, xt, dtt, A, Bt, Ct):
    """One-token recurrence. state [B,H,P,N]; returns (y [B,H,P], state)."""
    f32 = jnp.float32
    with jax.named_scope(SSD_SCOPE):
        decay = jnp.exp(dtt.astype(f32) * A.astype(f32)[None, :])
        state = state * decay[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dtt.astype(f32), Bt.astype(f32),
            xt.astype(f32))
        y = jnp.einsum("bn,bhpn->bhp", Ct.astype(f32), state)
    return y.astype(xt.dtype), state


# --------------------------------------------------------------------------- #
# Depthwise causal conv (width W, small) via shifts
# --------------------------------------------------------------------------- #
def causal_conv(x, w, b, history=None):
    """x [B,L,C]; w [W,C]; b [C]; history [B,W-1,C] or None (zeros)."""
    W = w.shape[0]
    if history is None:
        history = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype) for i in range(W))
    return jax.nn.silu(y + b.astype(x.dtype))


# --------------------------------------------------------------------------- #
# Full Mamba2 block
# --------------------------------------------------------------------------- #
def mamba_init(rng, cfg: ModelConfig, dtype) -> dict:
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(rng, 8)
    s = d ** -0.5
    conv_dim = di + 2 * n
    return {
        "in_z": normal_init(ks[0], (d, di), s, dtype),
        "in_x": normal_init(ks[1], (d, di), s, dtype),
        "in_B": normal_init(ks[2], (d, n), s, dtype),
        "in_C": normal_init(ks[3], (d, n), s, dtype),
        "in_dt": normal_init(ks[4], (d, h), s, dtype),
        "conv_w": normal_init(ks[5], (cfg.conv_width, conv_dim),
                              cfg.conv_width ** -0.5, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.linspace(1e-3, 1e-1, h))).astype(dtype),  # softplus^-1 of dt
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(dtype),
        "D": jnp.ones((h,), dtype),
        "norm": rmsnorm_init(di, dtype),
        "out": normal_init(ks[6], (di, d), di ** -0.5, dtype),
    }


def _mamba_project(params, u, constrain: Constrain):
    dt_ = u.dtype
    z = jnp.einsum("bld,dk->blk", u, params["in_z"].astype(dt_))
    xp = jnp.einsum("bld,dk->blk", u, params["in_x"].astype(dt_))
    Bp = jnp.einsum("bld,dn->bln", u, params["in_B"].astype(dt_))
    Cp = jnp.einsum("bld,dn->bln", u, params["in_C"].astype(dt_))
    dt = jnp.einsum("bld,dh->blh", u, params["in_dt"].astype(dt_))
    z = constrain(z, ("batch", "seq", "ff"))
    xp = constrain(xp, ("batch", "seq", "ff"))
    return z, xp, Bp, Cp, dt


def mamba_apply(params, u, cfg: ModelConfig, constrain: Constrain = null_constrain,
                initial_state=None, conv_history=None, return_state=False):
    """u [B,L,D] -> [B,L,D]. Full-sequence (train/prefill) path."""
    B_, L, _ = u.shape
    di, n, h, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xp, Bp, Cp, dt = _mamba_project(params, u, constrain)
    xBC_pre = jnp.concatenate([xp, Bp, Cp], axis=-1)
    xBC = causal_conv(xBC_pre, params["conv_w"], params["conv_b"], conv_history)
    xp, Bp, Cp = jnp.split(xBC, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xp.reshape(B_, L, h, P)
    y, state = ssd_chunked(xh, dt, A, Bp, Cp, cfg.ssm_chunk, initial_state)
    y = y + xh * params["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B_, L, di)
    y = gated_rmsnorm(params["norm"], y, z, cfg.norm_eps)
    out = jnp.einsum("blk,kd->bld", y, params["out"].astype(y.dtype))
    out = constrain(out, ("batch", "seq", "embed"))
    if return_state:
        # conv history is the last W-1 PRE-activation xBC columns
        new_cache = {"state": state, "conv": xBC_pre[:, L - (cfg.conv_width - 1):]}
        return out, new_cache
    return out


def mamba_init_cache(cfg: ModelConfig, batch: int, dtype):
    di, n, h, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    return {
        "state": jnp.zeros((batch, h, P, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, di + 2 * n), dtype),
    }


def mamba_decode_step(params, u, cache, cfg: ModelConfig,
                      constrain: Constrain = null_constrain):
    """u [B,1,D]; cache {'state','conv'} -> ([B,1,D], cache)."""
    B_ = u.shape[0]
    di, n, h, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xp, Bp, Cp, dt = _mamba_project(params, u, constrain)
    xBC = jnp.concatenate([xp, Bp, Cp], axis=-1)  # [B,1,conv_dim]
    hist = cache["conv"]
    window = jnp.concatenate([hist, xBC], axis=1)  # [B,W,conv_dim]
    w = params["conv_w"].astype(u.dtype)
    conv_out = jnp.einsum("bwc,wc->bc", window, w) + params["conv_b"].astype(u.dtype)
    conv_out = jax.nn.silu(conv_out)[:, None, :]
    xp, Bp, Cp = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xp.reshape(B_, h, P)
    y, state = ssd_decode_step(cache["state"], xh, dt[:, 0], A, Bp[:, 0], Cp[:, 0])
    y = y + xh * params["D"].astype(y.dtype)[None, :, None]
    y = y.reshape(B_, 1, di)
    y = gated_rmsnorm(params["norm"], y, z, cfg.norm_eps)
    out = jnp.einsum("blk,kd->bld", y, params["out"].astype(y.dtype))
    new_cache = {"state": state, "conv": window[:, 1:]}
    return out, new_cache
