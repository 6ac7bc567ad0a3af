"""Mamba2-780M: the plain float32 reference and its model FLOPs.

Attention-free language model (arXiv:2405.21060; ``mamba_ssm``'s
``Mamba2`` layer, ngroups 1): per layer a pre-norm residual around one
Mamba2 mixer, which projects the input to z, x, B, C and dt, runs a
causal depthwise convolution and SiLU over x, B and C, the selective
state-space recurrence per head

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T,    y_t = C_t S_t + D x_t,

the gated RMSNorm ``rmsnorm(y * silu(z))`` and an output projection; tied
embedding and head.  The recurrence runs step by step in float32, blocks
of time steps recomputed in the backward pass.  It imports nothing of the
program; weights are laid out as the program holds them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import HIGHEST, cross_entropy, rmsnorm

# tokens per reference call, and time steps per recomputed block
ROW_TOKENS = 8192
T_BLOCK = 64


def _dims(cfg):
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    N, P = cfg["ssm_state"], cfg["ssm_head_dim"]
    return (cfg["num_layers"], d, di, N, di // P, P, cfg["conv_width"],
            cfg["vocab_size"])


def init(cfg: dict, key) -> dict:
    """``mamba_ssm``'s initialisation: linear layers U(+-1/sqrt(fan_in)),
    the output projection further divided by sqrt(n_layer), the depthwise
    convolution and its bias U(+-1/2), dt log-uniform in [1e-3, 1e-1]
    through an inverse softplus, A uniform in [1, 16], D one, embedding
    N(0, 0.02)."""
    L, d, di, N, H, P, W, V = _dims(cfg)
    keys = iter(jax.random.split(key, 16))

    def uniform(bound, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  -bound, bound)

    dt = jnp.exp(jax.random.uniform(next(keys), (L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    conv_dim = di + 2 * N
    mixer = {
        "in_z": uniform(d ** -0.5, L, d, di),
        "in_x": uniform(d ** -0.5, L, d, di),
        "in_B": uniform(d ** -0.5, L, d, N),
        "in_C": uniform(d ** -0.5, L, d, N),
        "in_dt": uniform(d ** -0.5, L, d, H),
        "conv_w": uniform(0.5, L, W, conv_dim),
        "conv_b": uniform(0.5, L, conv_dim),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(next(keys), (L, H), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((L, H), jnp.float32),
        "norm": {"scale": jnp.ones((L, di), jnp.float32)},
        "out": uniform(di ** -0.5 / L ** 0.5, L, di, d),
    }
    params = {
        "embed": {"embedding": 0.02 * jax.random.normal(
            next(keys), (V, d), jnp.float32)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "layers": {"ln": {"scale": jnp.ones((L, d), jnp.float32)},
                   "mamba": mixer},
    }
    if not cfg.get("tie_embeddings"):
        params["head"] = {"w": uniform(d ** -0.5, d, V)}
    return params


def _recurrence(x, dt, A, Bm, Cm):
    """x [b,T,H,P], dt [b,T,H], A [H], Bm/Cm [b,T,N] -> y [b,T,H,P]."""
    b, T, H, P = x.shape
    N = Bm.shape[-1]
    blk = T_BLOCK if T % T_BLOCK == 0 else T

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = S * jnp.exp(dtt * A)[:, :, None, None] \
            + dtt[:, :, None, None] * xt[..., None] * Bt[:, None, None, :]
        return S, jnp.einsum("bn,bhpn->bhp", Ct, S, precision=HIGHEST)

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(step, S, inp)

    def time_major(a):
        a = a.swapaxes(0, 1)
        return a.reshape((T // blk, blk) + a.shape[1:])

    _, ys = jax.lax.scan(block, jnp.zeros((b, H, P, N), jnp.float32),
                         tuple(map(time_major, (x, dt, Bm, Cm))))
    return ys.reshape(T, b, H, P).swapaxes(0, 1)


def _mixer(p, u, cfg, mm):
    L, d, di, N, H, P, W, V = _dims(cfg)
    b, T, _ = u.shape
    z = mm("btd,dk->btk", u, p["in_z"])
    xbc = jnp.concatenate([mm("btd,dk->btk", u, p["in_x"]),
                           mm("btd,dk->btk", u, p["in_B"]),
                           mm("btd,dk->btk", u, p["in_C"])], -1)
    dt = jax.nn.softplus(mm("btd,dh->bth", u, p["in_dt"]) + p["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + T] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    xh = xs.reshape(b, T, H, P)
    y = _recurrence(xh, dt, -jnp.exp(p["A_log"]), Bm, Cm)
    y = (y + xh * p["D"][:, None]).reshape(b, T, di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"]["scale"], cfg["norm_eps"])
    return mm("btk,kd->btd", y, p["out"])


def loss(params, tokens, labels, cfg: dict, mm):
    """Mean next-token cross-entropy of rows ``tokens`` [b, T]."""
    eps = cfg["norm_eps"]
    emb = params["embed"]["embedding"]

    def layer(x, p):
        h = rmsnorm(x, p["ln"]["scale"], eps)
        return x + _mixer(p["mamba"], h, cfg, mm), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), emb[tokens], params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    head = emb if "head" not in params else params["head"]["w"].T
    d = x.shape[-1]
    return cross_entropy(x.reshape(-1, d), labels.reshape(-1), head, mm)


def model_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs of one training step: forward and backward (3x the forward),
    no recompute.  Input and output projections, the depthwise
    convolution, the head, and the state-space part as the chunked SSD
    algorithm (chunk Q) needs it: causal within-chunk products C B^T and
    their weighted sum of x, each over the (Q+1)/2 positions a step sees on
    average, and per step the chunk-state update and read-out."""
    L, d, di, N, H, P, W, V = _dims(cfg)
    Q = min(cfg["ssm_chunk"], seq)
    proj = 2 * d * (2 * di + 2 * N + H) + 2 * di * d
    conv = 2 * W * (di + 2 * N)
    ssd = 2 * (N + di) * (Q + 1) / 2 + 2 * 2 * di * N
    forward = L * (proj + conv + ssd) + 2 * d * V
    return 3.0 * forward * batch * seq
