"""Qwen2-0.5B: the plain float32 reference and its model FLOPs.

Decoder-only transformer (arXiv:2407.10671; Hugging Face ``Qwen2Model``):
pre-norm RMSNorm, grouped-query attention with q/k/v bias and rotary
embeddings (rotate-half), SwiGLU MLP, tied embedding and head.  Written
from the published description in plain ``jax.numpy``; it imports nothing
of the program.  Weights are laid out as the program holds them, with a
leading layer axis on every layer leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import cross_entropy, rmsnorm

# tokens per reference call, and query rows per attention block
ROW_TOKENS = 4096
Q_BLOCK = 512


def _dims(cfg):
    H, d = cfg["num_heads"], cfg["d_model"]
    return (cfg["num_layers"], d, H, cfg["num_kv_heads"], d // H,
            cfg["d_ff"], cfg["vocab_size"])


def init(cfg: dict, key) -> dict:
    """N(0, 0.02) for the embedding and every matrix, zero biases, unit
    norm scales (Hugging Face ``initializer_range``)."""
    L, d, H, KV, hd, f, V = _dims(cfg)
    keys = iter(jax.random.split(key, 16))

    def normal(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def zeros(*shape):
        return jnp.zeros(shape, jnp.float32)

    attn = {"wq": normal(L, d, H, hd), "wk": normal(L, d, KV, hd),
            "wv": normal(L, d, KV, hd), "wo": normal(L, H, hd, d)}
    if cfg.get("qkv_bias"):
        attn.update(bq=zeros(L, H, hd), bk=zeros(L, KV, hd),
                    bv=zeros(L, KV, hd))
    params = {
        "embed": {"embedding": normal(V, d)},
        "final_norm": {"scale": ones(d)},
        "layers": {
            "ln1": {"scale": ones(L, d)}, "ln2": {"scale": ones(L, d)},
            "attn": attn,
            "mlp": {"wi_gate": normal(L, d, f), "wi_up": normal(L, d, f),
                    "wo": normal(L, f, d)},
        },
    }
    if not cfg.get("tie_embeddings"):
        params["head"] = {"w": normal(d, V)}
    return params


def _rope(x, theta):
    """Rotate-half rotary embedding of x [r, S, heads, hd] by position."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm):
    """Causal softmax attention, query head h reading kv head h // G; one
    block of query rows at a time, recomputed in the backward pass."""
    r, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S
    blocks = q.reshape(r, S // qb, qb, KV, G, hd).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = mm("rqkgh,rtkh->rkgqt", qi, k) * hd ** -0.5
        mask = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm("rkgqt,rtkh->rqkgh", p, v)

    o = jax.lax.map(one, (blocks, jnp.arange(S // qb)))
    return o.swapaxes(0, 1).reshape(r, S, H, hd)


def loss(params, tokens, labels, cfg: dict, mm):
    """Mean next-token cross-entropy of rows ``tokens`` [r, S]."""
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    emb = params["embed"]["embedding"]
    x = emb[tokens]

    def layer(x, p):
        a = p["attn"]
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        q = mm("rsd,dhk->rshk", h, a["wq"])
        k = mm("rsd,dhk->rshk", h, a["wk"])
        v = mm("rsd,dhk->rshk", h, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        o = _attention(_rope(q, theta), _rope(k, theta), v, mm)
        x = x + mm("rshk,hkd->rsd", o, a["wo"])
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        u = jax.nn.silu(mm("rsd,df->rsf", h, m["wi_gate"])) \
            * mm("rsd,df->rsf", h, m["wi_up"])
        return x + mm("rsf,fd->rsd", u, m["wo"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    head = emb if "head" not in params else params["head"]["w"].T
    d = x.shape[-1]
    return cross_entropy(x.reshape(-1, d), labels.reshape(-1), head, mm)


def model_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs of one training step: forward and backward (3x the forward),
    no recompute.  Matrix products of every layer and of the head, and
    causal attention counted over the (S+1)/2 keys a query sees on
    average; norms, biases and elementwise work are left out."""
    L, d, H, KV, hd, f, V = _dims(cfg)
    per_token_layer = 2 * (d * H * hd + 2 * d * KV * hd + H * hd * d
                           + 3 * d * f)
    attention = 2 * 2 * H * hd * (seq + 1) / 2
    forward = L * (per_token_layer + attention) + 2 * d * V
    return 3.0 * forward * batch * seq
