"""GQA attention: direct, chunked (flash-style in XLA), folded-causal, decode.

Layouts: q [B,S,H,hd], k/v [B,T,KV,hd].  GQA groups G = H // KV.
``chunked_attention`` is the memory-bounded train/prefill path (online
softmax over KV chunks, optional Q chunking).  ``folded_causal_attention``
is the beyond-paper FLOP-reduction path (recursive causality folding: the
upper-triangular blocks are never materialized, cutting HLO FLOPs toward the
causal-optimal S^2/2).  ``decode_attention`` is the single-token path.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.layers import Constrain, apply_rope, normal_init, null_constrain

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Parameter init / projections
# --------------------------------------------------------------------------- #
def attention_init(rng, d_model, num_heads, num_kv_heads, head_dim, dtype,
                   qkv_bias=False, with_gate=False) -> dict:
    ks = jax.random.split(rng, 5)
    s = d_model ** -0.5
    p = {
        "wq": normal_init(ks[0], (d_model, num_heads, head_dim), s, dtype),
        "wk": normal_init(ks[1], (d_model, num_kv_heads, head_dim), s, dtype),
        "wv": normal_init(ks[2], (d_model, num_kv_heads, head_dim), s, dtype),
        "wo": normal_init(ks[3], (num_heads, head_dim, d_model),
                          (num_heads * head_dim) ** -0.5, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((num_kv_heads, head_dim), dtype)
        p["bv"] = jnp.zeros((num_kv_heads, head_dim), dtype)
    if with_gate:  # llama3.2-vision cross-attn tanh gate
        p["gate"] = jnp.zeros((), dtype)
    return p


def project_qkv(params, x, kv_x=None, positions=None, rope_theta=None,
                constrain: Constrain = null_constrain):
    """Returns q [B,S,H,hd], k/v [B,T,KV,hd]; applies RoPE if positions given."""
    dt = x.dtype
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", kv_x, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", kv_x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if positions is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    # q keeps the "seq" axis: with sequence parallelism and non-16-divisible
    # head counts (e.g. arctic's 56) the model axis lands on q's sequence
    # dim -> context-parallel attention (each shard owns 1/16 of the rows).
    # k/v must NEVER shard on seq: every q row needs every k/v row, and a
    # seq-sharded K under a heads-sharded Q forces GSPMD into involuntary
    # full rematerialization (measured: 17 TB/step of all-gathers at 405B).
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def project_out(params, o, constrain: Constrain = null_constrain):
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype),
                     preferred_element_type=o.dtype)
    return constrain(out, ("batch", "seq", "embed"))


# --------------------------------------------------------------------------- #
# Direct attention (small shapes / oracle)
# --------------------------------------------------------------------------- #
def direct_attention(q, k, v, causal=True, q_offset=0):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    if causal:
        qpos = jnp.arange(S) + q_offset
        mask = qpos[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return o.reshape(B, S, H, hd)


# --------------------------------------------------------------------------- #
# Chunked (flash-style) attention — the XLA train/prefill workhorse
# --------------------------------------------------------------------------- #
def _chunk_scan(q, k, v, causal, qpos, kv_chunk, return_stats=False):
    """Online-softmax scan over KV chunks for one q-block.

    q: [B,Sq,KV,G,hd]; qpos: f32 [Sq] global row positions (an ARRAY so it
    stays valid when traced, e.g. under shard_map context parallelism)."""
    B, Sq, KV, G, hd = q.shape
    T = k.shape[1]
    n_chunks = T // kv_chunk
    kc = k.reshape(B, n_chunks, kv_chunk, KV, hd)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, hd)
    scale = hd ** -0.5

    def body(carry, inputs):
        o, m, l = carry
        j, kj, vj = inputs
        s = jnp.einsum("bskgh,btkh->bkgst", q, kj).astype(jnp.float32) * scale
        if causal:
            kpos = (jnp.arange(kv_chunk) + j * kv_chunk).astype(jnp.float32)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkh->bkgsh", p.astype(q.dtype), vj)
        o_new = o * alpha[..., None].astype(o.dtype) + pv
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((B, KV, G, Sq, hd), q.dtype)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (jnp.arange(n_chunks), kc.swapaxes(0, 1), vc.swapaxes(0, 1)))
    o = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    o = o.transpose(0, 3, 1, 2, 4)  # [B,Sq,KV,G,hd]
    if return_stats:
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,KV,G,Sq]
        return o, lse
    return o


def _flash_fwd(qg, k, v, qpos, causal, q_chunk, kv_chunk):
    B, S, KV, G, hd = qg.shape
    nq = max(S // q_chunk, 1)
    if S % q_chunk:
        nq, q_chunk = 1, S
    qs = qg.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)
    qps = qpos.reshape(nq, q_chunk)

    def one_q(args):
        qb, qp = args
        return _chunk_scan(qb, k, v, causal, qp, kv_chunk,
                           return_stats=True)

    o, lse = jax.lax.map(one_q, (qs, qps))
    # o: [nq, B, bq, KV, G, hd]; lse: [nq, B, KV, G, bq]
    o = o.swapaxes(0, 1).reshape(B, S, KV, G, hd)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, S)
    return o, lse


def _flash_bwd_body(q, k, v, o, do, lse, qpos, causal, kv_chunk):
    """Recompute-based backward for one q block. Shapes:
    q/o/do [B,bq,KV,G,hd]; lse [B,KV,G,bq]; k/v [B,T,KV,hd]; qpos [bq]."""
    B, bq, KV, G, hd = q.shape
    T = k.shape[1]
    scale = hd ** -0.5
    nkv = T // kv_chunk
    kc = k.reshape(B, nkv, kv_chunk, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, nkv, kv_chunk, KV, hd).swapaxes(0, 1)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [B,bq,KV,G]
    delta = delta.transpose(0, 2, 3, 1)  # [B,KV,G,bq]

    def body(dq, xs):
        j, kj, vj = xs
        s = jnp.einsum("bskgh,btkh->bkgst", q, kj).astype(jnp.float32) * scale
        if causal:
            kpos = (jnp.arange(kv_chunk) + j * kv_chunk).astype(jnp.float32)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # [B,KV,G,bq,bk]
        dp = jnp.einsum("bskgh,btkh->bkgst",
                        do, vj).astype(jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bkgst,btkh->bskgh", ds.astype(q.dtype), kj)
        dkj = jnp.einsum("bkgst,bskgh->btkh", ds.astype(q.dtype), q)
        dvj = jnp.einsum("bkgst,bskgh->btkh", p.astype(q.dtype), do)
        return dq, (dkj, dvj)

    dq0 = jnp.zeros_like(q)
    dq, (dk_c, dv_c) = jax.lax.scan(body, dq0, (jnp.arange(nkv), kc, vc))
    dk = dk_c.swapaxes(0, 1).reshape(B, T, KV, hd)
    dv = dv_c.swapaxes(0, 1).reshape(B, T, KV, hd)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention_xla(q, k, v, qpos, causal, q_chunk, kv_chunk):
    o, _ = _flash_fwd(q, k, v, qpos, causal, q_chunk, kv_chunk)
    return o


def _flash_attention_xla_fwd(q, k, v, qpos, causal, q_chunk, kv_chunk):
    o, lse = _flash_fwd(q, k, v, qpos, causal, q_chunk, kv_chunk)
    return o, (q, k, v, qpos, o, lse)


def _flash_attention_xla_bwd(causal, q_chunk, kv_chunk, res, do_):
    q, k, v, qpos, o, lse = res  # q/o/do_ [B,S,KV,G,hd]; lse [B,KV,G,S]
    B, S, KV, G, hd = q.shape
    nq = max(S // q_chunk, 1)
    if S % q_chunk:
        nq = 1
    bq = S // nq
    qs = q.reshape(B, nq, bq, KV, G, hd).swapaxes(0, 1)
    os_ = o.reshape(B, nq, bq, KV, G, hd).swapaxes(0, 1)
    dos = do_.reshape(B, nq, bq, KV, G, hd).swapaxes(0, 1)
    lses = lse.reshape(B, KV, G, nq, bq).transpose(3, 0, 1, 2, 4)
    qps = qpos.reshape(nq, bq)

    def one_q(args):
        qb, ob, dob, lseb, qp = args
        return _flash_bwd_body(qb, k, v, ob, dob, lseb, qp, causal, kv_chunk)

    dq, dk, dv = jax.lax.map(one_q, (qs, os_, dos, lses, qps))
    dq = dq.swapaxes(0, 1).reshape(B, S, KV, G, hd)
    dk = jnp.sum(dk, axis=0)
    dv = jnp.sum(dv, axis=0)
    return dq, dk, dv, jnp.zeros_like(qpos)


_flash_attention_xla.defvjp(_flash_attention_xla_fwd, _flash_attention_xla_bwd)


def chunked_attention(q, k, v, causal=True, q_offset=0,
                      q_chunk=1024, kv_chunk=512):
    """Memory-bounded flash-style attention with a recompute backward.

    Residuals are only (q, k, v, o, lse) — scores are recomputed per chunk
    in the VJP, so train-time memory is O(S) not O(S^2) (the XLA analogue
    of the flash-attention backward).  q_offset may be a traced scalar (context
    parallelism passes the per-shard row offset)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    kv_chunk = min(kv_chunk, T)
    if T % kv_chunk:
        kv_chunk = T
    q_chunk = min(q_chunk, S)
    qpos = (jnp.arange(S) + q_offset).astype(jnp.float32)
    og = _flash_attention_xla(q.reshape(B, S, KV, G, hd), k, v, qpos,
                              causal, q_chunk, kv_chunk)
    return og.reshape(B, S, H, hd)


# --------------------------------------------------------------------------- #
# Folded-causal attention (beyond-paper perf path)
# --------------------------------------------------------------------------- #
# Causal attention over S splits as:
#   Q_lo  ->  causal(K_lo)                       (recurse)
#   Q_hi  ->  full(K_lo)  merged with  causal(K_hi)  (recurse)
# Each fold level removes the strictly-upper quadrant from the compiled HLO,
# converging to the causal-optimal S^2/2 FLOPs with `depth` levels.
def _merge_partials(o1, m1, l1, o2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None].astype(o1.dtype) + o2 * a2[..., None].astype(o2.dtype)
    return o, m, l


def _full_partial(q, k, v):
    """Unmasked attention partials. q [B,S,KV,G,hd] -> (o, m, l) unnormalized."""
    hd = q.shape[-1]
    s = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * (hd ** -0.5)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgst,btkh->bkgsh", p.astype(q.dtype), v)
    return o, m, l


def _causal_partial(q, k, v, depth):
    B, S, KV, G, hd = q.shape
    if depth <= 0 or S % 2 or S < 256:
        s = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * (hd ** -0.5)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgst,btkh->bkgsh", p.astype(q.dtype), v)
        return o, m, l
    h = S // 2
    q_lo, q_hi = q[:, :h], q[:, h:]
    k_lo, k_hi = k[:, :h], k[:, h:]
    v_lo, v_hi = v[:, :h], v[:, h:]
    o_lo, m_lo, l_lo = _causal_partial(q_lo, k_lo, v_lo, depth - 1)
    o_f, m_f, l_f = _full_partial(q_hi, k_lo, v_lo)
    o_c, m_c, l_c = _causal_partial(q_hi, k_hi, v_hi, depth - 1)
    o_hi, m_hi, l_hi = _merge_partials(o_f, m_f, l_f, o_c, m_c, l_c)
    o = jnp.concatenate([o_lo, o_hi], axis=3)  # seq axis of [B,KV,G,S,hd]
    m = jnp.concatenate([m_lo, m_hi], axis=3)
    l = jnp.concatenate([l_lo, l_hi], axis=3)
    return o, m, l


def folded_causal_attention(q, k, v, depth=4):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, hd)
    o, _, l = _causal_partial(qg, k, v, depth)
    o = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


# --------------------------------------------------------------------------- #
# Context-parallel attention (shard_map over the model axis)
# --------------------------------------------------------------------------- #
def context_parallel_attention(q, k, v, mesh, *, causal=True, q_offset=0,
                               q_chunk=1024, kv_chunk=512,
                               model_axis="model"):
    """Shard q ROWS over the model axis; k/v replicated per shard.

    An lax.map over a seq-sharded block axis SERIALIZES under SPMD (every
    device executes every block), so context parallelism must be expressed
    manually: each model shard computes attention for its 1/M of the query
    rows against the full K/V.  Causality is preserved via per-shard
    q_offset.  Differentiating through shard_map psums the replicated
    k/v cotangents automatically.  Scores memory/traffic drop by M — the
    fix for heads that don't divide the model axis (arctic's 56).
    """
    from jax.sharding import PartitionSpec as P

    M = mesh.shape[model_axis]
    B, S, H, hd = q.shape
    if S % M or (S // M) % 16:
        return chunked_attention(q, k, v, causal, q_offset,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = dp if B % max(
        1, __import__("math").prod(mesh.shape[a] for a in dp)) == 0 else None
    s_loc = S // M

    def body(qb, kb, vb):
        m = jax.lax.axis_index(model_axis)
        off = q_offset + m * s_loc
        return chunked_attention(qb, kb, vb, causal=causal, q_offset=off,
                                 q_chunk=min(q_chunk, s_loc),
                                 kv_chunk=kv_chunk)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, model_axis), P(bspec), P(bspec)),
        out_specs=P(bspec, model_axis),
        check_vma=False,
    )(q, k, v)


# --------------------------------------------------------------------------- #
# Decode (single new token against a KV cache)
# --------------------------------------------------------------------------- #
def decode_attention(q, k_cache, v_cache, pos):
    """q [B,1,H,hd]; caches [B,T,KV,hd]; pos scalar = #valid tokens."""
    B, _, H, hd = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, k_cache).astype(jnp.float32)
    s = s * (hd ** -0.5)
    valid = jnp.arange(T)[None, None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgt,btkh->bkgh", w, v_cache)
    return o.reshape(B, 1, H, hd)


# --------------------------------------------------------------------------- #
# Dispatcher
# --------------------------------------------------------------------------- #
def attention(q, k, v, *, causal=True, q_offset=0, impl="auto", fold_depth=4,
              q_chunk=1024, kv_chunk=512):
    """impl: auto | direct | chunked | folded."""
    S, T = q.shape[1], k.shape[1]
    if impl == "auto":
        if S * T <= 1024 * 1024:
            impl = "direct"
        else:
            impl = "chunked"
    if impl == "direct":
        return direct_attention(q, k, v, causal, q_offset)
    if impl == "folded" and causal and S == T:
        return folded_causal_attention(q, k, v, fold_depth)
    return chunked_attention(q, k, v, causal, q_offset,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
