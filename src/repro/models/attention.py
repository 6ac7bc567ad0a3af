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
import numpy as np

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
def _chunk_sizes(S, T, q_chunk, kv_chunk):
    """The q block and KV chunk the flash path runs: one block (or chunk)
    over the whole length wherever the size does not divide it."""
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    return (S if S % q_chunk else q_chunk), (T if T % kv_chunk else kv_chunk)


def _kv_chunks_visited(last_qpos, kv_chunk, n_chunks):
    """How many leading KV chunks a causal q block needs: chunk j holds an
    unmasked key iff its first key, j * kv_chunk, is at or before the
    block's last row.  Works on numpy and traced positions alike."""
    return (last_qpos // kv_chunk + 1).astype("int32").clip(0, n_chunks)


def attention_kv_blocks(S, T, q_offset=0, q_chunk=1024, kv_chunk=512,
                        causal=True):
    """(visited, total) (q block, KV chunk) pairs of one ``chunked_attention``
    call per batch row: the loop bound's own arithmetic on the host."""
    q_chunk, kv_chunk = _chunk_sizes(S, T, q_chunk, kv_chunk)
    nq, nkv = S // q_chunk, T // kv_chunk
    if not causal:
        return nq * nkv, nq * nkv
    last = (np.arange(1, nq + 1) * q_chunk - 1 + q_offset).astype(np.float32)
    return int(_kv_chunks_visited(last, kv_chunk, nkv).sum()), nq * nkv


def _kv_chunks(x, kv_chunk):
    """[B,T,KV,hd] -> [T/kv_chunk, B, kv_chunk, KV, hd]."""
    B, T, KV, hd = x.shape
    return x.reshape(B, T // kv_chunk, kv_chunk, KV, hd).swapaxes(0, 1)


def _n_visited(qpos, kv_chunk, n_chunks, causal):
    if not causal:
        return n_chunks
    return _kv_chunks_visited(jnp.max(qpos), kv_chunk, n_chunks)


def _chunk_scan(q, kc, vc, causal, qpos):
    """Online-softmax loop over KV chunks for one q block.

    q: [B,Sq,KV,G,hd]; kc/vc: [n_chunks,B,kv_chunk,KV,hd]; qpos: f32 [Sq]
    global row positions (an ARRAY so it stays valid when traced, e.g.
    under shard_map context parallelism).  Causal, the loop stops at the
    last chunk at or below the diagonal: every later chunk is wholly
    masked and would add exact zeros."""
    B, Sq, KV, G, hd = q.shape
    n_chunks, _, kv_chunk = kc.shape[:3]
    scale = hd ** -0.5

    def body(j, carry):
        o, m, l = carry
        kj, vj = kc[j], vc[j]
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
        return o_new, m_new, l_new

    o0 = jnp.zeros((B, KV, G, Sq, hd), q.dtype)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, _n_visited(qpos, kv_chunk, n_chunks, causal),
                                body, (o0, m0, l0))
    o = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    o = o.transpose(0, 3, 1, 2, 4)  # [B,Sq,KV,G,hd]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,KV,G,Sq]
    return o, lse


def _flash_fwd(qg, k, v, qpos, causal, q_chunk, kv_chunk):
    B, S, KV, G, hd = qg.shape
    nq = S // q_chunk
    qs = qg.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)
    qps = qpos.reshape(nq, q_chunk)
    kc, vc = _kv_chunks(k, kv_chunk), _kv_chunks(v, kv_chunk)

    def one_q(args):
        qb, qp = args
        return _chunk_scan(qb, kc, vc, causal, qp)

    o, lse = jax.lax.map(one_q, (qs, qps))
    # o: [nq, B, bq, KV, G, hd]; lse: [nq, B, KV, G, bq]
    o = o.swapaxes(0, 1).reshape(B, S, KV, G, hd)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, S)
    return o, lse


def _flash_bwd_body(q, kc, vc, o, do, lse, qpos, causal, dk, dv):
    """Recompute-based backward for one q block.  Shapes: q/o/do
    [B,bq,KV,G,hd]; lse [B,KV,G,bq]; kc/vc [n_chunks,B,kv_chunk,KV,hd];
    qpos [bq]; dk/dv float32 running sums over the q blocks, shaped as kc.
    Visits the forward's chunks; the skipped ones add nothing to dk/dv."""
    B, bq, KV, G, hd = q.shape
    n_chunks, _, kv_chunk = kc.shape[:3]
    scale = hd ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [B,bq,KV,G]
    delta = delta.transpose(0, 2, 3, 1)  # [B,KV,G,bq]

    def body(j, carry):
        dq, dk, dv = carry
        kj, vj = kc[j], vc[j]
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
        dkj = jnp.einsum("bkgst,bskgh->btkh", ds.astype(q.dtype), q,
                         preferred_element_type=jnp.float32)
        dvj = jnp.einsum("bkgst,bskgh->btkh", p.astype(q.dtype), do,
                         preferred_element_type=jnp.float32)
        dk = jax.lax.dynamic_update_index_in_dim(dk, dk[j] + dkj, j, 0)
        dv = jax.lax.dynamic_update_index_in_dim(dv, dv[j] + dvj, j, 0)
        return dq, dk, dv

    return jax.lax.fori_loop(0, _n_visited(qpos, kv_chunk, n_chunks, causal),
                             body, (jnp.zeros_like(q), dk, dv))


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
    T = k.shape[1]
    nq = S // q_chunk

    def blocks(x):
        return x.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)

    lses = lse.reshape(B, KV, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    qps = qpos.reshape(nq, q_chunk)
    kc, vc = _kv_chunks(k, kv_chunk), _kv_chunks(v, kv_chunk)

    def one_q(acc, args):
        qb, ob, dob, lseb, qp = args
        dq, dk, dv = _flash_bwd_body(qb, kc, vc, ob, dob, lseb, qp, causal,
                                     *acc)
        return (dk, dv), dq

    zeros = jnp.zeros(kc.shape, jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        one_q, (zeros, zeros), (blocks(q), blocks(o), blocks(do_), lses, qps))
    dq = dq.swapaxes(0, 1).reshape(B, S, KV, G, hd)
    dk = dk.swapaxes(0, 1).reshape(B, T, KV, hd).astype(k.dtype)
    dv = dv.swapaxes(0, 1).reshape(B, T, KV, hd).astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(qpos)


_flash_attention_xla.defvjp(_flash_attention_xla_fwd, _flash_attention_xla_bwd)


def chunked_attention(q, k, v, causal=True, q_offset=0,
                      q_chunk=1024, kv_chunk=512):
    """Memory-bounded flash-style attention with a recompute backward.

    Residuals are only (q, k, v, o, lse) — scores are recomputed per chunk
    in the VJP, so train-time memory is O(S) not O(S^2) (the XLA analogue
    of the flash-attention backward).  Causal, each q block visits only
    the KV chunks at or below the diagonal, forward and backward
    (``attention_kv_blocks`` counts them).  q_offset may be a traced
    scalar (context parallelism passes the per-shard row offset): the
    bound comes from the rows' positions, not from a static offset."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk, kv_chunk = _chunk_sizes(S, T, q_chunk, kv_chunk)
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
def attention_path(S, T, impl="auto", causal=True):
    """The path ``attention`` takes for S query and T key rows: direct,
    folded or chunked."""
    if impl == "auto":
        impl = "direct" if S * T <= 1024 * 1024 else "chunked"
    if impl == "direct":
        return "direct"
    if impl == "folded" and causal and S == T:
        return "folded"
    return "chunked"


def attention(q, k, v, *, causal=True, q_offset=0, impl="auto", fold_depth=4,
              q_chunk=1024, kv_chunk=512):
    """impl: auto | direct | chunked | folded."""
    path = attention_path(q.shape[1], k.shape[1], impl, causal)
    if path == "direct":
        return direct_attention(q, k, v, causal, q_offset)
    if path == "folded":
        return folded_causal_attention(q, k, v, fold_depth)
    return chunked_attention(q, k, v, causal, q_offset,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
