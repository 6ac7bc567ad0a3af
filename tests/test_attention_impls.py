"""Attention implementation equivalence incl. the folded-causal perf path
and the flash-style custom VJP."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (attention_kv_blocks, chunked_attention,
                                    decode_attention, direct_attention,
                                    folded_causal_attention)


@pytest.fixture
def qkv(rng):
    B, S, H, KV, hd = 2, 256, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    return q, k, v


def test_folded_equals_direct(qkv):
    q, k, v = qkv
    for depth in (1, 2, 3):
        o = folded_causal_attention(q, k, v, depth=depth)
        r = direct_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o, r, rtol=3e-4, atol=3e-4)


def test_chunked_gradients_match_direct(qkv):
    q, k, v = qkv

    def loss_chunked(q, k, v):
        return jnp.sum(jnp.tanh(chunked_attention(
            q, k, v, causal=True, q_chunk=64, kv_chunk=64)))

    def loss_direct(q, k, v):
        return jnp.sum(jnp.tanh(direct_attention(q, k, v, causal=True)))

    g1 = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_direct, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-3)


def test_decode_matches_direct_row(qkv):
    q, k, v = qkv
    pos = 100
    o_full = direct_attention(q[:, :pos + 1], k[:, :pos + 1],
                              v[:, :pos + 1], causal=True)
    o_dec = decode_attention(q[:, pos:pos + 1], k, v, jnp.int32(pos))
    np.testing.assert_allclose(o_dec[:, 0], o_full[:, pos],
                               rtol=3e-4, atol=3e-4)


# The flash path against direct attention, with several q blocks and KV
# chunks: (S, T, q_chunk, kv_chunk, causal, q_offset, traced offset).
FLASH_CASES = {
    "q256_kv128": (2048, 2048, 256, 128, True, 0, False),
    "q128_kv128": (2048, 2048, 128, 128, True, 0, False),
    "q128_kv256": (2048, 2048, 128, 256, True, 0, False),
    "static_offset": (2048, 2048, 256, 128, True, 384, False),
    "traced_offset": (2048, 2048, 256, 128, True, 384, True),
    "noncausal": (2048, 2048, 256, 128, False, 0, False),
    "rows_at_end_of_longer_keys": (1024, 2048, 256, 128, True, 1024, False),
}


@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_flash_skipping_matches_direct(case):
    S, T, q_chunk, kv_chunk, causal, q_offset, traced = FLASH_CASES[case]
    r = np.random.default_rng(1)
    B, H, KV, hd = 1, 4, 2, 16
    q = jnp.asarray(r.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, T, KV, hd)), jnp.float32)

    def flash(q, k, v, off):
        return chunked_attention(q, k, v, causal, off, q_chunk=q_chunk,
                                 kv_chunk=kv_chunk)

    def direct(q, k, v, off):
        return direct_attention(q, k, v, causal, off)

    def loss(att):
        return lambda q, k, v, off: jnp.sum(jnp.tanh(att(q, k, v, off)))

    off = jnp.int32(q_offset) if traced else q_offset
    static = () if traced else (3,)
    o = jax.jit(flash, static_argnums=static)(q, k, v, off)
    g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)),
                static_argnums=static)(q, k, v, off)
    np.testing.assert_allclose(o, direct(q, k, v, q_offset),
                               rtol=3e-4, atol=3e-4)
    want = jax.grad(loss(direct), argnums=(0, 1, 2))(q, k, v, q_offset)
    for a, b in zip(g, want):
        np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("shape,want", [
    ((16384, 16384, 0, 1024, 512), (272, 512)),
    ((4096, 4096, 0, 1024, 512), (20, 32)),
])
def test_attention_kv_blocks_at_the_bench_shapes(shape, want):
    assert attention_kv_blocks(*shape) == want
    assert attention_kv_blocks(*shape, causal=False) == (want[1], want[1])


@pytest.mark.parametrize("shape", [
    (2048, 2048, 0, 256, 128), (2048, 2048, 384, 256, 128),
    (1024, 2048, 1024, 256, 128), (2048, 2048, 0, 128, 256),
    (1000, 1000, 0, 256, 128),   # sizes that do not divide: one block
])
def test_attention_kv_blocks_counts_pairs_with_an_unmasked_entry(shape):
    S, T, q_offset, q_chunk, kv_chunk = shape
    q_chunk = q_chunk if S % q_chunk == 0 else S
    kv_chunk = kv_chunk if T % kv_chunk == 0 else T
    qpos = np.arange(S) + q_offset
    unmasked = qpos[:, None] >= np.arange(T)[None, :]
    pairs = unmasked.reshape(S // q_chunk, q_chunk, T // kv_chunk, kv_chunk)
    assert attention_kv_blocks(*shape) == (int(pairs.any(axis=(1, 3)).sum()),
                                           pairs.shape[0] * pairs.shape[2])
