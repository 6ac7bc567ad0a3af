"""Mamba2 chunked SSD scan (Pallas TPU).

Grid: (batch, heads, L // chunk) — the chunk axis is innermost/sequential;
the inter-chunk SSM state [N, P] lives in VMEM scratch and persists across
grid steps for a fixed (b, h), reset at chunk 0.  Within a chunk the
quadratic intra-term runs on the MXU; the state update is two small
matmuls.  This is the TPU-native shape of the SSD algorithm: HBM traffic
is O(L·(P+N)) while compute stays MXU-dense.

The wrapper moves heads ahead of the sequence so that every VMEM block
ends in a (chunk, lanes) tile, and hands ``dt`` in twice — as a column
[chunk, 1] and as a row [1, chunk] — so the kernel forms the inclusive
cumulative decay in both orientations with masked reductions instead of
1-D scans or transposes.  The per-head ``A`` sits whole in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref,
                state_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    A = a_ref[pl.program_id(1)]                  # scalar (this head)
    x = x_ref[...].astype(jnp.float32)           # [Q, P]
    dt_c = dtc_ref[...].astype(jnp.float32)      # [Q, 1]
    dt_r = dtr_ref[...].astype(jnp.float32)      # [1, Q]
    Bm = b_ref[...].astype(jnp.float32)          # [Q, N]
    Cm = c_ref[...].astype(jnp.float32)          # [Q, N]

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ti >= si
    # inclusive decay cum[t] = sum_{s<=t} dt[s]*A (<= 0), in both layouts
    cum_c = jnp.sum(jnp.where(causal, dt_r * A, 0.0), axis=1,
                    keepdims=True)               # [Q, 1]
    cum_r = jnp.sum(jnp.where(ti <= si, dt_c * A, 0.0), axis=0,
                    keepdims=True)               # [1, Q]
    total = jnp.sum(dt_r * A, axis=1, keepdims=True)   # [1, 1]
    # ---- intra-chunk (quadratic) ---------------------------------------- #
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # [Q, Q]
    decay = jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf))
    y = jax.lax.dot(cb * decay * dt_r, x)        # [Q, P]
    # ---- inter-chunk (state) -------------------------------------------- #
    S = state_ref[...]                           # [N, P]
    y += jnp.exp(cum_c) * jax.lax.dot(Cm, S)
    w = jnp.exp(total - cum_c) * dt_c            # [Q, 1]
    state_ref[...] = jnp.exp(total) * S + jax.lax.dot_general(
        Bm, w * x, (((0,), (0,)), ((), ())))     # [N, P]
    y_ref[...] = y.astype(y_ref.dtype)


def ssd_scan_fwd(x, dt, A, Bm, Cm, *, chunk=128, interpret=False):
    """x [B,L,H,P]; dt [B,L,H]; A [H]; Bm/Cm [B,L,N] -> y [B,L,H,P]."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    dt_h = dt.transpose(0, 2, 1)                 # [B, H, L]
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, L // chunk),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt_h[..., None], dt_h[:, :, None, :],
      A.astype(jnp.float32), Bm, Cm)
    return y.transpose(0, 2, 1, 3)
