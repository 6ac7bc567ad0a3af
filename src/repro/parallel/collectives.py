"""Progress-instrumented ring collectives.

FLARE's intra-kernel inspecting (paper §5.1, Fig 6) reads per-ring-step
progress counters out of a hung collective to localize the faulty machine in
O(1).  On GPU the paper attaches CUDA-GDB to NCCL kernels; XLA collectives
are compiler-generated, so we instead make progress export a *first-class
output of the collective itself*: our ring reduce-scatter / all-gather
return a per-rank vector of completed ring steps alongside the result.  On a
real TPU fleet those counters would be streamed to host-visible memory per
step; under a hang the frozen counters are exactly the state the inspector
needs (see repro.core.inspecting).

These collectives run inside ``shard_map`` over one mesh axis and use
``lax.ppermute`` rings — the same schedule NCCL uses, expressed jax-natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ring_perm(n: int, reverse: bool = False):
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def ring_reduce_scatter_local(x, axis_name: str, axis_size: int,
                              with_progress: bool = True):
    """Per-shard body: x [N*chunk, ...] -> (owned chunk [chunk, ...], progress).

    Classic ring reduce-scatter: N-1 steps; at step s each rank sends the
    chunk it just accumulated to its right neighbour.  progress[s] = 1 once
    step s completed on this rank.
    """
    n = axis_size
    rank = jax.lax.axis_index(axis_name)
    chunks = x.reshape((n,) + (x.shape[0] // n,) + x.shape[1:])
    perm = _ring_perm(n)

    def body(s, carry):
        acc, progress = carry
        # chunk index this rank SENDS at step s: (rank - s) mod n
        send_idx = (rank - s) % n
        recv_idx = (rank - s - 1) % n
        sent = jax.lax.ppermute(acc[send_idx], axis_name, perm)
        acc = acc.at[recv_idx].add(sent)
        progress = progress.at[s].set(1) if with_progress else progress
        return acc, progress

    progress0 = jnp.zeros((max(n - 1, 1),), jnp.int32)
    acc, progress = jax.lax.fori_loop(0, n - 1, body, (chunks, progress0))
    owned = acc[(rank + 1) % n]
    return owned, progress


def ring_all_gather_local(x, axis_name: str, axis_size: int,
                          with_progress: bool = True, slot_offset: int = 0):
    """Per-shard body: x [chunk, ...] -> (gathered [N*chunk, ...], progress).

    `slot_offset`: rank r's local chunk is global chunk (r + slot_offset)
    mod N — reduce-scatter hands rank r chunk (r+1), so the composed
    all-reduce passes slot_offset=1.
    """
    n = axis_size
    rank = jax.lax.axis_index(axis_name)
    my_slot = (rank + slot_offset) % n
    out = jnp.zeros((n,) + x.shape, x.dtype).at[my_slot].set(x)
    perm = _ring_perm(n)

    def body(s, carry):
        out, cur, progress = carry
        nxt = jax.lax.ppermute(cur, axis_name, perm)
        # received value originated at rank (rank - s - 1)
        slot = (rank - s - 1 + slot_offset) % n
        out = out.at[slot].set(nxt)
        progress = progress.at[s].set(1) if with_progress else progress
        return out, nxt, progress

    progress0 = jnp.zeros((max(n - 1, 1),), jnp.int32)
    out, _, progress = jax.lax.fori_loop(0, n - 1, body, (out, x, progress0))
    return out.reshape((n * x.shape[0],) + x.shape[1:]), progress


def ring_all_reduce_local(x, axis_name: str, axis_size: int,
                          with_progress: bool = True):
    """reduce-scatter + all-gather ring; 2(N-1) progress steps."""
    owned, p1 = ring_reduce_scatter_local(x, axis_name, axis_size,
                                          with_progress)
    # reduce-scatter leaves rank r holding fully-reduced chunk (r+1) % N
    full, p2 = ring_all_gather_local(owned, axis_name, axis_size,
                                     with_progress, slot_offset=1)
    return full, jnp.concatenate([p1, p2])


def ring_all_reduce(x, mesh: Mesh, axis: str = "model",
                    with_progress: bool = True):
    """jit-level wrapper: all-reduce `x` (replicated result) over `axis`.

    x's leading dim must be divisible by the axis size.  Returns
    (result, progress [axis_size, 2*(N-1)]).
    """
    n = mesh.shape[axis]

    def body(xs):
        return ring_all_reduce_local(xs, axis, n, with_progress)

    other = tuple(a for a in mesh.axis_names if a != axis)
    res, prog = jax.shard_map(
        body, mesh=mesh,
        in_specs=P(),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )(x)
    return res, prog.reshape(n, -1)


# --------------------------------------------------------------------------- #
# int8-compressed gradient all-reduce (distributed-optimization trick)
# --------------------------------------------------------------------------- #
def quantize_int8(x, block: int = 256, rng=None):
    """Block-wise absmax int8 quantization with optional stochastic rounding."""
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = blocks / scale
    if rng is not None:
        q = jnp.floor(q + jax.random.uniform(rng, q.shape))
    else:
        q = jnp.round(q)
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale[:, 0], x.shape, pad


def dequantize_int8(q, scale, shape, pad):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def compressed_psum_local(x, axis_name: str, error: jax.Array | None = None,
                          block: int = 256):
    """int8 all-reduce with error feedback, inside shard_map.

    Quantizes the local contribution, psums int32-accumulated values, and
    carries the quantization error to the next call (error feedback keeps
    SGD/Adam convergence — Karimireddy et al. 2019).
    Returns (reduced fp32, new_error).
    """
    xf = x.astype(jnp.float32)
    if error is not None:
        xf = xf + error
    # shared per-block scale: psum-max of local absmax (tiny collective),
    # then int8 payload psum'd in int32 — exact shared-scale semantics, the
    # local quantization error goes into error feedback.
    flat = xf.reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    local_max = jnp.max(jnp.abs(blocks), axis=1)
    scale = jax.lax.pmax(local_max, axis_name) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127)
    local_dq = (q * scale[:, None]).reshape(-1)
    local_dq = local_dq[:local_dq.size - pad] if pad else local_dq
    new_error = xf - local_dq.reshape(xf.shape)
    summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
    out = (summed.astype(jnp.float32) * scale[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(xf.shape), new_error
