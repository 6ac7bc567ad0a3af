"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Provided as a first-class feature (the assignment requires PP support at
scale) but not used by the default configs: on a 2-pod v5e slice every
assigned arch fits with ZeRO-DP x TP (+ int8 optimizer state), where PP's
bubble only hurts (see DESIGN.md §5).

The schedule is the classic GPipe fill-drain loop expressed with shard_map
over the ``stage`` axis + ppermute of microbatch activations.  With M
microbatches and S stages the bubble fraction is (S-1)/(M+S-1).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, x_microbatches,
                   mesh: Mesh, axis: str = "stage"):
    """Run microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x        (one stage's layers)
    params_stacked: pytree with leading [S] dim, sharded over `axis`
    x_microbatches: [M, mb, ...] activations (M >= S recommended)
    Returns [M, mb, ...] outputs (from the last stage, gathered).
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    T = M + S - 1  # total ticks (fill + steady + drain)

    def shard_body(sparams, xs):
        stage = jax.lax.axis_index(axis)
        # per-shard param block keeps a leading [1] stage dim — drop it
        sparams = jax.tree.map(lambda a: a[0], sparams)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)  # current activation
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range); others take the
            # activation permuted from the previous stage.
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, feed, buf)
            mb_idx = t - stage  # microbatch this stage processes at tick t
            active = (mb_idx >= 0) & (mb_idx < M)
            y = stage_fn(sparams, inp)
            y = jnp.where(active, y, buf)
            # pass activation to next stage (ring permute; last->0 unused)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            outs = jax.lax.cond(
                active & (stage == S - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                lambda o: o, outs)
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # only the last stage holds real outputs; psum broadcasts them
        outs = outs * (stage == S - 1)
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x_microbatches)
