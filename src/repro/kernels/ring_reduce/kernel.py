"""Ring-combine step with progress export (the intra-kernel-inspecting seam).

One ring step of reduce-scatter is: acc_chunk += incoming_chunk.  This
kernel performs the chunked combine AND writes a per-block progress counter
to a dedicated output buffer — the TPU-native equivalent of the ring-step
registers FLARE reads out of a hung NCCL kernel with CUDA-GDB (paper Fig 6).
On hardware the progress buffer lives in HBM and is host-visible mid-kernel
via async copies; under a hang its frozen values feed
repro.core.inspecting.diagnose_ring directly.

Grid: (chunk_elems // block,) — progress[i] = i+1 after block i combines.
The progress vector sits whole in SMEM (one scalar store per block), so
its block never has to match the vector-memory tiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(acc_ref, in_ref, o_ref, prog_ref):
    i = pl.program_id(0)
    o_ref[...] = acc_ref[...] + in_ref[...]
    prog_ref[i] = i + 1  # SASS step-counter analogue, host-readable


def ring_combine_step(acc, incoming, *, block=1024, interpret=False):
    """acc, incoming [C] -> (combined [C], progress [C//block] int32)."""
    (C,) = acc.shape
    block = min(block, C)
    assert C % block == 0
    n_blocks = C // block
    out, prog = pl.pallas_call(
        _combine_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((C,), acc.dtype),
                   jax.ShapeDtypeStruct((n_blocks,), jnp.int32)],
        interpret=interpret,
    )(acc, incoming)
    return out, prog
