"""jit'd entry point for the SSD scan kernel (+ FLARE registration)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default, traced_op
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


def _meta(x, dt, A, Bm, Cm, **kw):
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    chunk = kw.get("chunk", 256)
    flops = 2.0 * B * L * H * (chunk * (N + P) + N * P * 2)
    return {"flops": flops, "shape": list(x.shape)}


@traced_op("ssd_scan", "compute", _meta)
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk=256, interpret=None):
    if interpret is None:
        interpret = interpret_default()
    y, _ = ssd_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    return y
