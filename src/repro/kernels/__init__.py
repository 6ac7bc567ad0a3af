"""Pallas TPU kernels for the compute hot-spots + FLARE tracing seams.

Kernels (each: kernel.py = pl.pallas_call + BlockSpec, ops.py = jit'd
wrapper, ref.py = pure-jnp oracle):
  padded_matmul    — Case-2: MXU-alignment padding inside the tile
  ssd_scan         — Mamba2 chunked state-space scan, forward and backward
  fused_norm       — residual+RMSNorm fusion (Table-5 minority kernels)
  ring_reduce      — ring-combine step with progress export (intra-kernel
                     inspecting seam)

Only ``ssd_scan`` is on a model path: ``models/mamba2.ssd_chunked`` calls
its kernel function on the TPU where ``ssd_path`` says the shapes tile.
``tests/test_tpu_compile.py`` holds each kernel to the v5e compiler at
real widths.  ``interpret_default()`` is True on the
CPU backend, where the tests validate them, and any backend other than
CPU or TPU is an error rather than a silent switch to the interpreter.
Every ops.py entry point self-registers with an attached FLARE daemon —
this is the paper's explicit "C++ interface" registration (§4.1).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax


def interpret_default() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"these Pallas kernels run on the TPU, or interpreted on the "
            f"CPU; JAX's backend is {backend!r}")
    return backend == "cpu"


def traced_op(name: str, kind: str = "compute",
              meta_fn: Optional[Callable] = None):
    """Wrap an op entry point with FLARE kernel tracing when attached."""
    from repro.core.daemon import get_daemon
    from repro.core.events import EventKind

    ekind = (EventKind.KERNEL_COMPUTE if kind == "compute"
             else EventKind.KERNEL_COMM)

    def deco(fn):
        def wrapped(*args, **kwargs):
            daemon = get_daemon()
            if daemon is None:
                return fn(*args, **kwargs)
            issue = time.perf_counter()
            out = fn(*args, **kwargs)
            meta = meta_fn(*args, **kwargs) if meta_fn else {}
            daemon._pending.put((name, ekind, issue, daemon._step, out, meta))
            return out
        wrapped.__name__ = getattr(fn, "__name__", name)
        wrapped.__wrapped__ = fn
        return wrapped
    return deco
