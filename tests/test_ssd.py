"""The chunked SSD scan at the published chunk of 256 against the
step-by-step recurrence: forward and every gradient, over two chunks, with
dt and A at the extremes of the Mamba2 init (dt up to 0.1, |A| up to 16).
There the within-chunk exponents above the diagonal reach 0.1 x 16 x 255,
far past float32's ``exp``: the gradient is finite only where they are
masked before ``exp``.  Both paths of ``ssd_chunked``: the jnp body and
the Pallas kernels (interpreted on the CPU), at the published head dim and
state.  Also the path's choice, and the pair counts the trainer's counters
add."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.models.mamba2 import (ssd_chunked, ssd_pairs, ssd_path,
                                 ssd_sequential)

B, L, H, P, N, CHUNK = 1, 512, 4, 64, 128, 256
SCANS = {
    "jnp": functools.partial(ssd_chunked, chunk=CHUNK),
    "pallas": functools.partial(ssd_scan_kernel, chunk=CHUNK,
                                interpret=True),
}
# float32 sums in a different order over 512 steps: the observed worst
# relative error is 7e-6; 1e-4 is the bound, far below any masking fault,
# which gives NaN or errors of order one.
RTOL = 1e-4


def _inputs(case):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (B, L, H, P))
    Bm = 0.3 * jax.random.normal(k[1], (B, L, N))
    Cm = 0.3 * jax.random.normal(k[2], (B, L, N))
    A = -jnp.linspace(1.0, 16.0, H)
    if case in ("dt_max", "initial_state"):
        dt = jnp.full((B, L, H), 0.1)
    else:   # log-uniform over the init's [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(k[3], (B, L, H), minval=jnp.log(1e-3),
                                        maxval=jnp.log(1e-1)))
    S0 = (0.5 * jax.random.normal(k[4], (B, H, P, N))
          if case == "initial_state" else None)
    return (x, dt, A, Bm, Cm), S0


def _loss(scan, S0):
    w = jnp.cos(jnp.arange(P, dtype=jnp.float32))

    def loss(x, dt, A, Bm, Cm):
        y, S = scan(x, dt, A, Bm, Cm, initial_state=S0)
        return jnp.sum(y * w) + jnp.sum(jnp.sin(S))
    return loss


def _close(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("case", ["dt_max", "dt_loguniform",
                                  "initial_state"])
@pytest.mark.parametrize("path", list(SCANS))
def test_ssd_chunked_matches_sequential_at_chunk_256(path, case):
    args, S0 = _inputs(case)
    chunked = SCANS[path]
    y, S = chunked(*args, initial_state=S0)
    y_ref, S_ref = ssd_sequential(*args, initial_state=S0)
    assert _close(y, y_ref) < RTOL and _close(S, S_ref) < RTOL
    argnums = tuple(range(5))
    grads = jax.grad(_loss(chunked, S0), argnums)(*args)
    refs = jax.grad(_loss(ssd_sequential, S0), argnums)(*args)
    for name, g, r in zip(("x", "dt", "A", "B", "C"), grads, refs):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _close(g, r) < RTOL, (name, _close(g, r))
    if S0 is not None:   # the initial state's gradient too
        g = jax.grad(lambda s: _loss(chunked, s)(*args))(S0)
        r = jax.grad(lambda s: _loss(ssd_sequential, s)(*args))(S0)
        assert _close(g, r) < RTOL, ("initial_state", _close(g, r))


@pytest.mark.parametrize("shape,backend,want", [
    ((2048, 256, 48, 64, 128), "tpu", "pallas"),   # mamba2-780m.seq2k
    ((2048, 256, 48, 64, 128), "cpu", "jnp"),
    ((2000, 256, 48, 64, 128), "tpu", "jnp"),      # 256 does not divide L
    ((2048, 256, 80, 64, 64), "tpu", "jnp"),       # zamba2-2.7b: N 64
    ((2048, 16, 8, 16, 16), "tpu", "jnp"),         # a reduced config
])
def test_ssd_path_runs_the_kernel_where_the_blocks_tile(shape, backend, want):
    assert ssd_path(*shape, backend) == want


@pytest.mark.parametrize("seq,chunk,want", [
    (2048, 256, (8 * 256 * 257 // 2, 8 * 256 * 256)),  # the cell's grid
    (512, 256, (2 * 256 * 257 // 2, 2 * 256 * 256)),
    (64, 256, (64 * 65 // 2, 64 * 64)),       # one chunk, the sequence
    (96, 64, (96 * 97 // 2, 96 * 96)),        # 64 does not divide 96
])
def test_ssd_pairs_count_the_chunks_the_scan_forms(seq, chunk, want):
    assert ssd_pairs(seq, chunk) == want
    kept, total = ssd_pairs(seq, chunk)
    if (seq, chunk) == (2048, 256):
        assert round(kept / total, 3) == 0.502
