"""Per-kernel shape/dtype sweeps against pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_norm.ops import fused_residual_rmsnorm
from repro.kernels.fused_norm.ref import fused_ref
from repro.kernels.padded_matmul.ops import padded_matmul
from repro.kernels.padded_matmul.ref import matmul_ref
from repro.kernels.ring_reduce.ops import ring_combine
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

TOLS = {jnp.float32: dict(rtol=3e-4, atol=3e-4),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [(128, 128, 128), (64, 100, 212),
                                 (256, 384, 212), (32, 848, 96)])
def test_padded_matmul_sweep(rng, mkn, dtype):
    M, K, N = mkn
    a = jnp.asarray(rng.standard_normal((M, K)), dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    tol = dict(TOLS[dtype])
    tol["atol"] = max(tol["atol"], 2e-3 * K ** 0.5)
    np.testing.assert_allclose(np.asarray(padded_matmul(a, b), np.float32),
                               np.asarray(matmul_ref(a, b), np.float32),
                               **tol)


@pytest.mark.parametrize("shape", [(1, 64, 2, 8, 8), (2, 128, 3, 16, 8),
                                   (1, 96, 1, 32, 16)])
def test_ssd_scan_sweep(rng, shape):
    B, L, H, P, N = shape
    chunk = 32 if L % 32 == 0 else L
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    r = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, r, rtol=4e-4, atol=4e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(256, 64), (512, 96), (128, 256)])
def test_fused_norm_sweep(rng, shape, dtype):
    R, D = shape
    x = jnp.asarray(rng.standard_normal((R, D)), dtype)
    r = jnp.asarray(rng.standard_normal((R, D)), dtype)
    s = jnp.asarray(rng.standard_normal((D,)), dtype)
    y, h = fused_residual_rmsnorm(x, r, s, block_r=128)
    yr, hr = fused_ref(x, r, s)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOLS[dtype])
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("C,block", [(4096, 512), (2048, 1024), (1024, 1024)])
def test_ring_combine(rng, C, block):
    a = jnp.asarray(rng.standard_normal(C), jnp.float32)
    b = jnp.asarray(rng.standard_normal(C), jnp.float32)
    out, prog = ring_combine(a, b, block=block)
    np.testing.assert_allclose(out, a + b, rtol=1e-6)
    np.testing.assert_array_equal(prog, np.arange(1, C // block + 1))


def test_interpret_only_on_cpu(monkeypatch):
    from repro.kernels import interpret_default
    assert interpret_default() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_default()
