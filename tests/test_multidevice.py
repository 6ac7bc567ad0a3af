"""Multi-device sharding semantics, run in a subprocess with 8 fake devices
(the main test process must keep seeing ONE device — assignment rule)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.collectives import (ring_all_reduce,
                                            compressed_psum_local)
    from repro.parallel.pipeline import pipeline_apply

    mesh = make_test_mesh(data=2, model=4)

    # ---- progress-instrumented ring all-reduce == psum ---------------- #
    x = jnp.arange(32.0).reshape(8, 4)
    res, prog = jax.jit(
        lambda v: ring_all_reduce(v, mesh, axis="model"))(x)
    # input replicated over model => allreduce sums 4 copies
    np.testing.assert_allclose(np.asarray(res), 4 * np.asarray(x), rtol=1e-6)
    prog = np.asarray(prog)
    assert prog.shape == (4, 6) and prog.min() == 1  # 2*(N-1) steps done
    print("ring_all_reduce OK")

    # ---- int8 compressed psum with error feedback --------------------- #
    def body(v):
        out, err = compressed_psum_local(v, "model", None)
        return out, err
    xs = jnp.linspace(-2, 2, 64).reshape(8, 8)
    out, err = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=(P(), P("model")),
        check_vma=False))(xs)
    np.testing.assert_allclose(np.asarray(out), 4 * np.asarray(xs),
                               rtol=0.05, atol=0.05)
    print("compressed_psum OK")

    # ---- GPipe pipeline == sequential application --------------------- #
    smesh = make_test_mesh(data=1, model=1)  # placeholder
    from repro.launch.mesh import _mk
    pmesh = _mk((4,), ("stage",))
    def stage_fn(w, x):
        return jnp.tanh(x @ w)
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.5
    xs = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))  # M=8 mb=4
    out = pipeline_apply(stage_fn, ws, xs, pmesh, axis="stage")
    ref = xs
    for i in range(4):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("pipeline OK")

    # ---- MoE expert-parallel == local oracle --------------------------- #
    from repro.configs import get_reduced
    from repro.models.moe import moe_apply, moe_init
    cfg = get_reduced("dbrx-132b")  # 4 experts top-2
    params = moe_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, cfg.d_model))
    y_local, aux_l = moe_apply(params, x, cfg, mesh=None)
    y_shard, aux_s = jax.jit(
        lambda p, v: moe_apply(p, v, cfg, mesh=mesh))(params, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_shard),
                               rtol=2e-4, atol=2e-4)
    print("moe EP OK")
""")


def test_multidevice_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    for marker in ("ring_all_reduce OK", "compressed_psum OK",
                   "pipeline OK", "moe EP OK"):
        assert marker in r.stdout, r.stdout + r.stderr
