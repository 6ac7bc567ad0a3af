"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what the CPU and the Pallas interpreter accept:
blocks off the (8, 128) tiling, programs that overflow the chip's HBM.
These cases hold the full-width trainer step and every Pallas kernel to
it at real widths.  The topology is described only inside a fixture, so
importing this file never loads the TPU library.
"""
import importlib
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.fused_norm.ops import fused_residual_rmsnorm
from repro.kernels.padded_matmul.ops import padded_matmul
from repro.kernels.ring_reduce.ops import ring_combine
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models.attention import chunked_attention
from repro.optim.adamw import adamw_init
from repro.runtime.train import RunConfig, Trainer

V5E_HBM_BYTES = 15.75 * 2 ** 30   # what the v5e compiler reports as usable


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _total_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            + mem.generated_code_size_in_bytes)


def test_qwen2_full_width_train_step_fits_v5e(one_chip):
    """The chip smoke's shape: 8 x 1024 tokens, full remat, float32
    params and AdamW state, bf16 compute."""
    run = RunConfig(model=get_config("qwen2-0.5b"), global_batch=8,
                    seq_len=1024, remat="full", flare=False)
    trainer = Trainer(run)
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, run.opt), params)
    tok = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    args = _shapes((params, opt, {"tokens": tok, "labels": tok},
                    jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
    compiled = trainer.step_fn.lower(*args).compile()
    total = _total_bytes(compiled.memory_analysis())
    assert 0 < total < V5E_HBM_BYTES, total / 2 ** 30


@pytest.fixture(scope="module")
def mamba2_steps(one_chip):
    """The ``mamba2-780m.seq2k`` cell's step compiled on each SSD path:
    ``pallas`` as the chip runs it (``ssd_path`` asks the backend, which is
    the CPU here, so it is told the TPU while the step is traced) and
    ``jnp``, the scan's einsum body."""
    out = {}
    for path in ("pallas", "jnp"):
        run = RunConfig(model=get_config("mamba2-780m"), global_batch=2,
                        seq_len=2048, remat="full", flare=False)
        trainer = Trainer(run)
        params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
        opt = jax.eval_shape(lambda p: adamw_init(p, run.opt), params)
        tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
        args = _shapes((params, opt, {"tokens": tok, "labels": tok},
                        jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
        with pytest.MonkeyPatch.context() as mp:
            if path == "pallas":
                mp.setattr(jax, "default_backend", lambda: "tpu")
            lowered = trainer.step_fn.lower(*args)
        out[path] = lowered.compile()
    return out


def test_mamba2_full_width_train_step_fits_v5e(mamba2_steps):
    """The ``mamba2-780m.seq2k`` cell's step: 2 x 2048 tokens at the
    published chunk of 256, full remat, float32 params and AdamW state,
    bf16 compute, tied 50,288-row head, the scan on the Pallas kernels.
    Its compiler account is 14.63 GiB; a third row of 2048 adds about
    1.07e9 bytes and would not fit."""
    total = _total_bytes(mamba2_steps["pallas"].memory_analysis())
    assert 0 < total < V5E_HBM_BYTES, total / 2 ** 30


def _kernel_calls(compiled) -> list[str]:
    """The ``op_name`` of each Pallas call in a compiled program."""
    return [m.group(1) for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_mamba2_step_runs_the_ssd_kernels_within_the_jnp_account(
        mamba2_steps):
    """On the chip the scan is a forward call in the step's forward and,
    under full remat, a forward and a backward call in its backward; the
    jnp path has none.  The kernels' step holds no more HBM than the
    jnp path's."""
    calls = _kernel_calls(mamba2_steps["pallas"])
    assert [c.rsplit("/", 2)[-2] for c in calls] == [
        "ssd_scan_fwd", "ssd_scan_fwd", "ssd_scan_bwd"]
    assert _kernel_calls(mamba2_steps["jnp"]) == []
    pallas, jnp_ = (_total_bytes(mamba2_steps[p].memory_analysis())
                    for p in ("pallas", "jnp"))
    assert pallas <= jnp_, (pallas, jnp_)


def test_mamba2_step_kernels_carry_the_ssd_scope(mamba2_steps, monkeypatch):
    """The forward and backward calls carry ``ssd`` in their ``op_name``,
    and the benchmark's reduction files them under ``ssd``: an unscoped
    backward would leave ``ssd_ms`` short and ``ssd_roofline_pct`` high."""
    monkeypatch.syspath_prepend(str(Path(__file__).parents[1] / "bench"))
    scopes = importlib.import_module("scopes")
    ssm_scopes = importlib.import_module("ssm_scopes")
    calls = _kernel_calls(mamba2_steps["pallas"])
    assert {c.rsplit("/", 2)[-2] for c in calls} == {"ssd_scan_fwd",
                                                     "ssd_scan_bwd"}
    for call in calls:
        assert "/ssd/" in call, call
        assert scopes.scope_of(call, ssm_scopes.SCOPES) == "ssd", call


def test_flash_attention_fwd_bwd_fits_v5e_at_16k(one_chip):
    """The flash path's forward and recompute backward at qwen2-0.5b's
    heads (14 q, 2 KV, head dim 64) over 1 x 16384 tokens: the long-context
    cell's attention, which the 8 x 1024 step above (direct attention)
    never compiles."""
    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, 16384, 14, 64), bf, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 64), bf, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(chunked_attention(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < V5E_HBM_BYTES, temp / 2 ** 30


BF, F32 = jnp.bfloat16, jnp.float32
KERNEL_CASES = {
    # qwen2-0.5b FFN up-projection over 4096 tokens (d=896, d_ff=4864)
    "padded_matmul": (padded_matmul, [((4096, 896), BF), ((896, 4864), BF)],
                      {}),
    "fused_norm": (fused_residual_rmsnorm,
                   [((4096, 896), BF), ((4096, 896), BF), ((896,), BF)], {}),
    # mamba2-780m.seq2k: 2 x 2048 tokens, d_inner 3072 = 48 heads x 64,
    # state 128, the published chunk of 256
    "ssd_scan": (ssd_scan,
                 [((2, 2048, 48, 64), BF), ((2, 2048, 48), F32), ((48,), F32),
                  ((2, 2048, 128), BF), ((2, 2048, 128), BF)],
                 {"chunk": 256}),
    # one ring step over a 16 MiB float32 gradient shard
    "ring_combine": (ring_combine, [((1 << 22,), F32), ((1 << 22,), F32)],
                     {"block": 8192}),
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    op, specs, static = KERNEL_CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = op.__wrapped__.lower(*args, interpret=False,
                                    **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_backward_compiles_for_v5e(one_chip):
    """``jax.grad`` through the SSD scan at the cell's widths: the forward
    that saves the chunk-start states, and the backward kernel."""
    _, specs, static = KERNEL_CASES["ssd_scan"]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]

    def loss(*a):
        y = ssd_scan.__wrapped__(*a, interpret=False, **static)
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 2
