"""The chip smoke script: its phases at a reduced size on the CPU, its
refusal to run anywhere but on a TPU, and the compile-cache path that it
and the training launcher share."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_reduced
from repro.launch import train as launch_train

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_on_reduced_config(smoke, tmp_path, capsys):
    out = smoke.run_smoke(get_reduced("qwen2-0.5b"), batch=2, seq=32,
                          steps=3, remat="full", out_dir=tmp_path / "spill")
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = [line["phase"] for line in lines]
    assert phases == ["config", "reference", "train", "loss_check",
                      "memory", "flare", "diagnosis"]
    assert abs(out["gap"]) <= smoke.LOSS_RTOL * abs(out["f32_loss"])
    assert out["replay"].events > 0 and out["replay"].corrupt_files == 0
    assert list((tmp_path / "spill").glob("*.fcs"))


def test_smoke_check_raises_on_failure(smoke):
    with pytest.raises(RuntimeError, match="chip smoke failed: gap"):
        smoke.check(False, "gap")


def test_smoke_refuses_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_path_is_fixed(monkeypatch, tmp_path, env_dir):
    import jax
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        expected = str(SCRIPT.parent / ".jax_cache")
    else:
        expected = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", expected)
    try:
        assert launch_train.use_compile_cache() == expected
        # JAX reads the variable itself; the helper sets no other path
        assert jax.config.jax_compilation_cache_dir == (
            expected if env_dir is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
