"""A rehearsal of the benchmark on the CPU, at the repo's REDUCED presets:
the window, the spill read-back and the correctness check, through the
functions that ``bench/run.py`` calls.  It prints no device metric; a
rehearsal is not a chip run.  It also plants each fault a one-chip
training cell can have under the trainer's step and sees ``correct``
come out false, and runs ``bench/run.py`` where it must refuse."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SEED = 2**31 + 77  # seeds run past 32 signed bits
ARCHS = ("qwen2-0.5b", "mamba2-780m")
# At the REDUCED presets a leaf holds hundreds of elements, not millions,
# so the gaps of per-leaf norms read several times their full-size values
# (up to 1.4e-2 here, against 1e-3 on the chip): the rehearsal holds the
# program, the planted faults and the control to these wider limits.  On
# the CPU over six seeds, qwen2's program reads a loss gap of 8e-6 to
# 4e-5 and its fp8 control 7e-5 to 3.3e-4 (1.9e-4 at ``SEED``); mamba2's
# program reads up to 2.4e-4.
REHEARSAL_LIMITS = {
    "qwen2-0.5b": {"loss_gap": 1e-4, "grad_gap": 3e-2, "update_gap": 3e-2},
    "mamba2-780m": {"loss_gap": 1e-3, "grad_gap": 3e-2, "update_gap": 3e-2},
}


def reduced_cell(arch: str) -> harness.Cell:
    """``qwen2-0.5b.seq4k`` as committed (traffic settings, metrics) with
    ``arch``'s configuration file and reference, the repo's REDUCED preset
    for its model, a batch of 2 x 64 and the rehearsal's limits."""
    from repro.configs import get_reduced

    cell = harness.resolve("qwen2-0.5b.seq4k")
    conf = BENCH / "configs" / f"{arch}.json"
    model = dataclasses.asdict(get_reduced(arch))
    return dataclasses.replace(
        cell, name=f"{arch}.reduced",
        config=dict(harness.load_json(conf), model=model),
        module=harness.load_module(conf.with_suffix(".py"),
                                   "rehearsal_" + arch.replace("-", "_")
                                   .replace(".", "_")),
        traffic=dict(cell.traffic, batch=2, seq=64),
        limits=REHEARSAL_LIMITS[arch])


def rehearse(cell, tmp_path, trace=False, seed=SEED):
    return harness.run_cell(cell, seed, 0.3, trace, out_dir=tmp_path / "out",
                            t_start=time.perf_counter(),
                            device=harness.device_summary())


@pytest.mark.parametrize("arch", ARCHS)
def test_rehearsal_is_correct_at_reduced_size(arch, tmp_path):
    rec = rehearse(reduced_cell(arch), tmp_path)
    line = harness.result(rec, trace=False)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) >= {"loss_gap", "grad_gap", "update_gap"}
    assert line["attempted"] == rec.window.steps >= 1
    assert rec.window_compiles == 0
    assert rec.replay["events"] == rec.daemon["events"] > 0
    assert len(rec.batches) == harness.CHECKED_STEPS
    assert list(line)[-1] == "checks"
    # the compiled step's own account, read with no compile, is the peak
    # where the allocator's leaves the temporaries out
    assert rec.memory_read_compiles == 0 and rec.step_program_bytes > 0
    assert line["device"]["memory_peak_bytes"] == max(
        rec.memory_peak_bytes, rec.step_program_bytes)


def test_traced_rehearsal_reads_spans_and_overhead(tmp_path):
    rec = rehearse(reduced_cell("qwen2-0.5b"), tmp_path, trace=True)
    assert harness.result(rec, trace=False)["correct"]
    assert harness.metric_reader("loader_wait_ms").read(rec) >= 0
    assert harness.metric_reader("compile_s").read(rec) > 0
    assert rec.untraced_step_s > 0
    assert harness.metric_reader("flare_overhead_pct").read(rec) is not None
    # the CPU backend writes no device plane: nothing to read, no zero
    assert harness.metric_reader("device_idle_pct").read(rec) is None
    assert list((tmp_path / "out" / "trace").rglob("*.xplane.pb"))


def test_fp8_control_reads_apart_from_the_program(tmp_path):
    """The control (bench/calibrate.py) at reduced size: the reference with
    fp8 contractions, put in the program's place, reads several times the
    bf16 program's loss gap, and the harness's own verdict on it is not
    correct."""
    import calibrate
    import reference

    rec = rehearse(reduced_cell("qwen2-0.5b"), tmp_path)
    control = harness.follow_reference(rec, mm=reference.fp8_mm)
    program = reference.gaps(rec.program, rec.reference)
    assert reference.gaps(control, rec.reference)["loss_gap"] \
        > 3 * program["loss_gap"]
    assert calibrate.verdict(rec, rec.program)["correct"]
    verdict = calibrate.verdict(rec, control)
    assert not verdict["correct"] and verdict["failed"], verdict


# ---------------------------------------------------------------- faults
def _unchanged(step):
    def fn(params, opt_state, batch, i):
        _, _, metrics = step(params, opt_state, batch, i)
        return params, opt_state, metrics
    return fn


def _half_batch(step):
    def fn(params, opt_state, batch, i):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt_state, half, i)
    return fn


def _answer_altered(step):
    def fn(params, opt_state, batch, i):
        new, opt_state, metrics = step(params, opt_state, batch, i)
        old = params["embed"]["embedding"]
        moved = 2 * new["embed"]["embedding"] - old   # the update doubled
        return dict(new, embed={"embedding": moved}), opt_state, metrics
    return fn


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_planted_fault_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.runtime import train

    real = train.make_train_step
    monkeypatch.setattr(train, "make_train_step",
                        lambda *a, **k: fault(real(*a, **k)))
    rec = rehearse(reduced_cell("qwen2-0.5b"), tmp_path)
    line = harness.result(rec, trace=False)
    assert not line["correct"], line["checks"]


# --------------------------------------------------------------- refusal
def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.seq4k",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_run_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert not json.dumps(proc.stdout).count('"correct"')
