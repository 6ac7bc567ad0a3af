"""The benchmark's own arithmetic, on the CPU: trace reduction, model
FLOPs, window arithmetic, the peak table, the numbers compared, and that
every cell resolves to its files by name.  Loads no TPU library."""
from __future__ import annotations

import json
import re
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ------------------------------------------------------------------ trace
def test_trace_reduction_busy_idle_top_ops_and_gaps():
    ms = 1_000_000
    ops = {"/device:TPU:0": [
        ("fusion.1", 0, 40 * ms), ("fusion.2", 30 * ms, 50 * ms),  # overlap
        ("convolution.3", 70 * ms, 90 * ms),
        ("fusion.1", 95 * ms, 130 * ms)]}  # runs past the window's end
    host = [(devtrace.BOUNDARY, 0, 1), ("PjitFunction(step_fn)", 48 * ms,
                                        72 * ms),
            ("TransferToDevice", 50 * ms, 71 * ms),   # shorter, same cover
            ("python_loop", 0, 200 * ms),              # covers everything
            (devtrace.BOUNDARY, 100 * ms, 100 * ms + 1)]
    t0, t1 = devtrace.window_of(host)
    assert (t0, t1) == (0, 100 * ms)
    r = devtrace.reduce(ops, host, t0, t1)
    # busy: [0,50] + [70,90] + [95,100] = 75 ms of 100
    assert r["busy_s"] == pytest.approx(0.075)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_pct"] == pytest.approx(25.0)
    # per-op self time inside the window: the time fusion.2 shares with
    # fusion.1 counts to fusion.2 alone
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.035, "fusion.2": 0.020, "convolution.3": 0.020})
    # gaps [50,70], [90,95]: the first is named by the shortest host
    # event that covers it as well as any other does
    assert r["idle_gaps"][0] == ["TransferToDevice", pytest.approx(0.020)]
    assert r["idle_gaps"][1] == ["python_loop", pytest.approx(0.005)]
    assert len(r["idle_gaps"]) == 2


def test_trace_reduction_averages_devices_and_finds_nothing_to_read():
    ops = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": [("a", 0, 5)]}
    r = devtrace.reduce(ops, [], 0, 10)
    assert r["busy_s"] == pytest.approx(7.5e-9)
    assert r["idle_pct"] == pytest.approx(25.0)
    assert devtrace.reduce({}, [], 0, 10) is None
    assert devtrace.window_of([(devtrace.BOUNDARY, 5, 6)]) == (0, 0)


def test_device_ops_rank_by_self_time_under_short_names():
    ops = {"/device:TPU:0": [
        ("%while.1 = (s32[]) while(...)", 0, 100),
        ("%fusion.2 = f32[8] fusion(...)", 10, 40),
        ("%fusion.2 = f32[8] fusion(...)", 50, 90),
        ("%copy.3 = f32[8] copy(...)", 60, 70)]}   # inside the second fusion
    r = devtrace.reduce(ops, [], 0, 100)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.2": 60e-9, "while.1": 30e-9, "copy.3": 10e-9})
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"])


def test_union_and_gaps():
    merged = devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert devtrace.gaps_of(merged, 0, 10) == [(3, 5), (8, 10)]


# ------------------------------------------------------------------ FLOPs
def _module(config):
    return harness.load_module(BENCH / "configs" / f"{config}.py",
                               "flops_" + config.replace("-", "_")
                               .replace(".", "_"))


def test_qwen2_flops_by_hand():
    cfg = {"num_layers": 2, "d_model": 4, "num_heads": 2, "num_kv_heads": 1,
           "d_ff": 8, "vocab_size": 10}
    B, S = 3, 5
    # per token, forward: q 4x4, k 4x2, v 4x2, o 4x4, gate/up/down 3x(4x8)
    # is 2*(16+8+8+16+96) = 288 per layer; the head 2*4*10 = 80
    # attention: QK and PV, 2 heads x hd 2, over (5+1)/2 = 3 keys:
    # 2*2*2*2*3 = 48 per layer
    forward_per_token = 2 * (288 + 48) + 80
    assert _module("qwen2-0.5b").model_flops(cfg, B, S) == \
        3 * forward_per_token * B * S


def test_mamba2_flops_by_hand():
    cfg = {"num_layers": 1, "d_model": 4, "ssm_expand": 2, "ssm_state": 2,
           "ssm_head_dim": 4, "conv_width": 4, "ssm_chunk": 3,
           "vocab_size": 10}
    B, S = 2, 6
    # d_inner 8, 2 heads; in_proj 4 -> 2*8+2*2+2 = 22 (2*88), out 8->4 (2*32)
    proj = 2 * 4 * 22 + 2 * 8 * 4
    conv = 2 * 4 * (8 + 2 * 2)
    # chunk 3: C.B^T over N=2 and y over d_inner 8, each over (3+1)/2 = 2
    # positions, and per step the state update and read-out, 2*2*8*2 each
    ssd = 2 * 2 * 2 + 2 * 8 * 2 + 2 * 2 * 8 * 2
    forward_per_token = proj + conv + ssd + 2 * 4 * 10
    assert _module("mamba2-780m").model_flops(cfg, B, S) == \
        3 * forward_per_token * B * S


def test_published_model_flops_per_step():
    """The cells' step FLOPs agree with 6*N*T plus causal attention."""
    q = harness.resolve("qwen2-0.5b.seq4k")
    six_n_t = 6 * 494_032_768 * 16384
    attention = 6 * 4096 * 896 * 24 * 16384 * (4097 / 4096)
    norms_and_biases = 6 * 16384 * (24 * (2 * 896 + 1152) + 896)
    assert q.module.model_flops(q.model, 4, 4096) == pytest.approx(
        six_n_t + attention - norms_and_biases, rel=1e-9)


# ----------------------------------------------------------------- window
def test_window_takes_all_tokens_over_all_window_time(monkeypatch):
    warm = harness.WARM_STEPS
    clock = iter([float(i) for i in range(warm)] + [11.0, 15.5, 16.0])
    monkeypatch.setattr(harness.time, "perf_counter", lambda: next(clock))
    w = harness.Window(seconds=5.0, trace_dir=None)
    for step in range(warm):                     # set-up
        w(step)
    assert w.t0 is None
    w(warm)                                      # t = 11: window opens
    w(warm + 1)                                  # t = 15.5: 4.5 s, still open
    with pytest.raises(harness.WindowClosed):
        w(warm + 2)                              # t = 16: first past 5 s
    assert (w.first, w.end, w.steps) == (warm, warm + 2, 2)
    assert w.window_s == 5.0
    rec = harness.Record.__new__(harness.Record)
    rec.cell = harness.resolve("qwen2-0.5b.seq4k")
    rec.window = w
    rec.history = [{"step": s, "step_time_s": t, "loss": 1.0,
                    "grad_norm": 1.0}
                   for s, t in [(warm - 1, 9.0), (warm, 2.0), (warm + 1, 3.0),
                                (warm + 2, 1.0)]]
    assert rec.tokens_per_s == 2 * 4 * 4096 / 5.0
    assert rec.traced_step_s == 2.5   # the window's two steps only


def test_probe_puts_the_programs_step_call_back():
    """The window runs the trainer's own step call, not the probe's."""
    calls = []

    def program(*args):
        calls.append(args)
        return args[:2] + ({},)
    trainer = types.SimpleNamespace(step_fn=program)
    probe = harness.Probe.__new__(harness.Probe)
    probe.step = 0
    probe.record = lambda *a: None
    probe.wrap(trainer)
    for i in range(harness.CHECKED_STEPS + 1):
        assert trainer.step_fn is not program
        trainer.step_fn(np.zeros(3, np.float32), {}, {}, np.int32(i))
    assert trainer.step_fn is program
    assert len(calls) == probe.step == harness.CHECKED_STEPS + 1
    assert [s.shape for s in probe.shapes[:1]] == [(3,)]


# ------------------------------------------------------------------ peaks
def test_unknown_device_kind_is_refused():
    assert harness.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError, match="no peaks"):
            harness.peak(kind)


def test_step_mfu_refuses_an_unknown_device():
    rec = harness.Record.__new__(harness.Record)
    rec.cell = harness.resolve("qwen2-0.5b.seq4k")
    rec.device = {"kind": "cpu", "count": 1}
    with pytest.raises(KeyError):
        harness.metric_reader("step_mfu").read(rec)


# -------------------------------------------------------------- compared
def test_gaps_take_the_worst_step_and_leaf():
    ref = {"losses": [10.0, 9.0, 8.0],
           "first_grad": {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-4},
           "change": {"a": 1.0, "b": 1.0, "c": 3.0, "tiny": 5.0}}
    prog = {"losses": [10.1, 9.0, 7.6],
            "first_grad": {"a": 1.5, "b": 2.0, "c": 4.2, "tiny": 0.0},
            "change": {"a": 1.0, "b": 1.2, "c": 3.0, "tiny": 0.0}}
    g = reference.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.05)            # step 2
    # median leaf gradient 1.5: "a" reads 0.5 / 1.5
    assert g["grad_gap"] == pytest.approx(0.5 / 1.5)
    # "tiny" is under a thousandth of the median: left out of the change
    assert g["left_out"] == ["tiny"]
    assert g["update_gap"] == pytest.approx(0.2)           # "b"


def test_a_non_finite_reading_is_an_infinite_gap():
    ref = {"losses": [10.0, 9.0], "first_grad": {"a": 1.0, "b": 2.0},
           "change": {"a": 1.0, "b": 1.0}}
    prog = {"losses": [10.0, float("nan")],
            "first_grad": {"a": float("nan"), "b": 2.0},
            "change": {"a": 1.0, "b": float("inf")}}
    g = reference.gaps(prog, ref)
    assert g["loss_gap"] == g["grad_gap"] == g["update_gap"] == float("inf")


def test_fp8_control_rounds_operands_and_cotangents():
    import jax
    import jax.numpy as jnp
    x = jnp.linspace(-3.0, 3.0, 97)
    q = reference._q8(x)
    rel = jnp.abs(q - x) / jnp.maximum(jnp.abs(x), 3.0 / 448 * 2 ** -6)
    assert float(jnp.max(rel)) <= 2 ** -4 + 1e-6     # e4m3: 3 mantissa bits
    assert float(jnp.max(jnp.abs(q - x))) > 0
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    out = reference.fp8_mm("ij,jk->ik", a, b)
    exact = reference.exact_mm("ij,jk->ik", a, b)
    err = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < err < 0.2
    ga = jax.grad(lambda a: jnp.sum(reference.fp8_mm("ij,jk->ik", a, b)))(a)
    ge = jax.grad(lambda a: jnp.sum(reference.exact_mm("ij,jk->ik", a, b)))(a)
    assert float(jnp.max(jnp.abs(ga - ge) / jnp.max(jnp.abs(ge)))) < 0.2


def test_learning_rate_warms_up_from_zero():
    opt = {"peak_lr": 3e-4, "warmup_steps": 20}
    assert reference.learning_rate(0, opt, 100) == 0.0
    assert reference.learning_rate(1, opt, 100) == pytest.approx(1.5e-5)
    assert reference.learning_rate(20, opt, 100) == pytest.approx(3e-4)
    assert reference.learning_rate(100, opt, 100) == pytest.approx(3e-5)


# ----------------------------------------------------------------- specs
@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files_by_name(workload):
    cell = harness.resolve(workload)
    assert cell.model["name"] == cell.config["name"]
    for fn in ("init", "loss", "model_flops"):
        assert callable(getattr(cell.module, fn))
    assert set(cell.limits) <= {"loss_gap", "grad_gap", "update_gap"}
    assert {"batch", "seq", "remat", "optimizer"} <= set(cell.traffic)
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s"} <= names
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in names


def test_benchmark_json_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert NAME.match(c["name"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_a_new_cell_needs_data_files_alone(tmp_path):
    """A cell added as a BENCHMARK.json entry, a traffic file and a cell
    file resolves with no code changed."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({
        "name": "qwen2-0.5b.seq8k", "config": "qwen2-0.5b",
        "traffic": "b2_s8192", "chips": 1, "why": "a later cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    traffic = json.loads((BENCH / "traffic" / "b4_s4096.json").read_text())
    (tmp_path / "bench" / "traffic" / "b2_s8192.json").write_text(
        json.dumps(dict(traffic, batch=2, seq=8192)))
    (tmp_path / "bench" / "cells" / "qwen2-0.5b.seq8k.json").write_text(
        json.dumps({"limits": {"loss_gap": 1e-3}}))
    cell = harness.resolve("qwen2-0.5b.seq8k", root=tmp_path)
    assert cell.tokens_per_step == 16384
    assert cell.limits == {"loss_gap": 1e-3}
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve("qwen2-0.5b.seq99k", root=tmp_path)
