"""The readers of the Mamba2 cell's per-layer metrics, on the CPU: the
scope reduction with ``ssm`` and ``ssd`` beside the step's other scopes,
the SSD scan's FLOPs and bytes by hand, its roofline share, and the pair
count against what the trainer's ``ssd.pairs_kept`` counter adds.  Loads
no TPU library."""
from __future__ import annotations

import dataclasses
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import scopes  # noqa: E402
import ssd_work  # noqa: E402
import ssm_scopes  # noqa: E402

CELL = "mamba2-780m.seq2k"
NEW = ("ssd_ms", "ssm_proj_ms", "ssd_roofline_pct")


@pytest.mark.parametrize("path,scope", [
    ("jit(step_fn)/jvp()/while/body/closed_call/ssm/ssd/closed_call/exp",
     "ssd"),
    ("jit(step_fn)/transpose(jvp(ssm))/transpose(jvp(ssd))/dot_general",
     "ssd"),
    ("jit(step_fn)/jvp()/while/body/closed_call/ssm/dot_general", "ssm"),
    ("jit(step_fn)/transpose(jvp(head))/mul", "head"),
    ("jit(step_fn)/while/body/dynamic_slice", None),
])
def test_the_scan_counts_to_ssd_and_the_rest_of_the_block_to_ssm(path,
                                                                  scope):
    assert ssm_scopes.SCOPES == scopes.SCOPES + ("ssm", "ssd")
    assert scopes.scope_of(path, ssm_scopes.SCOPES) == scope


def test_ssm_reduction_takes_self_time_per_profiled_step():
    ms = 1_000_000
    body = "jit(step_fn)/jvp()/while/body/closed_call"
    ops = {"/device:TPU:0": [
        ("while.1", "jit(step_fn)/jvp()/while", 0, 70 * ms),
        ("fusion.2", f"{body}/ssm/dot_general", 0, 20 * ms),
        ("fusion.3", f"{body}/ssm/ssd/exp", 20 * ms, 50 * ms),
        ("fusion.4", "jit(step_fn)/jvp(embed)/gather", 60 * ms, 65 * ms),
        ("fusion.5", "jit(step_fn)/transpose(jvp(head))/mul", 70 * ms,
         80 * ms),
        ("fusion.6", "jit(step_fn)/optimizer/mul", 80 * ms, 90 * ms),
        ("copy.7", "", 90 * ms, 100 * ms)]}
    r = ssm_scopes.per_step(ops, 0, 100 * ms, 2)
    assert r == pytest.approx({
        "ssm": 0.010, "ssd": 0.015, "embed": 0.0025, "head": 0.005,
        "optimizer": 0.005, None: (15 + 10) / 2e3})
    # the step's five scopes alone put the block's time under no scope
    assert scopes.per_step(ops, 0, 100 * ms, 2)[None] \
        == pytest.approx(r[None] + r["ssm"] + r["ssd"])


TINY = {"num_layers": 1, "d_model": 4, "ssm_expand": 2, "ssm_state": 2,
        "ssm_head_dim": 4, "ssm_chunk": 3}


def test_ssd_flops_and_bytes_by_hand():
    B, S = 2, 6
    # d_inner 8, 2 heads; chunk 3 over 6: 2 chunks of 3 x 4 / 2 causal pairs
    assert ssd_work.causal_pairs(S, 3) == 12
    # per row and pass: C.B^T over N=2 and the sum of x over d_inner 8 for
    # each pair, the chunk states and the read-out (2 x 8 x 2 each per
    # position), the recurrence 2 x 8 x 2 per chunk
    forward = 2 * (2 + 8) * 12 + 2 * 2 * 8 * 2 * 6 + 2 * 8 * 2 * 2
    assert ssd_work.flops(TINY, B, S) == 3 * forward * B
    # x, y over 8 and B, C over 2 in bf16, dt over 2 heads and the two
    # chunk states of 8 x 2 in float32
    per_pass = 2 * 6 * (8 + 8 + 2 + 2) + 4 * 6 * 2 + 4 * 2 * 8 * 2
    assert ssd_work.bytes_moved(TINY, B, S, 2) == 3 * per_pass * B
    peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert ssd_work.least_seconds(TINY, B, S, 2, peak) == pytest.approx(
        max(3 * forward * B, 3 * per_pass * B) / 1e3)


def test_the_cells_scan_is_bound_by_memory():
    cell = harness.resolve(CELL)
    t, peak = cell.traffic, harness.peak("TPU v5 lite")
    flops = ssd_work.flops(cell.model, t["batch"], t["seq"])
    moved = ssd_work.bytes_moved(cell.model, t["batch"], t["seq"], 2)
    assert moved / peak["hbm_bytes_per_s"] > flops / peak["bf16_flops_per_s"]
    kept = ssd_work.causal_pairs(2048, 256)
    assert kept / (8 * 256 * 256) == pytest.approx(0.502, abs=5e-4)


def _rec(trace=True):
    cell = harness.resolve(CELL)
    return types.SimpleNamespace(
        cell=cell, trace={} if trace else None,
        device={"kind": "TPU v5 lite"}, spill_dir=Path("/nonexistent"))


def test_roofline_share_is_the_least_time_over_ssd_ms(monkeypatch):
    rec = _rec()
    monkeypatch.setattr(ssm_scopes, "read_ms",
                        lambda r, scope: {"ssd": 200.0}.get(scope))
    t = rec.cell.traffic
    least = ssd_work.least_seconds(rec.cell.model, t["batch"], t["seq"], 2,
                                   harness.peak("TPU v5 lite"))
    read = harness.metric_reader("ssd_roofline_pct").read
    assert read(rec) == pytest.approx(100 * least / 0.2)
    assert 0 < read(rec) < 100
    assert harness.metric_reader("ssd_ms").read(rec) == 200.0
    assert harness.metric_reader("ssm_proj_ms").read(rec) is None


def test_readers_read_nothing_without_a_trace():
    rec = _rec(trace=False)
    for name in NEW:
        assert harness.metric_reader(name).read(rec) is None


def test_the_new_metrics_belong_to_the_new_cell_alone():
    assert [m["name"] for m in harness.resolve(CELL).per_layer][-3:] \
        == list(NEW)
    for other in ("qwen2-0.5b.seq4k", "qwen2-0.5b.seq16k"):
        names = {m["name"] for m in harness.resolve(other).per_layer}
        assert not names & set(NEW)


def test_reader_pairs_equal_what_the_counter_adds_each_step():
    from repro.configs import get_reduced
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train import RunConfig, Trainer

    for seq, chunk in ((64, 16), (96, 64)):
        model = dataclasses.replace(get_reduced("mamba2-780m"),
                                    ssm_chunk=chunk)
        trainer = Trainer(RunConfig(
            model=model, global_batch=1, seq_len=seq, steps=2,
            warmup_steps=1, opt=AdamWConfig(lr=1e-3), flare=True))
        trainer.train()
        counters = trainer.daemon.telemetry.snapshot()["counters"]
        assert counters["ssd.pairs_kept"] == 2 * ssd_work.causal_pairs(
            seq, chunk)
