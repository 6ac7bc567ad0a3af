"""The traced rehearsal's result line on the CPU, at the REDUCED qwen2
preset: the program's phase spans and Flare's self time reach the line,
and the scope metrics, which read a device plane the CPU does not write,
are left out of it rather than read as zero."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from test_rehearsal import reduced_cell, rehearse  # noqa: E402


def test_traced_line_carries_host_gap_and_flare_self(tmp_path, monkeypatch):
    rec = rehearse(reduced_cell("qwen2-0.5b"), tmp_path, trace=True)
    # the CPU has no peak in peaks.json; any stands in for step_mfu here
    monkeypatch.setattr(harness, "peak", lambda kind: {
        "bf16_flops_per_s": 1.0})
    line = harness.result(rec, trace=True)
    assert line["correct"], line["checks"]
    metrics = line["metrics"]
    assert metrics["host_gap_ms"]["value"] > 0
    assert metrics["flare_self_ms"]["value"] > 0
    for scoped in ("attention_ms", "mlp_ms", "head_ms", "optimizer_ms"):
        assert scoped not in metrics
