"""step_mfu: model FLOPs of the window's steps over the window's seconds
and the chip's bf16 peak, in percent.  The FLOPs come from the
configuration's ``model_flops`` (forward and backward, no recompute); the
peak from ``peaks.json`` by device kind, where an unknown kind is an
error.  Moves tokens_per_s."""
import harness


def read(rec):
    cell, t = rec.cell, rec.cell.traffic
    flops = cell.module.model_flops(cell.model, t["batch"], t["seq"])
    chips = cell.chips
    peak = harness.peak(rec.device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * rec.window.steps / rec.window.window_s \
        / (peak * chips)
