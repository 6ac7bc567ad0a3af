"""ssd_roofline_pct: the SSD scan's share of its roofline, in percent: the
least time the chip could take for the scan's work in one step (the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, ssd_work.py, with
the peaks of peaks.json by device kind) over ``ssd_ms``.  The work is what
the algorithm needs, forward and backward with no recompute.  Nothing
where ``ssd_ms`` reads nothing.  Moves tokens_per_s."""
import harness
import ssd_work
import ssm_scopes

ACT_BYTES = {"bfloat16": 2, "float32": 4}


def read(rec):
    ms = ssm_scopes.read_ms(rec, "ssd")
    if ms is None:
        return None
    cell, t = rec.cell, rec.cell.traffic
    least = ssd_work.least_seconds(
        cell.model, t["batch"], t["seq"], ACT_BYTES[t["compute_dtype"]],
        harness.peak(rec.device["kind"]))
    return 100.0 * least / (ms / 1e3)
