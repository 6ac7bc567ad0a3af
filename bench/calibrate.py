#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload qwen2-0.5b.seq4k \
        --seeds 11,12,13 --controls 3 > readings.jsonl

For each seed, in one process: the program's set-up steps and one window
step through ``harness.run_cell``, then the float32 reference; the gaps
between them are the lower readings.  For the first ``--controls``
seeds also the control (the reference with fp8 contractions put in the
program's place) and the reference with half of each batch left out,
each compared with the same float32 reference: the upper readings.  Each
of these two also goes in the program's place through the harness's own
verdict, ``harness.result``, at the cell's committed limits: its
``correct`` has to read false.  One JSON line per seed.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import run


def verdict(rec, program: dict) -> dict:
    """The harness's verdict on a run with ``program`` in the program's
    place: ``correct`` and the numbers that failed their limits."""
    import harness
    line = harness.result(dataclasses.replace(rec, program=program), False)
    return {"correct": line["correct"],
            "failed": {k: c for k, c in line["checks"].items()
                       if c["value"] > c["limit"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    run.setup_jax()
    import harness
    import reference

    cell = harness.resolve(args.workload)
    device = harness.device_summary()
    if device["platform"] != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rec = harness.run_cell(cell, seed, 0.0, False,
                               out_dir=run.OUT / f"{cell.name}.calibrate",
                               t_start=t0, device=device)
        line = {"workload": cell.name, "seed": seed, "device": device,
                "setup_s": rec.setup_s, "reference_s": rec.reference_s,
                "memory_peak_bytes": rec.memory_peak_bytes,
                "program": reference.gaps(rec.program, rec.reference),
                "losses": rec.program["losses"],
                "reference_losses": rec.reference["losses"]}
        if i < args.controls:
            t1 = time.perf_counter()
            control = harness.follow_reference(rec, mm=reference.fp8_mm)
            line["control_s"] = time.perf_counter() - t1
            line["control"] = reference.gaps(control, rec.reference)
            line["control_verdict"] = verdict(rec, control)
            if cell.traffic["batch"] > 1:
                half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
                        for b in rec.batches]
                half = harness.follow_reference(rec, batches=half)
                line["half_batch"] = reference.gaps(half, rec.reference)
                line["half_batch_verdict"] = verdict(rec, half)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
