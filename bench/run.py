#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload qwen2-0.5b.seq4k --seed 7 --seconds 10 \
        --trace 0

From the root of a checkout.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics), ``device`` and, last, ``checks``: every number
compared with the reference, beside its limit, which also close standard
error.  With no TPU, or fewer chips than the cell asks for, it exits 2
and prints no result.  JAX's compilation cache lives in ``.jax_cache`` of
the checkout; run outputs (spill, trace) under ``.bench_out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """The compilation cache inside the checkout, at a fixed path, with
    every program kept; ``use_compile_cache`` takes it from the
    environment.  libtpu's logs, which go to a fixed path under /tmp by
    default, are off unless ``TPU_LOG_DIR`` says where."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    args = parse(argv)
    setup_jax()
    import harness

    cell = harness.resolve(args.workload)
    device = harness.device_summary()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {device['count']} {device['platform']!r} device(s)",
              file=sys.stderr)
        return 2
    rec = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           out_dir=OUT / f"{args.workload}.trace{args.trace}",
                           t_start=T_START, device=device)
    line = harness.result(rec, bool(args.trace))
    print(json.dumps({"window_compiles": rec.window_compiles,
                      "memory_read_compiles": rec.memory_read_compiles,
                      "window_steps": rec.window.steps,
                      "window_s": rec.window.window_s,
                      "window_step_s": [r["step_time_s"]
                                        for r in rec.window_history],
                      "reference_s": rec.reference_s,
                      "reference_left_out": harness.reference.gaps(
                          rec.program, rec.reference)["left_out"]}),
          flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
