"""Training launcher CLI.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 30 --mask-mode naive   # Case-3 regression reproduction
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax

from repro.configs import get_config, get_reduced, list_archs
from repro.optim.adamw import AdamWConfig
from repro.runtime.train import RunConfig, Trainer

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at one fixed directory and
    return it.  JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; only when
    that is unset is the cache pointed at ``<repo>/.jax_cache``.  The path
    is part of the cache key, so it never comes from a temp name, a pid
    or the time.  Call it from an entry point before the first compile,
    never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """The device JAX will run on, as every result should name it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--mask-mode", default="none",
                    choices=["none", "naive", "fast"])
    ap.add_argument("--no-flare", action="store_true")
    ap.add_argument("--flare-log", default=None)
    args = ap.parse_args()

    use_compile_cache()
    print(json.dumps({"device": device_summary()}), flush=True)
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    run = RunConfig(
        model=cfg, global_batch=args.batch, seq_len=args.seq,
        steps=args.steps, peak_lr=args.lr,
        num_microbatches=args.microbatches,
        opt=AdamWConfig(lr=args.lr, state_dtype=args.opt_dtype),
        remat=args.remat, checkpoint_dir=args.checkpoint_dir,
        flare=not args.no_flare, flare_log=args.flare_log,
        mask_mode=args.mask_mode)
    trainer = Trainer(run)
    hist = trainer.train()
    for rec in hist[:: max(len(hist) // 10, 1)]:
        print(json.dumps(rec))
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"({hist[-1]['tokens_per_s']:.0f} tok/s)")


if __name__ == "__main__":
    main()
