#!/usr/bin/env python3
"""Chip smoke test: the traced trainer at full width on one TPU.

Trains qwen2-0.5b at its published widths (random weights from a seed)
through ``RunConfig``/``Trainer`` — the objects ``python -m
repro.launch.train`` builds — for a few steps with Flare attached.  The
daemon spills FCS into ``chiprun_out/chip_smoke/``.  The step-0 loss is
checked against a float32 reference of the same parameters and batch, and
the spill is replayed through the diagnosis plane in this process.

    python3 chip_smoke.py

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
With no TPU it exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import contextlib
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

ARCH = "qwen2-0.5b"
# 8 x 1024 tokens with full remat: about 10.4 GB of the v5e's 15.75 GB
# by the compiler's count; without remat even batch 4 does not fit.
BATCH, SEQ, STEPS, REMAT = 8, 1024, 6, "full"
# Gap allowed between the bf16 step-0 loss and the float32 reference,
# relative to the reference.  bf16 keeps 8 significant bits (unit
# roundoff 2**-9, about 2e-3).  The random init's tied embedding (std 1)
# puts the step-0 loss in the hundreds, not near ln(vocab), so an absolute
# bound would scale with the init, not the arithmetic.  At full width the
# CPU backend gave gaps of 0.5e-4 to 3e-4 of the loss with depth cut to
# 1-4 layers, and all 24 layers on a TPU v5e gave 3.4e-4: one bf16
# rounding of the loss leaves about 6x room.
LOSS_RTOL = 2e-3

# JAX's own monitoring events.  A backend compile event spans one XLA
# compile or one read of it from the persistent cache, never another such
# event, so their sum is the compile time kept apart from step time.  The
# retrieval event marks a cache hit.  (JAX's "compile_time_saved" event is
# time not spent, and tracing events nest, so neither is summed.)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def compile_events():
    """Collect JAX's (event, seconds) for compiles and cache hits made
    while the body runs."""
    import jax

    events: list[tuple[str, float]] = []

    def on_duration(event: str, duration: float, **_):
        if event in (_BACKEND_COMPILE, _CACHE_HIT):
            events.append((event, duration))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _compile_summary(events) -> dict:
    hits = sum(e == _CACHE_HIT for e, _ in events)
    return {"compile_s": sum(d for e, d in events if e == _BACKEND_COMPILE),
            "xla_compiles": sum(e == _BACKEND_COMPILE for e, _ in events)
            - hits,
            "cache_hits": hits}


def _f32_reference_loss(model_cfg, params, batch) -> float:
    """Mean step-0 loss in float32 at highest matmul precision, through
    plain (direct) attention, one sequence at a time so the float32
    logits of a 152k vocabulary stay one row's worth."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import Policy
    from repro.models.registry import build_model

    ref = build_model(model_cfg, policy=Policy(jnp.float32, jnp.float32),
                      attn_impl="direct")

    def mean_loss(p, tokens, labels):
        def row(tl):
            return ref.loss(p, {"tokens": tl[0][None],
                                "labels": tl[1][None]})[0]
        return jnp.mean(jax.lax.map(row, (tokens, labels)))

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(mean_loss)(params, jnp.asarray(batch["tokens"]),
                                        jnp.asarray(batch["labels"])))


def run_smoke(model_cfg, *, batch: int, seq: int, steps: int, remat: str,
              out_dir: Path) -> dict:
    """Train, check against the reference, diagnose the spill.  Raises on
    any failed check; returns what was measured."""
    import jax

    from repro.fleet import FleetConfig, FleetMultiplexer, FleetReplayer
    from repro.runtime.train import RunConfig, Trainer

    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    run = RunConfig(model=model_cfg, global_batch=batch, seq_len=seq,
                    steps=steps, remat=remat, flare=True,
                    flare_log=str(out_dir / f"{model_cfg.name}.fcs"))
    trainer = Trainer(run)
    emit("config", arch=model_cfg.name, layers=model_cfg.num_layers,
         d_model=model_cfg.d_model, vocab=model_cfg.vocab_size,
         params=model_cfg.param_count(), batch=batch, seq=seq, steps=steps,
         remat=remat, param_dtype=run.param_dtype,
         compute_dtype=run.compute_dtype)

    # ---- float32 reference: the parameters and batch of step 0 ---------- #
    t0 = time.perf_counter()
    with compile_events() as events:
        params = trainer.model.init(jax.random.PRNGKey(run.seed))
        ref_loss = _f32_reference_loss(model_cfg, params,
                                       trainer._loader().next_batch())
        del params
    emit("reference", f32_loss=ref_loss, seconds=time.perf_counter() - t0,
         **_compile_summary(events))

    # ---- train: compile time kept apart from step time ------------------ #
    t0 = time.perf_counter()
    with compile_events() as events:
        hist = trainer.train()
    train_s = time.perf_counter() - t0
    losses = [r["loss"] for r in hist]
    grad_norms = [r["grad_norm"] for r in hist]
    step_s = [r["step_time_s"] for r in hist]
    check(len(hist) == steps, f"{len(hist)} of {steps} steps ran")
    check(all(map(math.isfinite, losses + grad_norms)),
          f"non-finite loss or grad norm: {losses} {grad_norms}")
    median_s = statistics.median(step_s[1:]) if steps > 1 else float("nan")
    emit("train", losses=losses, grad_norms=grad_norms, step_s=step_s,
         step0_s=step_s[0], median_step_s_after_0=median_s,
         train_s=train_s, tokens_per_step=batch * seq,
         **_compile_summary(events))

    gap = losses[0] - ref_loss
    emit("loss_check", bf16_step0_loss=losses[0], f32_loss=ref_loss, gap=gap,
         rel_gap=abs(gap) / abs(ref_loss), rtol=LOSS_RTOL)
    check(abs(gap) <= LOSS_RTOL * abs(ref_loss),
          f"bf16 step-0 loss {losses[0]} vs float32 {ref_loss}: "
          f"gap {gap} beyond {LOSS_RTOL} of the loss")

    stats = jax.devices()[0].memory_stats() or {}
    emit("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))

    # ---- diagnose the spill, inline (this process holds the chip) ------- #
    daemon = trainer.daemon
    emit("flare", events=daemon.events_emitted, bytes=daemon.bytes_logged,
         spill_errors=daemon.spill_errors, sink_errors=daemon.sink_errors,
         files=[Path(p).name for p in daemon.log_paths])
    check(daemon.events_emitted > 0, "the daemon emitted no events")
    check(daemon.spill_errors == 0, f"{daemon.spill_errors} spill errors")
    check(daemon.sink_errors == 0, f"{daemon.sink_errors} sink errors")
    mux = FleetMultiplexer(FleetConfig(backend=f"{model_cfg.family}-train"))
    replay = FleetReplayer(mux, job_workers=1).replay_dir(str(out_dir))
    anomalies = mux.finalize()
    emit("diagnosis", replayed_events=replay.events, files=replay.files,
         corrupt_files=replay.corrupt_files, anomalies=len(anomalies),
         found=[str(a) for a in anomalies])
    check(replay.corrupt_files == 0,
          f"{replay.corrupt_files} corrupt spill files")
    check(replay.events == daemon.events_emitted,
          f"replayed {replay.events} of {daemon.events_emitted} events")
    return {"losses": losses, "f32_loss": ref_loss, "gap": gap,
            "anomalies": anomalies, "replay": replay}


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.launch.train import device_summary, use_compile_cache

    device = device_summary()
    emit("device", compile_cache=use_compile_cache(), **device)
    run_smoke(get_config(ARCH), batch=BATCH, seq=SEQ, steps=STEPS,
              remat=REMAT, out_dir=OUT_DIR)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
