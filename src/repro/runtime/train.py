"""Training runtime: jitted train step + the FLARE-instrumented driver loop.

``make_train_step`` builds the pure step (microbatched grad accumulation,
AdamW with compressed state, LR schedule).  ``Trainer`` is the driver: it
owns the dataloader, attaches the FLARE daemon, emits step/dataloader
events, checkpoints, and exposes fault hooks for the supervisor.

The step's device work carries the named scopes of ``repro.scopes`` in
each HLO op's ``op_name``, and each host phase of ``Trainer.train`` is a
Flare span that is also a profiler annotation (``TracingDaemon.span``):
``dataloader.next_batch``, ``train_step.h2d`` (the batch's copies and the
fault hook), ``train_step.dispatch``, ``train_step.sync`` (the loss
fetch) and ``train_step.record`` (history, step end, checkpoint).  Each
step also adds to the daemon's counters the increments the model reports
for its sequence length (``step_counters``): the flash path's KV blocks,
the SSD scan's pairs and kernel layers.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.core.events import EventKind
from repro.data import DataConfig, ShardedLoader
from repro.models.layers import Policy
from repro.models.registry import build_model, modality_inputs
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import warmup_cosine
from repro.scopes import OPTIMIZER_SCOPE


@dataclass
class RunConfig:
    model: ModelConfig
    global_batch: int = 8
    seq_len: int = 128
    num_microbatches: int = 1
    steps: int = 50
    warmup_steps: int = 20
    peak_lr: float = 3e-4
    remat: str = "none"
    grad_accum_dtype: str = "float32"  # float32 | bfloat16 (microbatching)
    opt: AdamWConfig = field(default_factory=AdamWConfig)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 25
    flare: bool = True
    flare_log: Optional[str] = None
    mask_mode: str = "none"   # none | naive | fast (Case-3)
    data_prefetch: bool = True  # False = synchronous dataloader (Case-3)

    def policy(self) -> Policy:
        return Policy(jnp.dtype(self.param_dtype), jnp.dtype(self.compute_dtype))


_NO_SPAN = contextlib.nullcontext()


def make_train_step(model, cfg: RunConfig, mesh=None):
    """Returns step_fn(params, opt_state, batch, step) -> (p, o, metrics)."""
    opt_cfg = cfg.opt
    M = cfg.num_microbatches

    def _constrain_micro(x):
        # keep the microbatch split sharded over the dp axes (avoids GSPMD
        # "involuntary full rematerialization" on the reshape)
        if mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        spec = P(None, dp, *([None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch,
                               vision_embeds=batch.get("vision_embeds"))
        return loss, aux

    def step_fn(params, opt_state, batch, step):
        lr = warmup_cosine(step, peak_lr=cfg.peak_lr,
                           warmup_steps=cfg.warmup_steps,
                           total_steps=cfg.steps)
        if M <= 1:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            acc_dt = jnp.dtype(cfg.grad_accum_dtype)

            def micro(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dt), gacc, g)
                return (gacc, lacc + l), None

            mbs = jax.tree.map(
                lambda x: _constrain_micro(
                    x.reshape((M, x.shape[0] // M) + x.shape[1:])),
                batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / M, grads)
            loss = loss / M
        with jax.named_scope(OPTIMIZER_SCOPE):
            params, opt_state, om = adamw_update(
                grads, opt_state, params, opt_cfg, lr)
        metrics = {"loss": loss, "lr": lr, **om}
        return params, opt_state, metrics

    return step_fn


class Trainer:
    """FLARE-instrumented training driver with checkpoint/restart support."""

    def __init__(self, cfg: RunConfig, fault_hook: Optional[Callable] = None):
        self.cfg = cfg
        self.model = build_model(cfg.model, policy=cfg.policy(),
                                 remat=cfg.remat)
        self.step_fn = jax.jit(make_train_step(self.model, cfg),
                               donate_argnums=(0, 1))
        self.fault_hook = fault_hook
        self.daemon = None
        self.ckpt = None
        if cfg.checkpoint_dir:
            from repro.checkpoint import CheckpointManager
            self.ckpt = CheckpointManager(cfg.checkpoint_dir)
        self.history: list[dict] = []

    # ------------------------------------------------------------------ #
    def init_state(self):
        rng = jax.random.PRNGKey(self.cfg.seed)
        params = self.model.init(rng)
        opt_state = adamw_init(params, self.cfg.opt)
        return params, opt_state, 0

    def restore_or_init(self):
        params, opt_state, start = self.init_state()
        if self.ckpt and self.ckpt.latest_step() is not None:
            tree = {"params": params, "opt": opt_state}
            restored = self.ckpt.restore(tree)
            params, opt_state = restored["params"], restored["opt"]
            start = self.ckpt.latest_step() + 1
        return params, opt_state, start

    def _loader(self) -> ShardedLoader:
        c = self.cfg
        return ShardedLoader(DataConfig(
            vocab_size=c.model.vocab_size, batch=c.global_batch,
            seq_len=c.seq_len, seed=c.seed, mask_mode=c.mask_mode))

    def _modality_stubs(self) -> dict:
        """Stand-ins for the stubbed modality front ends' embeddings, one
        per input the model takes (``modality_inputs``)."""
        c = self.cfg
        return {name: jnp.ones(shape, jnp.dtype(c.compute_dtype))
                for name, shape in modality_inputs(
                    c.model, c.global_batch).items()}

    # ------------------------------------------------------------------ #
    def train(self, steps: Optional[int] = None) -> list[dict]:
        cfg = self.cfg
        steps = steps if steps is not None else cfg.steps
        if cfg.flare:
            from repro.core.daemon import DaemonConfig, TracingDaemon
            self.daemon = TracingDaemon(DaemonConfig(
                rank=0, backend=f"{cfg.model.family}-train",
                log_path=cfg.flare_log, hang_timeout=300.0))
            self.daemon.attach()
        daemon = self.daemon
        span = daemon.span if daemon else lambda *a, **k: _NO_SPAN
        counts = []   # (counter, per-step increment)
        if daemon:
            counts = [(daemon.telemetry.counter(name), n) for name, n
                      in self.model.step_counters(cfg.seq_len).items()]
        loader = self._loader()
        if cfg.data_prefetch:
            loader.start()
        params, opt_state, start = self.restore_or_init()
        stubs = self._modality_stubs()
        tokens_per_step = cfg.global_batch * cfg.seq_len
        step_flops = 6.0 * cfg.model.active_param_count() * tokens_per_step
        try:
            for step in range(start, steps):
                if daemon:
                    daemon.step_begin(step)
                    daemon.set_stack(["Trainer.train", "next_batch"])
                t0 = time.perf_counter()
                with span(EventKind.DATALOADER, "dataloader.next_batch",
                          tokens=tokens_per_step):
                    batch = loader.next_batch()
                with span(EventKind.PY_API, "train_step.h2d"):
                    if daemon:
                        daemon.set_stack(["Trainer.train", "train_step"])
                    jb = {"tokens": jnp.asarray(batch["tokens"]),
                          "labels": jnp.asarray(batch["labels"]), **stubs}
                    if self.fault_hook:
                        self.fault_hook(step)
                with span(EventKind.PY_API, "train_step.dispatch") as dispatch:
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, jb, jnp.int32(step))
                with span(EventKind.PY_API, "train_step.sync") as sync:
                    loss = float(metrics["loss"])
                with span(EventKind.PY_API, "train_step.record"):
                    if daemon:
                        # whole-step device occupancy (the jitted step is
                        # one fused XLA program on this backend)
                        daemon.record_span(
                            EventKind.KERNEL_COMPUTE, "train_step_exec",
                            dispatch.t0, sync.t1, flops=step_flops)
                        daemon.step_end(tokens=tokens_per_step, loss=loss)
                        for c, n in counts:
                            c.inc(n)
                    rec = {"step": step, "loss": loss,
                           "lr": float(metrics["lr"]),
                           "grad_norm": float(metrics["grad_norm"]),
                           "step_time_s": time.perf_counter() - t0,
                           "tokens_per_s": tokens_per_step
                           / max(time.perf_counter() - t0, 1e-9)}
                    self.history.append(rec)
                    if self.ckpt and (step + 1) % cfg.checkpoint_every == 0:
                        self.ckpt.save(step,
                                       {"params": params, "opt": opt_state},
                                       {"loss": loss})
        finally:
            loader.stop()
            if daemon:
                daemon.detach()
        self.final_state = (params, opt_state)
        return self.history
