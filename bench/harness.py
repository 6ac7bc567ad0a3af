"""Drive one training cell of the benchmark through the program's trainer.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<name>.json``, with its reference ``configs/<name>.py`` beside
it) under a traffic mix (``traffic/<name>.json``), with the limits of its
correctness check in ``cells/<cell>.json``.  Per-layer metrics are read by
``metrics/<metric>.py``.  All of these are found by name.

``run_cell`` builds the ``RunConfig`` that ``python -m repro.launch.train``
would build from the cell's files and drives ``Trainer.train`` once:

1. set-up: the seed's weights, made on the device in one jitted call, go
   to the trainer in place of its own initialisation; steps 0 to
   ``WARM_STEPS - 1`` run through the trainer's own step call and feed;
   a probe around that call's first ``CHECKED_STEPS + 1`` calls records
   what the reference is compared on (the checked steps' batches, the
   first clipped gradient as AdamW's first moment holds it after step 0,
   the parameters' change after the checked steps) and then puts the
   program's call back;
2. the window: the trainer's ``fault_hook``, which runs on the host before
   each step's dispatch, marks the start at step ``WARM_STEPS`` and, at the
   first step boundary past ``seconds``, ends the window;
3. with ``trace``, a profiler window of ``PROFILE_STEPS`` further steps,
   then the same step program run untraced (``flare=False``) for Flare's
   overhead;
4. the hook raises ``WindowClosed``, which ``Trainer.train``'s ``finally``
   answers by stopping the loader and detaching the daemon, so the spill
   is flushed; the spill is replayed, the device memory read (the
   allocator's peak and the compiled step's own account), the program's
   state freed, and the float32 reference follows the checked steps.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import statistics
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

import devtrace
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

WARM_STEPS = 4       # steps before the window; the probe reads steps 0-2
# Steps the reference follows: two, not three, since the float32
# reference takes longer than the window (PERF.md).
CHECKED_STEPS = 2
PROFILE_STEPS = 3    # whole steps inside the profiler window
UNTRACED_STEPS = (3, 10)  # bounds on the untraced block's timed steps
UNTRACED_WARM = 2
TOTAL_STEPS = 10**6  # RunConfig.steps: the loop never ends first

# JAX's own monitoring events (as chip_smoke.py reads them): a backend
# compile event spans one XLA compile or one read of it from the
# persistent cache; the retrieval event marks such a read.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"


class WindowClosed(Exception):
    """Raised from the trainer's fault hook to end ``Trainer.train``."""


# ------------------------------------------------------------------ specs
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    module: Any           # its reference: init, loss, model_flops
    traffic: dict
    limits: dict          # the correctness limits, from cells/<cell>.json
    end_to_end: list
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def tokens_per_step(self) -> int:
        return self.traffic["batch"] * self.traffic["seq"]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    spec = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    conf_file = root / conf["file"]
    mod_name = "bench_config_" + "".join(
        ch if ch.isalnum() else "_" for ch in conf["name"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=w["chips"], config=load_json(conf_file),
        module=load_module(conf_file.with_suffix(".py"), mod_name),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "cells" / f"{workload}.json")[
            "limits"],
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def peak(kind: str) -> dict:
    """Per-chip peaks of ``kind`` from ``peaks.json``; an unknown kind is
    an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def weight_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0)


# -------------------------------------------------------------- compiles
class CompileLog:
    """JAX's compile and cache-hit events, each with the host time at
    which it was reported."""

    def __init__(self):
        self.events: list[tuple[float, str, float]] = []

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in (BACKEND_COMPILE, CACHE_HIT):
            self.events.append((time.perf_counter(), event, duration))

    def compile_s(self, until: float) -> float:
        return sum(d for t, e, d in self.events
                   if e == BACKEND_COMPILE and t <= until)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(e == BACKEND_COMPILE and t0 < t <= t1
                   for t, e, _ in self.events)


# ---------------------------------------------------------------- window
class Window:
    """The trainer's fault hook: marks the window and, with a profiler
    directory, the profiled steps after it; raises ``WindowClosed`` to
    end the run."""

    def __init__(self, seconds: float, trace_dir: Optional[Path]):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.first = self.end = None
        self.t0 = self.t1 = None

    def __call__(self, step: int):
        now = time.perf_counter()
        if step == WARM_STEPS:
            self.first, self.t0 = step, now
            return
        if self.t0 is None:
            return
        if self.t1 is None:
            if now - self.t0 < self.seconds:
                return
            self.end, self.t1 = step, now
            if self.trace_dir is None:
                raise WindowClosed
            import jax
            jax.profiler.start_trace(str(self.trace_dir))
            return
        import jax
        k = step - self.end
        if k >= 1:  # whole steps lie between the first and last marks
            with jax.profiler.TraceAnnotation(devtrace.BOUNDARY):
                pass
        if k == 1 + PROFILE_STEPS:
            jax.profiler.stop_trace()
            raise WindowClosed

    @property
    def steps(self) -> int:
        return self.end - self.first

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


# ----------------------------------------------------------------- probe
class Probe:
    """Wraps the trainer's step call for the set-up steps and records what
    the reference is compared on.  Host copies only; it holds no device
    buffer.  After step ``CHECKED_STEPS`` it puts the program's own step
    call back, so the window runs exactly that."""

    def __init__(self, cell: Cell, seed: int, b1: float):
        import jax
        self.step = 0         # counted on the host: the trainer starts at 0
        self.batches: list[dict] = []
        self.first_grad: Optional[dict] = None
        self.change: Optional[dict] = None
        self.program = None   # the trainer's jitted step
        self.shapes = None    # its arguments' shapes and types
        self._b1 = b1
        self._key = weight_key(seed)
        self._change = reference.change_from_init(cell.module, cell.model)

        def moments(opt_state):
            return jax.tree.map(lambda s: s["m"], opt_state["mu_nu"],
                                is_leaf=lambda s: isinstance(s, dict)
                                and set(s) == {"m", "v"})
        self._moments = moments

    def wrap(self, trainer):
        import jax
        self.program = trainer.step_fn

        def probed(params, opt_state, batch, step):
            args = (params, opt_state, batch, step)
            if self.step == 0:
                self.shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            self.record(params, opt_state, batch)
            self.step += 1
            if self.step > CHECKED_STEPS:
                trainer.step_fn = self.program
            return self.program(*args)
        trainer.step_fn = probed

    def record(self, params, opt_state, batch):
        step = self.step
        if step < CHECKED_STEPS:
            self.batches.append({k: np.asarray(v) for k, v in batch.items()
                                 if k in ("tokens", "labels")})
        if step == 1:  # state after one step: m = (1 - b1) g
            norms = reference.named_norms(self._moments(opt_state))
            self.first_grad = {n: x / (1 - self._b1) for n, x in norms.items()}
        if step == CHECKED_STEPS:
            self.change = self._change(params, self._key)

    def step_program_bytes(self) -> int:
        """Device bytes the compiled step holds while it runs, by the
        compiler's own account: its arguments, the outputs not aliased to
        them, its temporaries and its code.  ``peak_bytes_in_use`` leaves
        the temporaries out.  The lowering finds the program the trainer
        ran; no compile."""
        ma = self.program.lower(*self.shapes).compile().memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes
                + ma.generated_code_size_in_bytes)


def make_trainer(run, cell: Cell, seed: int, *, hook=None, probe=None):
    """The program's ``Trainer``, given the seed's weights and, where
    ``probe`` is set, a probe around its first step calls."""
    import jax

    from repro.optim.adamw import adamw_init
    from repro.runtime.train import Trainer

    make = jax.jit(functools.partial(cell.module.init, cell.model))
    key = weight_key(seed)

    class BenchTrainer(Trainer):
        def init_state(self):
            params = make(key)
            return params, adamw_init(params, self.cfg.opt), 0

    trainer = BenchTrainer(run, fault_hook=hook)
    if probe is not None:
        probe.wrap(trainer)
    return trainer


def run_config(cell: Cell, seed: int, spill_dir: Path):
    from repro.configs import ModelConfig
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train import RunConfig

    t, o = cell.traffic, cell.traffic["optimizer"]
    return RunConfig(
        model=ModelConfig(**cell.model), global_batch=t["batch"],
        seq_len=t["seq"], steps=TOTAL_STEPS, warmup_steps=o["warmup_steps"],
        peak_lr=o["peak_lr"], remat=t["remat"],
        opt=AdamWConfig(lr=o["peak_lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                        weight_decay=o["weight_decay"],
                        grad_clip=o["grad_clip"]),
        param_dtype=t["param_dtype"], compute_dtype=t["compute_dtype"],
        seed=seed, flare=t["flare"],
        flare_log=str(spill_dir / f"{cell.config['name']}.fcs"))


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Record:
    """What one run of a cell measured; the metric readers read it."""
    cell: Cell
    seed: int
    device: dict
    setup_s: float
    window: Window
    history: list
    compile_setup_s: float
    window_compiles: int
    spill_dir: Path
    daemon: dict
    replay: dict
    memory_peak_bytes: int   # the allocator's peak
    step_program_bytes: int  # the compiled step's own account
    memory_read_compiles: int
    batches: list
    program: dict            # losses, first_grad, change of the program
    untraced_step_s: Optional[float] = None
    trace: Optional[dict] = None
    reference: Optional[dict] = None
    reference_s: Optional[float] = None

    @property
    def window_history(self) -> list:
        w = self.window
        return [r for r in self.history if w.first <= r["step"] < w.end]

    @property
    def tokens_per_s(self) -> float:
        return self.window.steps * self.cell.tokens_per_step \
            / self.window.window_s

    @property
    def traced_step_s(self) -> float:
        return statistics.fmean(r["step_time_s"] for r in self.window_history)


def device_summary() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def replay_spill(spill_dir: Path, backend: str) -> dict:
    from repro.fleet import FleetConfig, FleetMultiplexer, FleetReplayer
    mux = FleetMultiplexer(FleetConfig(backend=backend))
    stats = FleetReplayer(mux, job_workers=1).replay_dir(str(spill_dir))
    mux.finalize()
    return {"events": stats.events, "files": stats.files,
            "corrupt_files": stats.corrupt_files}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             out_dir: Path, t_start: float, device: dict) -> Record:
    """One run of ``cell``: set-up, window, and (with ``trace``) the
    profiler window and the untraced block; then the spill replay, the
    device memory readings and the reference."""
    shutil.rmtree(out_dir, ignore_errors=True)
    spill_dir, trace_dir = out_dir / "spill", out_dir / "trace"
    spill_dir.mkdir(parents=True)
    run = run_config(cell, seed, spill_dir)
    window = Window(seconds, trace_dir if trace else None)
    probe = Probe(cell, seed, run.opt.b1)
    with CompileLog() as compiles:
        trainer = make_trainer(run, cell, seed, hook=window, probe=probe)
        try:
            trainer.train()
        except WindowClosed:
            pass
        if window.t1 is None:
            raise RuntimeError("the window never closed")
        history, daemon = trainer.history, trainer.daemon
        counters = {"events": daemon.events_emitted,
                    "bytes": daemon.bytes_logged,
                    "spill_errors": daemon.spill_errors,
                    "sink_errors": daemon.sink_errors} if daemon else {}
        del trainer, daemon
        untraced = None
        if trace:
            untraced = untraced_step_s(run, cell, seed, window.steps)
        t_mem = time.perf_counter()
        program_bytes = probe.step_program_bytes()
        mem_compiles = compiles.compiles_between(t_mem, time.perf_counter())
    rec = Record(
        cell=cell, seed=seed, device=device,
        setup_s=window.t0 - t_start, window=window, history=history,
        compile_setup_s=compiles.compile_s(window.t0),
        window_compiles=compiles.compiles_between(window.t0, window.t1),
        spill_dir=spill_dir, daemon=counters,
        replay=replay_spill(spill_dir, f"{cell.model['family']}-train"),
        memory_peak_bytes=memory_peak(), step_program_bytes=program_bytes,
        memory_read_compiles=mem_compiles, batches=probe.batches,
        program={"losses": [r["loss"] for r in history[:CHECKED_STEPS]],
                 "first_grad": probe.first_grad, "change": probe.change},
        untraced_step_s=untraced)
    if trace:
        rec.trace = devtrace.reduce_dir(trace_dir)
    gc.collect()
    t0 = time.perf_counter()
    rec.reference = follow_reference(rec)
    rec.reference_s = time.perf_counter() - t0
    return rec


def untraced_step_s(run, cell: Cell, seed: int, window_steps: int) -> float:
    """Mean step time of the same step program with Flare detached."""
    n = min(max(window_steps, UNTRACED_STEPS[0]), UNTRACED_STEPS[1])
    trainer = make_trainer(dataclasses.replace(run, flare=False,
                                               flare_log=None), cell, seed)
    hist = trainer.train(steps=UNTRACED_WARM + n)
    del trainer
    return statistics.fmean(r["step_time_s"] for r in hist[UNTRACED_WARM:])


def rows_per_call(cell: Cell) -> int:
    return max(1, cell.module.ROW_TOKENS // cell.traffic["seq"])


def follow_reference(rec: Record, *, mm=reference.exact_mm,
                     batches=None) -> dict:
    cell = rec.cell
    return reference.follow(
        cell.module, cell.model, cell.traffic["optimizer"],
        weight_key(rec.seed), batches or rec.batches, mm=mm,
        total_steps=TOTAL_STEPS, rows_per_call=rows_per_call(cell))


# ---------------------------------------------------------------- checks
def checks(rec: Record) -> dict:
    """Every number compared, beside its limit; the run is correct when
    none exceeds its limit."""
    lim = rec.cell.limits
    g = reference.gaps(rec.program, rec.reference)
    out = {name: (g[name], lim[name])
           for name in ("loss_gap", "grad_gap", "update_gap") if name in lim}
    rows = [r.tobytes() for b in rec.batches for r in b["tokens"]]
    w = rec.window_history
    bad = sum(not (math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]))
              for r in w)
    out.update({
        "repeated_rows": (len(rows) - len(set(rows)), 0),
        "window_nonfinite_steps": (bad, 0),
        "spill_errors": (rec.daemon.get("spill_errors", 0), 0),
        "sink_errors": (rec.daemon.get("sink_errors", 0), 0),
        "corrupt_spill_files": (rec.replay["corrupt_files"], 0),
        "events_not_replayed": (abs(rec.daemon.get("events", 0)
                                    - rec.replay["events"]), 0),
        "events_emitted_missing": (int(rec.daemon.get("events", 0) == 0), 0),
    })
    return out


def result(rec: Record, trace: bool) -> dict:
    """The run's result line: end-to-end metrics, or with ``trace`` the
    per-layer metrics; ``checks`` comes last."""
    cell = rec.cell
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"tokens_per_s": rec.tokens_per_s, "setup_s": rec.setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    # The peak on the chip: the larger of the allocator's, which leaves the
    # step's temporaries out, and the compiled step's own account.
    device = dict(rec.device, memory_peak_bytes=max(rec.memory_peak_bytes,
                                                    rec.step_program_bytes),
                  allocator_peak_bytes=rec.memory_peak_bytes,
                  step_program_bytes=rec.step_program_bytes)
    line = {}
    cmp = checks(rec)
    line["correct"] = all(v <= limit for v, limit in cmp.values())
    line["attempted"] = rec.window.steps
    line["failed"] = cmp["window_nonfinite_steps"][0]
    line["metrics"] = metrics
    if trace and rec.trace is not None:
        device.update(busy_s=rec.trace["busy_s"],
                      window_s=rec.trace["window_s"])
        line["breakdown"] = {"device_ops": rec.trace["device_ops"],
                             "idle_gaps": rec.trace["idle_gaps"]}
    line["device"] = device
    line["window_compiles"] = rec.window_compiles
    line["checks"] = {k: {"value": v, "limit": limit}
                      for k, (v, limit) in cmp.items()}
    return line
