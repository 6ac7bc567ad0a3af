"""The trainer's own tracing: named scopes on the jitted step's device
work, one Flare span per host phase of a step (each also a profiler
annotation), and Flare's self time per step."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core import daemon as daemon_mod
from repro.core.events import EventKind
from repro.core.metrics import aggregate_step
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.train import (SSD_SCOPE, SSM_SCOPE, STEP_SCOPES,
                                 RunConfig, Trainer)
from repro.store.fcs import read_fcs

PHASES = ["dataloader.next_batch", "train_step.h2d", "train_step.dispatch",
          "train_step.sync", "train_step.record"]


def _run(arch="qwen2-0.5b", **kw):
    return RunConfig(model=get_reduced(arch), global_batch=2,
                     seq_len=32, steps=4, warmup_steps=2, peak_lr=1e-3,
                     opt=AdamWConfig(lr=1e-3), **kw)


def _has_scope(path: str, scope: str) -> bool:
    # a scope is a whole path component, or one inside transform wrappers
    return re.search(rf"(^|[/(]){scope}([)/]|$)", path) is not None


def _innermost(path: str, scopes) -> str | None:
    found = [(m.start(), s) for s in scopes
             for m in re.finditer(rf"(^|[/(]){s}(?=[)/]|$)", path)]
    return max(found)[1] if found else None


# (arch, remat) -> the scopes its step carries, and those its backward
# keeps inside the transpose
SCOPE_CASES = {
    "full": (("qwen2-0.5b", "full"), STEP_SCOPES,
             ("attention", "mlp", "head")),
    "none": (("qwen2-0.5b", "none"), STEP_SCOPES,
             ("attention", "mlp", "head")),
    "mamba2-full": (("mamba2-780m", "full"),
                    ("embed", SSM_SCOPE, SSD_SCOPE, "head", "optimizer"),
                    (SSM_SCOPE, SSD_SCOPE, "head")),
    "mamba2-none": (("mamba2-780m", "none"),
                    ("embed", SSM_SCOPE, SSD_SCOPE, "head", "optimizer"),
                    (SSM_SCOPE, SSD_SCOPE, "head")),
}


@pytest.mark.parametrize("case", list(SCOPE_CASES))
def test_step_ops_carry_each_scope(case):
    (arch, remat), carried, backward = SCOPE_CASES[case]
    run = _run(arch, remat=remat, flare=False)
    trainer = Trainer(run)
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, run.opt), params)
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = trainer.step_fn.lower(
        params, opt, {"tokens": tok, "labels": tok},
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert set(STEP_SCOPES) == {"embed", "attention", "mlp", "head",
                                "optimizer"}
    assert (SSM_SCOPE, SSD_SCOPE) == ("ssm", "ssd")
    for scope in carried:
        assert any(_has_scope(p, scope) for p in paths), scope
    # every scoped op is under one of the step's own scopes; a fused op's
    # name joins its sources' paths with ";", the first of them whole
    every = STEP_SCOPES + (SSM_SCOPE, SSD_SCOPE)
    whole = [p.split(";")[0] for p in paths]
    assert {_innermost(p, every) for p in whole} - {None} == set(carried)
    # the scan is inside its layer: ssd is innermost under ssm
    for p in whole:
        if _has_scope(p, SSD_SCOPE):
            assert _innermost(p, every) == SSD_SCOPE, p
            assert p.rindex(SSM_SCOPE) < p.rindex(SSD_SCOPE), p
    # the backward of each layer keeps its scope inside the transpose
    for scope in backward:
        assert any("transpose(" in p and _has_scope(p, scope)
                   for p in paths), scope


def _spill(tmp_path):
    trainer = Trainer(_run(flare=True, flare_log=str(tmp_path / "t.fcs")))
    hist = trainer.train()
    paths = sorted(tmp_path.glob("*.fcs"))
    assert paths
    events = []
    for path in paths:
        b = read_fcs(str(path))
        events += [(b.names[b.name_id[i]], int(b.step[i]),
                    float(b.start_ts[i]), float(b.end_ts[i]),
                    b.extra.get(i, {})) for i in range(len(b))]
    return trainer, hist, events


def test_every_step_spills_its_phases_in_order_and_its_self_time(tmp_path):
    trainer, hist, events = _spill(tmp_path)
    assert len(hist) == 4
    self_ns = []
    for step in range(4):
        phases = [(n, s, e) for n, st, s, e, _ in events
                  if st == step and n in PHASES]
        assert [n for n, _, _ in sorted(phases, key=lambda p: p[1])] \
            == PHASES, step
        spans = sorted(phases, key=lambda p: p[1])
        for (_, s, e), (_, s_next, _) in zip(spans, spans[1:]):
            assert s <= e <= s_next   # no overlap
        # train_step_exec runs from the dispatch to the loss fetch's end
        times = {n: (s, e) for n, s, e in phases}
        (exec_s, exec_e), = [(s, e) for n, st, s, e, _ in events
                             if st == step and n == "train_step_exec"]
        assert exec_s == times["train_step.dispatch"][0]
        assert exec_e == times["train_step.sync"][1]
        steps = [m for n, st, _, _, m in events
                 if st == step and n == f"step_{step}"]
        assert len(steps) == 1 and steps[0]["flare_self_ns"] > 0
        self_ns.append(steps[0]["flare_self_ns"])
    assert sum(self_ns) == trainer.daemon.self_ns
    assert trainer.daemon.telemetry.value("daemon.self_ns") == sum(self_ns)


def test_phase_kinds_keep_the_engine_metrics(tmp_path):
    """The phases are host API spans: root-cause narrowing sums them, the
    FLOPS metric still reads ``train_step_exec`` and the dataloader void
    still reads ``dataloader.next_batch``."""
    trainer = Trainer(_run(flare=True, flare_log=str(tmp_path / "t.jsonl")))
    trainer.train()
    from repro.core.events import load_jsonl
    events = load_jsonl(str(tmp_path / "t.jsonl"))
    kinds = {e.name: e.kind for e in events if e.name in PHASES}
    assert kinds == {**{p: EventKind.PY_API for p in PHASES},
                     "dataloader.next_batch": EventKind.DATALOADER}
    m = aggregate_step({0: events}, 2)
    assert list(m.flops) == ["train_step_exec"]
    assert set(PHASES) <= set(m.api_spans)


@pytest.mark.parametrize("flare", [True, False])
def test_spans_only_with_flare(flare, tmp_path, monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(daemon_mod, "_trace_annotation", lambda: Annotation)
    log = str(tmp_path / "t.fcs") if flare else None
    trainer = Trainer(_run(flare=flare, flare_log=log))
    trainer.train()
    if flare:
        assert opened == PHASES * 4
    else:
        assert opened == [] and trainer.daemon is None


# (seq_len, attn_impl, q_chunk, kv_chunk) -> per-step (visited, total):
# the bench cells' block grids (16 x 32 at seq16k, 4 x 8 at seq4k) at a
# reduced length, and the direct path, which runs no flash blocks.
KV_BLOCK_CASES = {
    "seq16k_grid": ((128, "chunked", 8, 4), (272, 512)),
    "seq4k_grid": ((64, "chunked", 16, 8), (20, 32)),
    "direct": ((32, "auto", 1024, 512), None),
}


@pytest.mark.parametrize("case", list(KV_BLOCK_CASES))
def test_daemon_counts_kv_blocks_visited_per_step(case):
    (seq, impl, q_chunk, kv_chunk), want = KV_BLOCK_CASES[case]
    run = dataclasses.replace(_run(flare=True, attn_impl=impl), seq_len=seq,
                              steps=3)
    trainer = Trainer(run)
    trainer.model.q_chunk, trainer.model.kv_chunk = q_chunk, kv_chunk
    trainer.train()
    assert trainer.kv_blocks() == want
    counters = trainer.daemon.telemetry.snapshot()["counters"]
    names = ("attention.kv_blocks_visited", "attention.kv_blocks_total")
    if want is None:
        assert not set(names) & set(counters)
    else:
        assert [counters[n] for n in names] == [3 * n for n in want]


# arch -> per-step (kept, total) SSD pairs at 2 x 64 tokens, the REDUCED
# chunk 16: 4 chunks of 16 x 17 / 2 causal pairs of 16 x 16; a transformer
# runs no SSD scan.
SSD_PAIR_CASES = {
    "mamba2-780m": (4 * 136, 4 * 256),
    "zamba2-2.7b": (4 * 136, 4 * 256),
    "qwen2-0.5b": None,
}


@pytest.mark.parametrize("arch", list(SSD_PAIR_CASES))
def test_daemon_counts_ssd_pairs_per_step(arch):
    want = SSD_PAIR_CASES[arch]
    run = dataclasses.replace(_run(arch, flare=True), seq_len=64, steps=3)
    trainer = Trainer(run)
    trainer.train()
    assert trainer.ssd_pairs() == want
    counters = trainer.daemon.telemetry.snapshot()["counters"]
    names = ("ssd.pairs_kept", "ssd.pairs_total")
    if want is None:
        assert not set(names) & set(counters)
    else:
        assert [counters[n] for n in names] == [3 * n for n in want]
