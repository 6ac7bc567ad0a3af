"""The per-layer readers of the step's named scopes and the host loop's
phase spans, on the CPU: the scope of an ``op_name`` path, self time per
profiled step by scope, the ``tf_op`` stat read from an XSpace's bytes,
and ``host_gap_ms`` and ``flare_self_ms`` on synthetic spill events.
Loads no TPU library."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import scopes  # noqa: E402
import spill  # noqa: E402


@pytest.mark.parametrize("path,scope", [
    ("jit(step_fn)/transpose(jvp(attention))/dot_general", "attention"),
    # as the TPU profiler reports it (PERF.md section 3)
    ("jit(step_fn)/jvp()/while/body/closed_call/attention/closed_call/"
     "while/body/closed_call/reduce_sum:", "attention"),
    ("jit(step_fn)/transpose(jvp(embed))/scatter-add:", "embed"),
    ("jit(step_fn)/while/body/jvp(mlp)/checkpoint/rematted_computation/add",
     "mlp"),
    ("jit(step_fn)/jvp(head)/jvp(attention)/mul", "attention"),  # innermost
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(step_fn)/while/body/dynamic_update_slice", None),
    ("jit(step_fn)/optimizers/mul", None),   # whole components only
    ("", None),
])
def test_scope_of_looks_through_transforms_to_the_innermost(path, scope):
    assert scopes.scope_of(path) == scope


def test_scope_reduction_takes_self_time_per_profiled_step():
    ms = 1_000_000
    body = "jit(step_fn)/while/body"
    one = [
        ("while.1", "jit(step_fn)/while", 0, 60 * ms),   # encloses its body
        ("fusion.2", f"{body}/jvp(attention)/dot_general", 5 * ms, 25 * ms),
        ("fusion.3", f"{body}/transpose(jvp(mlp))/add", 30 * ms, 50 * ms),
        ("fusion.4", "jit(step_fn)/jvp(head)/transpose(jvp(attention))/mul",
         60 * ms, 70 * ms),
        ("copy.5", "", 70 * ms, 80 * ms),                 # unscoped
        ("fusion.6", "jit(step_fn)/optimizer/mul", 80 * ms, 90 * ms),
        ("fusion.6", "jit(step_fn)/optimizer/mul", 95 * ms, 120 * ms)]
    # two profiled steps in [0, 100 ms]; a second device busy half as long
    two = [(n, p, s // 2, e // 2) for n, p, s, e in one]
    r = scopes.per_step({"/device:TPU:0": one, "/device:TPU:1": two},
                        0, 100 * ms, 2)
    assert r == pytest.approx({
        "attention": (30 + 15) / 4e3, "mlp": (20 + 10) / 4e3,
        "optimizer": (15 + 5 + 12.5) / 4e3,
        None: (20 + 10 + 10 + 5) / 4e3})
    busy = (95 + 57.5) / 4e3
    assert sum(r.values()) == pytest.approx(busy)


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) fields."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_paths_read_the_scope_stat_from_event_metadata():
    """``op_paths`` reads an XSpace's bytes: the stat named ``tf_op`` on
    each device op's event metadata, held as a string or as a reference
    to a stat name; host planes and other stats are passed over."""
    def stat_md(sid, name):
        return (5, _pb((1, sid), (2, _pb((1, sid), (2, name)))))

    def event_md(mid, name, *stats):
        return (4, _pb((1, mid), (2, _pb((1, mid), (2, name),
                                          *[(5, st) for st in stats]))))
    path = "jit(step_fn)/jvp()/while/body/closed_call/mlp/dot_general:"
    device = _pb((1, 7), (2, "/device:TPU:0"),
                 (3, _pb((2, "XLA Ops"))),             # lines are skipped
                 stat_md(1, "tf_op"), stat_md(2, "flops"), stat_md(3, path),
                 event_md(10, "%fusion.1 = f32[8] fusion(...)",
                          _pb((1, 2), (4, 99)), _pb((1, 1), (5, path))),
                 event_md(11, "%copy.2 = f32[8] copy(...)",
                          _pb((1, 1), (7, 3))),        # by reference
                 event_md(12, "%while.3 = (s32[]) while(...)"))
    host = _pb((2, "/host:CPU"), stat_md(1, "tf_op"),
               event_md(10, "python", _pb((1, 1), (5, "x/attention/y"))))
    paths = scopes.op_paths(_pb((1, device), (1, host)))
    assert paths == {"%fusion.1 = f32[8] fusion(...)": path,
                     "%copy.2 = f32[8] copy(...)": path}
    assert scopes.scope_of(path) == "mlp"


def _rec(first=4, end=7):
    return types.SimpleNamespace(window=types.SimpleNamespace(
        first=first, end=end))


def test_host_gap_is_sync_end_to_next_dispatch_end(monkeypatch):
    ev = [("train_step.dispatch", 4, 0.0, 0.001, {}),
          ("train_step.sync", 4, 0.001, 0.900, {}),
          ("train_step.dispatch", 5, 0.904, 0.906, {}),   # gap 6 ms
          ("train_step.sync", 5, 0.906, 1.800, {}),
          ("dataloader.next_batch", 6, 1.801, 1.802, {}),
          ("train_step.dispatch", 6, 1.805, 1.810, {}),   # gap 10 ms
          ("train_step.sync", 6, 1.810, 2.700, {})]
    monkeypatch.setattr(spill, "window_events", lambda rec: iter(ev))
    read = harness.metric_reader("host_gap_ms").read
    assert read(_rec()) == pytest.approx(8.0)
    monkeypatch.setattr(spill, "window_events", lambda rec: iter(ev[:2]))
    assert read(_rec()) is None


def test_flare_self_time_is_the_mean_of_the_steps_counts(monkeypatch):
    ev = [("step_4", 4, 0.0, 0.9, {"loss": 1.0, "flare_self_ns": 30_000}),
          ("train_step.sync", 4, 0.0, 0.9, {}),
          ("step_5", 5, 0.9, 1.8, {"loss": 1.0, "flare_self_ns": 50_000})]
    monkeypatch.setattr(spill, "window_events", lambda rec: iter(ev))
    read = harness.metric_reader("flare_self_ms").read
    assert read(_rec()) == pytest.approx(0.04)
    monkeypatch.setattr(spill, "window_events", lambda rec: iter(ev[1:2]))
    assert read(_rec()) is None


