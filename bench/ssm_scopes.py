"""Device time of the Mamba2 block's named scopes, from the run's trace.

The program names each Mamba2 layer ``ssm`` (its norm through the mixer's
residual add) and the chunked SSD scan inside it ``ssd``.  An op counts to
the innermost of ``SCOPES`` on its ``op_name`` path, so the scan's ops
count to ``ssd`` and the rest of the block to ``ssm``.  The trace is read
as ``scopes.py`` reads it (``scopes.load``), self times by
``devtrace.self_times``, over the window between the first and last
``bench.boundary`` marks, per profiled step.  Where the program names no
such scope, as a transformer step does not, nothing is read.
"""
from __future__ import annotations

from collections import defaultdict

import devtrace
import scopes

SCOPES = scopes.SCOPES + ("ssm", "ssd")
_LOADED: dict = {}   # the last trace file read, and its reduction


def per_step(ops: dict, t0: int, t1: int, steps: int) -> dict:
    """Seconds of device self time per step by innermost scope of
    ``SCOPES`` (None: unscoped), averaged over devices."""
    out: dict = defaultdict(float)
    for events in ops.values():
        clipped = [(i, max(s, t0), min(e, t1))
                   for i, (_, _, s, e) in enumerate(events)
                   if e > t0 and s < t1]
        for i, t in devtrace.self_times(clipped):
            out[scopes.scope_of(events[i][1], SCOPES)] += t
    return {k: v / (len(ops) * steps * 1e9) for k, v in out.items()}


def reduce_file(path) -> dict:
    ops, marks = scopes.load(path)
    if not ops or len(marks) < 2:
        return {}
    return per_step(ops, *devtrace.window_of(marks), len(marks) - 1)


def read_ms(rec, scope: str):
    """Milliseconds per profiled step of ``scope``; None where the run has
    no device trace or no op carries the scope."""
    if rec.trace is None:
        return None
    files = sorted((rec.spill_dir.parent / "trace").rglob("*.xplane.pb"))
    if not files:
        return None
    if _LOADED.get("path") != files[-1]:
        _LOADED.update(path=files[-1], by_scope=reduce_file(files[-1]))
    seconds = _LOADED["by_scope"].get(scope)
    return None if seconds is None else 1e3 * seconds
