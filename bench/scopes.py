"""Device time of the step's named scopes, from a ``jax.profiler`` trace.

Every op traced under ``jax.named_scope`` carries the scope in its
``op_name`` path, inside the wrappers of JAX's transforms:
``jit(step_fn)/transpose(jvp(attention))/dot_general``, or under remat
``.../jvp(mlp)/checkpoint/...``; the TPU profiler writes it as
``jit(step_fn)/jvp()/while/body/closed_call/attention/.../dot_general:``.
It is the stat ``SCOPE_STAT`` of each device op's event metadata.  An op
counts to the innermost scope on its path; an op with none is unscoped.
Times are self times (an XLA ``while`` encloses its body's ops), clipped
to the window between the first and last ``bench.boundary`` marks and
divided by the steps between them.  The trace is loaded once for all the
readers of a run.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

import devtrace

SCOPES = ("embed", "attention", "mlp", "head", "optimizer")
SCOPE_STAT = "tf_op"
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_LOADED: dict = {}   # the last trace file read, and its reduction


def unwrap(part: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    while (m := _WRAPPED.match(part)) is not None:
        part = m.group(1)
    return part


def scope_of(path: str, scopes=SCOPES):
    """The innermost of ``scopes`` on an ``op_name`` path, or None."""
    for part in reversed(path.split("/")):
        name = unwrap(part)
        if name in scopes:
            return name
    return None


def per_step(ops: dict, t0: int, t1: int, steps: int) -> dict:
    """Seconds of device self time per step by scope (None: unscoped),
    averaged over devices.  ``ops`` holds, per device, (name, op_name path,
    start_ns, end_ns) tuples; the window [t0, t1] holds ``steps`` steps."""
    out: dict = defaultdict(float)
    for events in ops.values():
        clipped = [(i, max(s, t0), min(e, t1))
                   for i, (_, _, s, e) in enumerate(events)
                   if e > t0 and s < t1]
        for i, t in devtrace.self_times(clipped):
            out[scope_of(events[i][1])] += t
    return {k: v / (len(ops) * steps * 1e9) for k, v in out.items()}


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """(field number, value) of each field of the protobuf message in
    ``buf[start:end]``: an int for a varint, a (start, end) slice for a
    length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_paths(buf: bytes) -> dict:
    """Device op name -> its ``SCOPE_STAT`` value, for every op of the
    device planes of a serialized ``XSpace``.  The stat sits on each op's
    event metadata, which ``jax.profiler.ProfileData`` does not expose, so
    the fields are read here: XSpace.planes (1); XPlane.name (2),
    .event_metadata (4), .stat_metadata (5); map entries key (1), value
    (2); XEventMetadata.name (2), .stats (5); XStatMetadata.name (2);
    XStat.metadata_id (1), .str_value (5), .ref_value (7)."""
    out: dict = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = _text(buf, v)
            elif n == 4:
                metas.append(v)
            elif n == 5:
                entry = dict(_fields(buf, *v))
                stat = dict(_fields(buf, *entry[2]))
                stat_names[entry[1]] = _text(buf, stat[2]) if 2 in stat else ""
        if not name.startswith("/device:"):
            continue
        for entry in metas:
            meta = _fields(buf, *dict(_fields(buf, *entry))[2])
            op, path = None, None
            for n, v in meta:
                if n == 2:
                    op = _text(buf, v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        path = _text(buf, stat[5])
                    elif 7 in stat:
                        path = stat_names.get(stat[7], "")
            if op is not None and path is not None:
                out[op] = path
    return out


def load(path):
    """(device ops by device as (name, op_name path, start_ns, end_ns),
    the host's ``bench.boundary`` marks) of one ``.xplane.pb`` file."""
    import jax

    raw = Path(path).read_bytes()
    paths = op_paths(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    ops: dict = {}
    marks: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == devtrace.DEVICE_OP_LINE:
                    ops[plane.name] = [
                        (e.name, paths.get(e.name, ""), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            marks.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name == devtrace.BOUNDARY)
    return ops, marks


def reduce_file(path) -> dict:
    """``per_step`` of the profiled steps of one trace file; empty where it
    holds no device ops or fewer than two marks."""
    ops, marks = load(path)
    if not ops or len(marks) < 2:
        return {}
    return per_step(ops, *devtrace.window_of(marks), len(marks) - 1)


def read_ms(rec, scope: str):
    """Milliseconds per profiled step of ``scope`` in the run's trace;
    None where the run has no device trace or no op carries the scope."""
    if rec.trace is None:
        return None
    files = sorted((rec.spill_dir.parent / "trace").rglob("*.xplane.pb"))
    if not files:
        return None
    if _LOADED.get("path") != files[-1]:
        _LOADED.update(path=files[-1], by_scope=reduce_file(files[-1]))
    seconds = _LOADED["by_scope"].get(scope)
    return None if seconds is None else 1e3 * seconds
