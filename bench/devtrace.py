"""Reduce a ``jax.profiler`` trace to the device's busy and idle time.

The traced window runs from the first to the last ``bench.boundary``
annotation that the harness writes on the host at step boundaries.
Busy time is the union of the intervals in which an XLA operation runs on
a device, averaged over the devices; the idle share is one minus busy
over the window.  Device operations are ranked by self time (an XLA
``while`` encloses its body's ops) under their HLO instruction names.
Each idle gap is named by what the host was doing in it: the host event
that covers most of the gap, the shortest such event where several cover
it alike.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

BOUNDARY = "bench.boundary"
DEVICE_OP_LINE = "XLA Ops"
TOP = 10


def load(path: Path):
    """(device ops by device, host events) of one ``.xplane.pb`` file, as
    (name, start_ns, end_ns) triples."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ops: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events)
    return ops, host


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps_of(busy, t0, t1):
    """Idle intervals of [t0, t1] outside the merged ``busy`` intervals."""
    out, cursor = [], t0
    for a, b in busy:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < t1:
        out.append((cursor, t1))
    return out


def short_name(name: str) -> str:
    """``%fusion.596 = (f32[...]) fusion(...)`` -> ``fusion.596``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """Per event, its duration less the time of the events nested inside
    it on the same line (an XLA ``while`` encloses its body's ops)."""
    out, stack = [], []   # stack of indices into out: [name, start, end, self]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][2]) - s
        out.append([n, s, e, e - s])
        stack.append(len(out) - 1)
    return [(n, t) for n, _, _, t in out]


def host_activity(host, a, b) -> str:
    best = None
    for name, s, e in host:
        if name == BOUNDARY:
            continue
        cover = min(e, b) - max(s, a)
        if cover <= 0:
            continue
        rank = (round(cover / (b - a), 2), -(e - s))
        if best is None or rank > best[0]:
            best = (rank, name)
    return best[1] if best else "(no host event)"


def reduce(ops: dict, host: list, t0: int, t1: int) -> dict:
    """Busy and idle time of the window [t0, t1] (ns), the device
    operations that took most time, and the longest idle gaps by what the
    host was doing."""
    if not ops or t1 <= t0:
        return None
    busy_ns, by_op, gaps = 0, defaultdict(float), []
    for events in ops.values():
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in events
                   if e > t0 and s < t1]
        for n, t in self_times(clipped):
            by_op[short_name(n)] += t / 1e9
        merged = union((s, e) for _, s, e in clipped)
        busy_ns += sum(b - a for a, b in merged)
        gaps.extend(gaps_of(merged, t0, t1))
    n_dev = len(ops)
    window_s = (t1 - t0) / 1e9
    busy_s = busy_ns / n_dev / 1e9
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": [[n, s / n_dev] for n, s in top_ops],
        "idle_gaps": [[host_activity(host, a, b), (b - a) / 1e9]
                      for a, b in longest],
    }


def window_of(host) -> tuple[int, int]:
    marks = sorted(s for name, s, _ in host if name == BOUNDARY)
    return (marks[0], marks[-1]) if len(marks) >= 2 else (0, 0)


def reduce_dir(trace_dir: Path):
    """Reduce the one trace under ``trace_dir``; None where it holds no
    device operations."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    ops, host = load(files[-1])
    return reduce(ops, host, *window_of(host))
