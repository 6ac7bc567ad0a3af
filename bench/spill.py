"""A run's Flare spill, read back: the events of the window's steps."""
from __future__ import annotations


def window_events(rec):
    """(name, step, start, end, meta) of every spilled event whose step
    lies in the window, on the daemon's clock (``time.perf_counter``)."""
    from repro.store.fcs import read_fcs

    w = rec.window
    for path in sorted(rec.spill_dir.glob("*.fcs")):
        batch = read_fcs(str(path))
        for i in range(len(batch)):
            step = int(batch.step[i])
            if w.first <= step < w.end:
                yield (batch.names[batch.name_id[i]], step,
                       float(batch.start_ts[i]), float(batch.end_ts[i]),
                       batch.extra.get(i, {}))
