"""loader_wait_ms: mean per window step of the duration of Flare's own
``dataloader.next_batch`` spans, read back from the run's spill.  Time the
training loop waited for its batch.  Moves tokens_per_s."""
from __future__ import annotations

SPAN = "dataloader.next_batch"


def read(rec):
    from repro.store.fcs import read_fcs

    w = rec.window
    waits = []
    for path in sorted(rec.spill_dir.glob("*.fcs")):
        batch = read_fcs(str(path))
        for i in range(len(batch)):
            if batch.names[batch.name_id[i]] == SPAN \
                    and w.first <= batch.step[i] < w.end:
                waits.append(batch.end_ts[i] - batch.start_ts[i])
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
