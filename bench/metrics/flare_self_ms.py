"""flare_self_ms: Flare's own bookkeeping on the training thread per
window step, in ms: the mean of the STEP events' ``flare_self_ns``, which
the daemon counts where it spends it (its spans, step boundaries, stack
and interceptor callbacks; never the user code a span times), read back
from the spill.  Nothing where the program counts none.  Moves
tokens_per_s."""
from __future__ import annotations

import spill

KEY = "flare_self_ns"


def read(rec):
    ns = [meta[KEY] for *_, meta in spill.window_events(rec) if KEY in meta]
    return sum(ns) / len(ns) / 1e6 if ns else None
