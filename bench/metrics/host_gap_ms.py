"""host_gap_ms: the host's time between one step's device work and the
next's: the mean, over consecutive window steps k and k+1, of the end of
step k+1's ``train_step.dispatch`` span less the end of step k's
``train_step.sync`` span, read back from the spill.  Nothing where the
program writes no such spans.  Moves tokens_per_s."""
from __future__ import annotations

import spill

DISPATCH, SYNC = "train_step.dispatch", "train_step.sync"


def read(rec):
    ends = {(name, step): end
            for name, step, _, end, _ in spill.window_events(rec)
            if name in (DISPATCH, SYNC)}
    gaps = [ends[DISPATCH, k + 1] - ends[SYNC, k]
            for k in range(rec.window.first, rec.window.end - 1)
            if (DISPATCH, k + 1) in ends and (SYNC, k) in ends]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
