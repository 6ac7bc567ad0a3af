"""flare_overhead_pct: (mean traced step - mean untraced step) / mean
untraced step, in percent.  The traced steps are the window's, with Flare
attached; the untraced block runs the same cached step program in a
second trainer with ``flare=False``, after the profiler window.  Step
time is the trainer's own ``step_time_s``.  Moves tokens_per_s."""


def read(rec):
    if rec.untraced_step_s is None:
        return None
    return 100.0 * (rec.traced_step_s - rec.untraced_step_s) \
        / rec.untraced_step_s
