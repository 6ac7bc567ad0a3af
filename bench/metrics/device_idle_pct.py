"""device_idle_pct: 1 - (union of device-operation intervals) / window,
in percent, from a jax.profiler trace of whole steady steps after the
window, reduced by devtrace.py.  Moves tokens_per_s."""


def read(rec):
    return None if rec.trace is None else rec.trace["idle_pct"]
