"""optimizer_ms: device self time per profiled step, in ms, of the ops
whose innermost named scope is ``optimizer``: AdamW's update whole, with
the global-norm clip.  Reduced from the profiler window by scopes.py.
Moves tokens_per_s."""
import scopes


def read(rec):
    return scopes.read_ms(rec, "optimizer")
