"""head_ms: device self time per profiled step, in ms, of the ops whose
innermost named scope is ``head``: the final norm, the tied or untied
head and the cross-entropy.  Reduced from the profiler window by
scopes.py.  Moves tokens_per_s."""
import scopes


def read(rec):
    return scopes.read_ms(rec, "head")
