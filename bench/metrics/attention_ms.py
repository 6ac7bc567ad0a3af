"""attention_ms: device self time per profiled step, in ms, of the ops
whose innermost named scope is ``attention``: ln1, the QKV projections,
attention itself and the output projection with its residual add.
Reduced from the profiler window by scopes.py.  Moves tokens_per_s."""
import scopes


def read(rec):
    return scopes.read_ms(rec, "attention")
