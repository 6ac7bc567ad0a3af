"""mlp_ms: device self time per profiled step, in ms, of the ops whose
innermost named scope is ``mlp``: ln2, the MLP (or the MoE with its dense
residual) and the residual add.  Reduced from the profiler window by
scopes.py.  Moves tokens_per_s."""
import scopes


def read(rec):
    return scopes.read_ms(rec, "mlp")
