"""ssd_ms: device self time per profiled step, in ms, of the ops whose
innermost named scope is ``ssd``: the chunked SSD scan whole (within-chunk
products, chunk states, the inter-chunk recurrence, the read-out),
forward, remat recompute and backward.  Reduced from the profiler window
by ssm_scopes.py.  Moves tokens_per_s."""
import ssm_scopes


def read(rec):
    return ssm_scopes.read_ms(rec, "ssd")
