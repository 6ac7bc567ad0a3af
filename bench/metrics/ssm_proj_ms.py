"""ssm_proj_ms: device self time per profiled step, in ms, of the ops
whose innermost named scope is ``ssm``: the Mamba2 block outside the scan
(the layer norm, the five input projections, the causal convolution, the
gated norm, the output projection and the residual add).  Reduced from the
profiler window by ssm_scopes.py.  Moves tokens_per_s."""
import ssm_scopes


def read(rec):
    return ssm_scopes.read_ms(rec, "ssm")
