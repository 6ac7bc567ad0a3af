"""``jax.named_scope`` names of the training step's layers, and the
``checkpoint_name`` names a remat policy saves.

Scopes only set each HLO op's ``op_name`` metadata (``jvp(attention)``,
``transpose(jvp(mlp))``): the compiled program is otherwise the same.  A
profiler trace carries them in each device op's ``tf_op`` stat, and the
benchmark's readers match these strings there.  This module imports
nothing of the repository, so the runtime, the models and the kernels can
all name them.
"""

EMBED_SCOPE = "embed"          # the token lookup
ATTENTION_SCOPE = "attention"  # ln1 through the output projection's residual
MLP_SCOPE = "mlp"              # ln2 through the MLP or MoE and its residual
HEAD_SCOPE = "head"            # final norm, head and cross-entropy
OPTIMIZER_SCOPE = "optimizer"  # adamw_update whole, with the clip
STEP_SCOPES = (EMBED_SCOPE, ATTENTION_SCOPE, MLP_SCOPE, HEAD_SCOPE,
               OPTIMIZER_SCOPE)
SSM_SCOPE = "ssm"  # a Mamba2 layer: ln through the mixer's residual add
SSD_SCOPE = "ssd"  # the chunked SSD scan whole, inside ``ssm``

# ``checkpoint_name`` names of the flash attention forward's outputs, which
# the ``full`` remat policy keeps so the forward is not run again in the
# backward (``models/attention.py chunked_attention``, ``layers.maybe_remat``)
ATTENTION_OUT = "attention_out"  # o [B,S,KV,G,hd], the compute dtype
ATTENTION_LSE = "attention_lse"  # log-sum-exp [B,KV,G,S], float32
