"""mamba2-780m — pure SSD (state-space duality), attention-free.

[arXiv:2405.21060; huggingface.co/state-spaces/mamba2-780m]  48L
d_model=1536 ssm_state=128, tied embedding and head; vocab 50,288 is the
published 50,277 padded to a multiple of 16 (pad_vocab_size_multiple).
"""
from repro.configs import ModelConfig

CONFIG = ModelConfig(
    name="mamba2-780m",
    family="ssm",
    num_layers=48,
    d_model=1536,
    num_heads=0,
    num_kv_heads=0,
    d_ff=0,
    vocab_size=50288,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    tie_embeddings=True,
    notes="attention-free; long_500k RUNS; issue-latency healthy profile "
    "keyed to the ssm backend family (paper §8.2)",
)

REDUCED = ModelConfig(
    name="mamba2-reduced",
    family="ssm",
    num_layers=3,
    d_model=64,
    num_heads=0,
    num_kv_heads=0,
    d_ff=0,
    vocab_size=256,
    ssm_state=16,
    ssm_expand=2,
    ssm_head_dim=16,
    ssm_chunk=16,
)
