"""The work the chunked SSD scan needs per training step, for its roofline.

The scan of a Mamba2 layer (arXiv:2405.21060, ngroups 1) over a sequence
of ``seq`` positions in chunks of ``Q``: within each chunk the causal
products C_t . B_s over the state (N) and their weighted sum of x over the
inner width (H x P), for the pairs s <= t alone; per position the chunk
state's update B_s (x) x_s and the read-out C_t . S; per chunk the
inter-chunk recurrence S <- decay S + S_c.  Forward and backward (twice the
forward), no recompute.  Bytes: the scan's inputs and outputs (x, dt, B, C,
y and the chunk states) once per pass, the backward's cotangents and
re-read inputs counting as two passes.  That is a floor on the work: what
an implementation adds (masked pairs, recompute) is not counted, so the
share cannot pass 100% for it.  Imports nothing of the program.
"""
from __future__ import annotations

F32 = 4
PASSES = 3  # forward, and the backward as two


def chunk_len(seq: int, chunk: int) -> int:
    """The chunk the scan runs: ``chunk``, or the whole sequence where it
    is shorter or ``chunk`` does not divide it."""
    q = min(chunk, seq)
    return q if seq % q == 0 else seq


def causal_pairs(seq: int, chunk: int) -> int:
    """(t, s) pairs with s <= t in one sequence's chunks."""
    q = chunk_len(seq, chunk)
    return (seq // q) * q * (q + 1) // 2


def _dims(cfg: dict):
    di = cfg["ssm_expand"] * cfg["d_model"]
    return cfg["num_layers"], di, cfg["ssm_state"]


def flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs of the scan in one step, all layers and rows."""
    layers, di, n = _dims(cfg)
    chunks = seq // chunk_len(seq, cfg["ssm_chunk"])
    forward = (2 * (n + di) * causal_pairs(seq, cfg["ssm_chunk"])
               + 2 * 2 * di * n * seq      # chunk states and read-out
               + 2 * di * n * chunks)      # inter-chunk recurrence
    return float(PASSES * forward * layers * batch)


def bytes_moved(cfg: dict, batch: int, seq: int, act_bytes: int) -> float:
    """Bytes of the scan in one step: x, B, C and y in the compute dtype
    (``act_bytes`` each), dt and the chunk states in float32."""
    layers, di, n = _dims(cfg)
    heads = di // cfg["ssm_head_dim"]
    chunks = seq // chunk_len(seq, cfg["ssm_chunk"])
    per_pass = (act_bytes * seq * (2 * di + 2 * n)   # x, y; B, C
                + F32 * seq * heads                  # dt
                + F32 * chunks * di * n)             # chunk states
    return float(PASSES * per_pass * layers * batch)


def least_seconds(cfg: dict, batch: int, seq: int, act_bytes: int,
                  peak: dict) -> float:
    """The least time the chip could take for the scan of one step: the
    larger of FLOPs over the bf16 peak and bytes over the HBM peak."""
    return max(flops(cfg, batch, seq) / peak["bf16_flops_per_s"],
               bytes_moved(cfg, batch, seq, act_bytes)
               / peak["hbm_bytes_per_s"])
