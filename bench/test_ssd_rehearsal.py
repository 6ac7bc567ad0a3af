"""A rehearsal of ``mamba2-780m.seq2k`` on the CPU at the REDUCED mamba2
widths, with the published chunk of 256 over sequences of 512, two chunks
each: the program's ``MambaLM`` through the harness against the float32
reference ``configs/mamba2-780m.py`` on the seed's random weights, under
the rehearsal's limits for mamba2 (``test_rehearsal.py``).  The init puts
dt up to 0.1 and |A| up to 16, so over a 256-position chunk the masked
exponents of the scan's within-chunk term reach several hundred: the
gradient is finite only where they are masked before ``exp``."""
from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from test_rehearsal import REHEARSAL_LIMITS, rehearse  # noqa: E402

CELL = "mamba2-780m.seq2k"
CHUNK, SEQ = 256, 512


def ssd_cell() -> harness.Cell:
    """The committed cell with the REDUCED mamba2 widths at the published
    chunk, 2 x 512 tokens and the rehearsal's limits."""
    from repro.configs import get_reduced

    cell = harness.resolve(CELL)
    model = dict(dataclasses.asdict(get_reduced("mamba2-780m")),
                 ssm_chunk=CHUNK, tie_embeddings=True)
    return dataclasses.replace(
        cell, name="mamba2-780m.chunk256",
        config=dict(cell.config, model=model),
        traffic=dict(cell.traffic, batch=2, seq=SEQ),
        limits=REHEARSAL_LIMITS["mamba2-780m"])


def test_mamba2_at_the_published_chunk_agrees_with_its_reference(tmp_path):
    cell = ssd_cell()
    assert cell.model["ssm_chunk"] == CHUNK and SEQ // CHUNK == 2
    rec = rehearse(cell, tmp_path)
    line = harness.result(rec, trace=False)
    assert line["correct"], line["checks"]
    assert line["checks"]["window_nonfinite_steps"]["value"] == 0
    assert all(math.isfinite(v) for v in rec.program["first_grad"].values())
    assert rec.window_compiles == 0
