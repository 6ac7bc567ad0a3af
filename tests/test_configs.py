"""Assigned-architecture configs: exact published shapes + param counts."""
import pytest

from repro.configs import (ASSIGNED_ARCHS, SHAPES, cells, get_config,
                           get_reduced, list_archs)

EXPECT = {
    "zamba2-2.7b": dict(num_layers=54, d_model=2560, num_heads=32,
                        num_kv_heads=32, d_ff=10240, vocab_size=32000,
                        ssm_state=64),
    "dbrx-132b": dict(num_layers=40, d_model=6144, num_heads=48,
                      num_kv_heads=8, d_ff=10752, vocab_size=100352,
                      num_experts=16, experts_per_token=4),
    "arctic-480b": dict(num_layers=35, d_model=7168, num_heads=56,
                        num_kv_heads=8, d_ff=4864, vocab_size=32000,
                        num_experts=128, experts_per_token=2),
    "llama3-405b": dict(num_layers=126, d_model=16384, num_heads=128,
                        num_kv_heads=8, d_ff=53248, vocab_size=128256),
    "llama3.2-1b": dict(num_layers=16, d_model=2048, num_heads=32,
                        num_kv_heads=8, d_ff=8192, vocab_size=128256),
    "qwen2-0.5b": dict(num_layers=24, d_model=896, num_heads=14,
                       num_kv_heads=2, d_ff=4864, vocab_size=151936,
                       qkv_bias=True),
    "qwen2-72b": dict(num_layers=80, d_model=8192, num_heads=64,
                      num_kv_heads=8, d_ff=29568, vocab_size=152064,
                      qkv_bias=True),
    "musicgen-large": dict(num_layers=48, d_model=2048, num_heads=32,
                           num_kv_heads=32, d_ff=8192, vocab_size=2048),
    "mamba2-780m": dict(num_layers=48, d_model=1536, num_heads=0,
                        d_ff=0, vocab_size=50288, ssm_state=128,
                        tie_embeddings=True),
    "llama-3.2-vision-11b": dict(num_layers=40, d_model=4096, num_heads=32,
                                 num_kv_heads=8, d_ff=14336,
                                 vocab_size=128256),
}

# analytic param counts should land near the advertised sizes
PARAM_BANDS = {
    "zamba2-2.7b": (2.0e9, 3.4e9),
    "dbrx-132b": (118e9, 145e9),
    "arctic-480b": (430e9, 520e9),
    "llama3-405b": (380e9, 430e9),
    "llama3.2-1b": (1.0e9, 1.6e9),
    "qwen2-0.5b": (0.4e9, 0.65e9),
    "qwen2-72b": (65e9, 80e9),
    "musicgen-large": (2.8e9, 3.7e9),  # MusicGen-large is 3.3B
    "mamba2-780m": (0.6e9, 0.95e9),
    "llama-3.2-vision-11b": (9e9, 13e9),
}


@pytest.mark.parametrize("arch", sorted(EXPECT))
def test_exact_config(arch):
    cfg = get_config(arch)
    for k, v in EXPECT[arch].items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


@pytest.mark.parametrize("arch", sorted(PARAM_BANDS))
def test_param_count_band(arch):
    lo, hi = PARAM_BANDS[arch]
    n = get_config(arch).param_count()
    assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo / 1e9}, {hi / 1e9}]"


def test_moe_active_params_smaller():
    for arch in ("dbrx-132b", "arctic-480b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.6 * cfg.param_count()


def test_cells_and_skips():
    all_cells = list(cells(include_skipped=True))
    assert len(all_cells) == 40  # 10 archs x 4 shapes
    run = [c for c in all_cells if not c[2]]
    skipped = [c for c in all_cells if c[2]]
    # long_500k runs only for ssm/hybrid
    assert {(a, s) for a, s, _ in skipped} == {
        (a, "long_500k") for a in ASSIGNED_ARCHS
        if not get_config(a).sub_quadratic}
    assert len(skipped) == 8 and len(run) == 32


def test_reduced_configs_are_small():
    for arch in list_archs():
        r = get_reduced(arch)
        assert r.param_count() < 5e6, arch
        assert r.family == get_config(arch).family


def test_shapes():
    assert SHAPES["train_4k"].tokens == 4096 * 256
    assert SHAPES["decode_32k"].tokens == 128  # one token per sequence
    assert SHAPES["long_500k"].seq_len == 524288
