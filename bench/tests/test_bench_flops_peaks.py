"""Model FLOPs against hand counts, and the table of peaks."""

import json

import pytest

from bench import flops, peaks, spec


def program(config: str) -> dict:
    return spec._json(spec.ROOT / f"bench/configs/{config}.json")["program"]


def test_qwen3_flops_per_token_by_hand():
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024,
    # SwiGLU 3 x 1024x3072; 28 layers; the tied head 1024x151936 once
    layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    n = 28 * layer + 1024 * 151936
    assert flops.matmul_params(program("qwen3-0.6b")) == n == 595_984_384
    attn = 12 * 28 * 16 * 128 * 4096
    got = flops.train_flops_per_token(program("qwen3-0.6b"), 4096)
    assert got == 6 * n + attn
    assert got == pytest.approx(6.40e9, rel=1e-3)
    # seq 1024: attention falls to a sixth of the count
    got_1k = flops.train_flops_per_token(program("qwen3-0.6b"), 1024)
    assert (got_1k - 6 * n) / got_1k == pytest.approx(0.164, abs=1e-3)


def test_granite_flops_per_token_by_hand():
    # per layer: q and o 6144x6144, MQA k and v 6144x128 each, gelu MLP
    # 2 x 6144x24576 = 379,060,224; untied head 6144x49152
    layer = 2 * 6144 * 6144 + 2 * 6144 * 128 + 2 * 6144 * 24576
    assert layer == 379_060_224
    m = program("granite-34b")
    n = m["num_layers"] * layer + 6144 * 49152
    assert flops.matmul_params(m) == n
    attn = 12 * m["num_layers"] * 48 * 128 * 4096
    assert flops.train_flops_per_token(m, 4096) == 6 * n + attn


def test_every_peak_names_its_source():
    with open(peaks.TABLE) as f:
        rows = json.load(f)
    assert rows
    for kind, row in rows.items():
        assert row["source"].strip(), kind
        assert row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99 imaginary")
