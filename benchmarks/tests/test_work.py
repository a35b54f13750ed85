"""The yardstick's arithmetic, pinned on hand-worked shapes."""
import json
import os

import pytest

from benchmarks.lib import peaks, work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_gpt_medium_train_flops_per_token():
    cfg = config("gpt2-medium")
    # per layer 4 H^2 + 2 H F = 12 x 1024^2; 24 layers; head 50304 x 1024
    assert work.gpt_matmul_params(cfg) == 24 * 12 * 1024 ** 2 + 50304 * 1024
    assert 6 * work.gpt_matmul_params(cfg) == pytest.approx(2.121e9, rel=1e-3)
    # attention, every key counted (the issue's 0.30 GFLOP): 3 x 24 x 4 x
    # 1024 x 1024; causal, a token sees (S + 1) / 2 keys on average
    full = 3 * work.attention_flops_per_token(24, 1024, 1024, causal=False)
    assert full == pytest.approx(0.302e9, rel=1e-3)
    causal = 3 * work.attention_flops_per_token(24, 1024, 1024)
    assert causal == pytest.approx(full * 1025 / 2048)
    assert work.gpt_train_flops_per_token(cfg, 1024) == pytest.approx(
        2.121e9 + 0.1511e9, rel=1e-3)


def test_gpt_served_token():
    cfg = config("gpt2-medium")
    assert work.gpt_forward_flops_token(cfg, 100) == \
        2.0 * work.gpt_matmul_params(cfg) + 24 * 4.0 * 1024 * 100


def test_mamba_1p4b():
    cfg = config("mamba-1.4b")
    d, N, K, R = work.mamba_dims(cfg)
    assert (d, N, K, R) == (4096, 16, 4, 128)
    per_layer = 2048 * 8192 + 4096 * 160 + 128 * 4096 + 4096 * 2048
    assert work.mamba_matmul_params(cfg) == 48 * per_layer + 50280 * 2048
    assert work.ssm_scan_flops_per_token(4096, 16) == 7 * 4096 * 16
    # one decode step of 128 rows: x, dt, y [128, 4096]; B, C [128, 16];
    # A [4096, 16]; state [128, 4096, 16] read and written; float32
    assert work.ssm_scan_bytes(128, 128, 4096, 16) == 4 * (
        3 * 128 * 4096 + 2 * 128 * 16 + 4096 * 16 + 2 * 128 * 4096 * 16)


def test_flash_attention_work():
    w = work.flash_attention_work(8, 16, 1024, 64)
    mm = 2.0 * 8 * 16 * (1024 * 1025 / 2) * 64
    assert w["flash_attention_fwd"]["flops"] == 2 * mm
    assert w["flash_attention_dq"]["flops"] == 3 * mm
    assert w["flash_attention_dkv"]["flops"] == 4 * mm
    assert w["flash_attention_fwd"]["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2


def test_ragged_rows_work():
    # one decode row at context 33 with 16-token pages reads 3 pages of
    # K and V; one prefill row of 4 tokens ending at context 4
    w = work.ragged_rows_work([(1, 33), (4, 4)], heads=2, head_dim=8,
                              page_size=16)
    assert w["flops"] == 4.0 * 2 * 8 * (33 + (1 + 2 + 3 + 4))
    assert w["bytes"] == 2.0 * (3 + 1) * 16 * 2 * 8 * 2 + 2.0 * 5 * 2 * 8 * 2


def test_roofline_says_which_roof():
    peak = peaks.peak_for("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert work.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="not in benchmarks/lib/peaks.py"):
        peaks.peak_for("cpu")
