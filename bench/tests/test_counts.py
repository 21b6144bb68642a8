"""The FLOP and byte counts the per-layer metrics divide by, against hand
counts, and the peak table."""
from __future__ import annotations

import json
import os

import pytest

import harness
import peaks


def cfg(name):
    with open(os.path.join(harness.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_cnn_counts_by_hand():
    fam = harness.part("families", "dsfl_cnn")
    c = cfg("mnist-cnn-k100")
    # conv1 24x24x32 outputs x 25 MACs; conv2 8x8x64 x (25x32); dense
    # 4x4x64 -> 512; dense 512 -> 10
    macs = 24 * 24 * 32 * 25 + 8 * 8 * 64 * 800 + 1024 * 512 + 512 * 10
    assert macs == 4_267_008
    assert fam.cnn_forward_flops(c) == 2 * macs
    # per client 5 x 400 local + 5 x 1000 distill training images and 1000
    # predictions; the server 5 x 1000 training images; 10k test images
    train = 100 * (2000 + 5000) + 5000
    infer = 100 * 1000 + 10000
    assert fam.round_flops(c) == 3 * 2 * macs * train + 2 * macs * infer
    assert fam.round_flops(c) == pytest.approx(1.8989e13, rel=1e-3)
    # ERA: read (100, 1000, 10) f32, write (1000, 10) f32
    assert fam.era_kernel_bytes(c) == 4 * (1_000_000 + 10_000)


def test_qwen_counts_by_hand():
    fam = harness.part("families", "qwen_serve")
    c = cfg("qwen1.5-4b")
    per_layer = 2560 * 3 * 2560 + 2560 * 2560 + 3 * 2560 * 6912
    params = 40 * per_layer + 151936 * 2560
    # the 3,561,413,120 parameters less QKV biases and norm scales
    assert params == 3_561_413_120 - 40 * 3 * 2560 - 40 * 2 * 2560 - 2560
    assert fam.param_bytes(c) == 2 * params
    # K and V, 20 heads x 128, bf16, 40 layers
    assert fam.kv_bytes_per_token(c) == 409_600
    assert fam.decode_step_bytes(c, 1000) == 2 * params + 409_600_000
    assert fam.decode_step_flops(c, 8, 1000) == (
        2 * 8 * params + 4 * 40 * 20 * 128 * 1000)
    assert fam.prefill_flops(c, 512) == (
        2 * 512 * params + 4 * 40 * 20 * 128 * 512 * 512)


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9 and "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
