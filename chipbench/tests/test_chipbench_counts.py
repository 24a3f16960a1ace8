"""Operations and bytes from shapes, against hand counts, and the peaks table."""
import json
import os

import pytest

from harness import counts
from harness.peaks import PEAKS, peaks_for

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name)) as f:
        return json.load(f)


def test_packed_gemm_at_a_live_m():
    # qwen3-8b's w1 at 16 live slots: 2*16*4096*12288 operations; codes
    # 4096*12288/2 B, one f32 scale, bf16 activations 16*4096 in, 16*12288 out
    assert counts.gemm_ops(16, 4096, 12288) == 1_610_612_736
    assert counts.packed_gemm_bytes(16, 4096, 12288, bits=4) == 25_165_824 + 4 + 524_288
    # odd K rounds the codes up to whole bytes
    assert counts.packed_gemm_bytes(1, 27, 64, bits=4, act_bytes=4) == 864 + 4 + 4 * 91


def test_qwen3_8b_matmul_flops_per_token():
    cfg = _config("qwen3-8b.json")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288  # q,o + k,v + MLP
    assert per_layer == 192_937_984
    assert counts.decoder_matmul_params(cfg) == 36 * per_layer + 4096 * 151936
    assert counts.decoder_flops_per_token(cfg) == 15_136_194_560  # 2 x 7.57e9


def test_vgg16_forward_flops_per_image():
    cfg = _config("vgg16.json")
    macs = [
        224 * 224 * 9 * 3 * 64, 224 * 224 * 9 * 64 * 64,
        112 * 112 * 9 * 64 * 128, 112 * 112 * 9 * 128 * 128,
        56 * 56 * 9 * 128 * 256, 56 * 56 * 9 * 256 * 256, 56 * 56 * 9 * 256 * 256,
        28 * 28 * 9 * 256 * 512, 28 * 28 * 9 * 512 * 512, 28 * 28 * 9 * 512 * 512,
        14 * 14 * 9 * 512 * 512, 14 * 14 * 9 * 512 * 512, 14 * 14 * 9 * 512 * 512,
        7 * 7 * 512 * 4096, 4096 * 4096, 4096 * 1000,
    ]
    assert counts.cnn_forward_flops(cfg) == 2 * sum(macs) == 30_940_528_640
    assert counts.cnn_weight_count(cfg) == 138_344_128


def test_decode_step_bound_is_the_larger_term_per_gemm():
    cfg = _config("qwen3-8b.json")
    peaks = peaks_for("TPU v5 lite")
    b = counts.decode_step_gemm_bounds(cfg, 16, peaks)
    # at 16 rows every GEMM is bound by its bytes
    assert b["least_s"] == pytest.approx(b["bytes_s"])
    assert b["bytes_s"] > b["ops_s"]
    gemms = counts.decoder_layer_gemms(cfg)
    moved = 36 * sum(k * n // 2 + 4 + 2 * 16 * (k + n) for _, k, n in gemms)
    assert b["bytes_s"] == pytest.approx(moved / 819e9)


def test_peaks_table_and_unknown_device():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
