"""counts.py against FLOPs and bytes worked out by hand from the
published shapes."""
import pytest

import counts
import spec

DS = spec.config("deepseek-7b")["model"]
SC = spec.config("starcoder2-15b")["model"]


def test_deepseek_7b():
    # per layer: 4 x 4096^2 attention + 3 x 4096 x 11008 gated MLP
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert per_layer == 202_375_168
    assert counts.linear_flops_per_token(DS) == 2 * 30 * per_layer \
        == 12_142_510_080
    assert counts.head_flops_per_token(DS) == 2 * 4096 * 102400
    assert counts.attention_flops(DS, 1) == 4 * 30 * 32 * 128 == 491_520
    # INT4: half a byte per weight and a float32 scale per 128 inputs
    blocks = 30 * per_layer * 17 // 32                   # 0.53125 B/weight
    head = 4096 * 102400 * 17 // 32
    norms = 2 * 4096 * (30 * 2 + 1)
    assert counts.decode_weight_bytes(DS) == blocks + head + norms \
        == 3_448_676_352


def test_starcoder2_15b():
    # 6144^2 q and o, 6144 x 512 k and v (4 KV heads), plain 2-matrix MLP
    per_layer = 2 * 6144 * 6144 + 2 * 6144 * 512 + 2 * 6144 * 24576
    assert per_layer == 383_778_816
    assert counts.linear_flops_per_token(SC) == 2 * 40 * per_layer \
        == 30_702_305_280
    assert counts.attention_flops(SC, 1) == 4 * 40 * 48 * 128
    # tied head: the bfloat16 embedding; LayerNorm has a scale and a bias
    expect = (40 * per_layer * 17 // 32 + 2 * 49152 * 6144
              + 2 * 6144 * (40 * 4 + 2))
    assert counts.decode_weight_bytes(SC) == expect == 8_761_270_272


def test_request_flops_counts_prompt_and_decode_steps():
    lin = counts.linear_flops_per_token(DS)
    head = counts.head_flops_per_token(DS)
    key = counts.attention_flops(DS, 1)
    # 2-token prompt (queries see 1 and 2 keys), then 3 served tokens:
    # the first from the prefill, two decode steps over 3 and 4 keys
    assert counts.request_flops(DS, 2, 3) == (
        2 * lin + head + 2 * (lin + head) + (1 + 2) * key + (3 + 4) * key)
    assert counts.request_flops(DS, 2, 1) == 2 * lin + head + 3 * key


def test_peaks_by_device_kind():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9000")
