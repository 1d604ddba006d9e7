"""``rooflines.py`` against counts made by hand for Qwen2-0.5B."""
import json
import os

import pytest

from benchmark import peaks, rooflines
from benchmark.cell import HERE


def _m(name="qwen2-0.5b"):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_param_count_is_the_published_one():
    # d 896, 14 q / 2 kv heads of 64, FFN 4864, 24 layers, 151936 rows, tied
    attn = 896 * 896 + 2 * 896 * 128 + 896 * 896 + (896 + 2 * 128)
    layer = attn + 3 * 896 * 4864 + 2 * 896
    assert rooflines.layer_param_count(_m()) == layer == 14_912_384
    assert rooflines.param_count(_m()) == 24 * layer + 151936 * 896 + 896
    assert rooflines.param_count(_m()) == 494_032_768       # "0.5B"
    assert rooflines.param_count(_m("qwen2-1.5b-split4")) == 1_543_714_304


def test_decode_step_bytes_by_hand():
    m = _m()
    kv_row = 24 * 2 * 2 * 64 * 2            # layers, K and V, kv heads, hd, bf16
    assert rooflines.kv_bytes_per_token(m, 2) == kv_row == 12288
    live, slots = 160_000, 192
    want = (494_032_768 * 2 + slots * 896 * 2 + live * kv_row
            + slots * kv_row)
    assert rooflines.decode_step_bytes(m, live, slots, 2) == want
    # the whole pool of the cell: 24576 pages of 16 positions, 4.8 GB
    assert 24576 * 16 * kv_row == 4_831_838_208


def test_flops_by_hand():
    m = _m()
    proj = 2 * (896 * 896 + 2 * 896 * 128 + 896 * 896)
    mlp = 2 * 3 * 896 * 4864
    attn = 2 * 2 * 512 * 14 * 64
    assert rooflines.layer_flops_per_token(m, 512) == proj + mlp + attn
    assert rooflines.unembed_flops_per_position(m) == 2 * 896 * 151936
    assert rooflines.prefill_flops(m, 512) == (
        24 * (proj + mlp + attn) * 512 + 2 * 896 * 151936)


def test_peaks_are_the_published_v5e_and_unknown_kinds_raise():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_s") == 819e9
    assert peaks.peak("TPU v5 lite", "ici_bits_s") == 1600e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "bf16_flops")
