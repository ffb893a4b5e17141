"""Work counts and peaks against hand arithmetic."""

import json
import os

import pytest

from chipbench import work
from chipbench.reference import dense

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_deepseek_7b_stage_sizes():
    c = cfg("deepseek-7b")
    # 15 x (4 x 4096^2 + 3 x 4096 x 11008 + 2 x 4096) + 2 x 102400 x 4096
    # + 4096 parameters of 2 bytes: 7.22 GiB (12.87 for all 30 layers)
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096
    assert dense.layer_params(c) == per_layer
    params = 15 * per_layer + 2 * 102400 * 4096 + 4096
    assert dense.weight_bytes(c) == 2 * params
    assert round(dense.weight_bytes(c) / 2 ** 30, 2) == 7.22
    assert round(dense.weight_bytes(dict(c, num_hidden_layers=30))
                 / 2 ** 30, 2) == 12.87
    # K and V of 15 layers x 32 heads x 128 at 2 bytes: 240 KiB a token
    assert dense.kv_bytes_per_token(c) == 240 * 1024


def test_deepseek_coder_33b_stage_sizes():
    c = cfg("deepseek-coder-33b-unscaled-rope")
    # a layer: 2 x 7168^2 (q, o) + 2 x 7168 x 1024 (k, v) + 3 x 7168 x
    # 19200 + 2 x 7168 = 530.3 M; embedding and head 32256 x 7168 = 231.2 M
    per_layer = 2 * 7168 ** 2 + 2 * 7168 * 1024 + 3 * 7168 * 19200 + 2 * 7168
    assert dense.layer_params(c) == per_layer
    assert round(per_layer / 1e6, 1) == 530.3
    assert round(32256 * 7168 / 1e6, 1) == 231.2
    assert round(dense.weight_bytes(c) / 2 ** 30, 2) == 8.76
    assert dense.kv_bytes_per_token(c) == 32 * 1024


def test_prefill_flops_by_hand():
    c = cfg("deepseek-coder-33b-unscaled-rope")
    B, T = 1, 16000
    matmul = 2 * T * 8 * (2 * 7168 ** 2 + 2 * 7168 * 1024 + 3 * 7168 * 19200)
    attn = 4 * 8 * 56 * 128 * (T * (T + 1) // 2)       # causal QK^T and PV
    head = 2 * 7168 * 32256                            # last position only
    assert dense.prefill_flops(c, B, T) == matmul + attn + head
    assert 165e12 < dense.prefill_flops(c, B, T) < 165.2e12
    assert dense.prefill_flops(c, 4, 100) == 4 * dense.prefill_flops(c, 1, 100)


def test_decode_step_by_hand():
    c = cfg("deepseek-7b")
    B, S = 4, 200
    flops, nbytes = dense.decode_step(c, B, S)
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert flops == B * (2 * 15 * per_layer + 4 * 15 * 32 * 128 * S
                         + 2 * 4096 * 102400)
    weights = dense.weight_bytes(c) - 102400 * 4096 * 2    # embedding table
    assert nbytes == weights + B * 4096 * 2 + B * (S + 1) * 240 * 1024
    # weight streaming bounds the step: 6.91 GB of weights and about 0.2
    # GB of cache at 819 GB/s
    pk = work.peak("TPU v5 lite")
    least, total = work.decode_bound_s(dense, c, pk, B, 192, 16)
    assert 15 * 0.00867 < least < 15 * 0.00870
    assert total == sum(dense.decode_step(c, B, 192 + i)[0]
                        for i in range(1, 16))


def test_peaks_table():
    pk = work.peak("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("TPU v9 imaginary")
