"""flops.py against counts worked by hand for both published shapes."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import flops  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_7b_layer_and_model_counts():
    c = dict(_config("mistral-7b-v0.3-1chip"), num_hidden_layers=4)
    # attention: 4096 x 128 x (32 q + 32 o + 8 k + 8 v heads) = 41,943,040
    # SwiGLU: 3 x 4096 x 14336 = 176,160,768
    assert flops.matmul_params_per_layer(c) == 41_943_040 + 176_160_768 == 218_103_808
    head = 4096 * 32768
    assert flops.matmul_params(c) == 4 * 218_103_808 + head == 1_006_632_960
    # total: + the embedding table and 9 norm vectors of 4096
    assert flops.total_params(c) == 1_006_632_960 + head + 9 * 4096 == 1_140_887_552
    # causal attention fwd+bwd at 1024: 6 x 4 layers x 1024 x 32 heads x 128
    assert flops.attention_flops_per_token(c, 1024) == 6 * 4 * 1024 * 4096 == 100_663_296
    assert flops.needed_flops_per_token(c, 1024) == 6 * 1_006_632_960 + 100_663_296
    # the full published depth: 32 layers, the 7.25B model
    full = dict(c, num_hidden_layers=32)
    assert flops.total_params(full) == 7_248_023_552


def test_internlm2_1_8b_counts():
    c = dict(_config("internlm2-1.8b-1chip"), num_hidden_layers=12)
    # attention: 2048 x 128 x (16 + 16 + 8 + 8) = 12,582,912; SwiGLU: 3 x 2048 x 8192 = 50,331,648
    assert flops.matmul_params_per_layer(c) == 62_914_560
    head = 2048 * 92544
    assert flops.matmul_params(c) == 12 * 62_914_560 + head == 944_504_832
    assert flops.attention_flops_per_token(c, 4096) == 6 * 12 * 4096 * 2048
    assert flops.total_params(dict(c, num_hidden_layers=24)) == 24 * 62_914_560 + 2 * head + 49 * 2048


def test_needed_flops_leave_out_the_embedding_and_count_attention_causally():
    c = dict(_config("mistral-7b-v0.3-1chip"), num_hidden_layers=4)
    table = c["hidden_size"] * c["vocab_size"]
    # bench.py's 6 * num_params() would add the table: +13% here
    assert 6 * flops.total_params(c) - 6 * flops.matmul_params(c) == pytest.approx(6 * table, rel=1e-3)
    # non-causal attention (12*L*d*S) is twice the causal count
    assert 12 * 4 * 4096 * 1024 == 2 * flops.attention_flops_per_token(c, 1024)
    # at 16k attention is 20% of what 4 layers need (6.04 of 7.65 GFLOP are matmuls)
    assert flops.needed_flops_per_token(c, 16384) == pytest.approx(7.65e9, rel=2e-3)


def test_peaks_are_keyed_by_exact_device_kind():
    v5e = flops.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v5", "TPU v5 litepod", "cpu", "_doc"):
        with pytest.raises(KeyError):
            flops.load_peaks(kind)
