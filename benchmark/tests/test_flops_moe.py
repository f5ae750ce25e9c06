"""The mixture-of-experts counts against hand-worked values for OLMoE."""

import json
import os

import pytest

from benchmark import flops_moe

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def test_olmoe_parameters_by_hand():
    # a layer: attention 4 * 2048^2 = 16,777,216; router 2048 * 64 =
    # 131,072; experts 64 * 3 * 2048 * 1024 = 402,653,184
    assert flops_moe.olmoe_layer_matmul_params(2048, 1024, 64, 64) == \
        16_777_216 + 131_072 + 402_653_184 == 419_561_472
    # + 4 norm scales of 2048 a layer; embedding and head 2 * 103,022,592;
    # the final norm
    assert flops_moe.olmoe_params(2048, 1024, 64, 50304, 2) == \
        2 * (419_561_472 + 8192) + 206_045_184 + 2048 == 1_045_186_560
    # the published model: 16 layers, 6.9 B parameters, 1.3 B active
    assert flops_moe.olmoe_params(2048, 1024, 64, 50304, 16) == \
        pytest.approx(6.92e9, rel=2e-3)
    assert flops_moe.olmoe_params(2048, 1024, 8, 50304, 16, router=64) == \
        pytest.approx(1.28e9, rel=5e-3)


def test_olmoe_model_flops_by_hand():
    # active matmul parameters of a layer: 16,777,216 + 131,072 +
    # 8 * 3 * 2048 * 1024 = 50,331,648 -> 67,239,936; two layers and the
    # head 103,022,592: 237,502,464; times 6 = 1,425,014,784 a token
    # attention: 2 layers * 6 products * (2 * 16 * 4096^2 * 128 / 2) / 4096
    #          = 2 * 6 * 34,359,738,368 / 4096 = 100,663,296 a token
    got = flops_moe.olmoe_model_flops_per_token(
        2048, 1024, 64, 8, 50304, 2, 16, 128, 4096)
    assert got == 1_425_014_784 + 100_663_296


def test_grouped_matmul_counts_by_hand():
    # 32768 rows through 2048 x 1024: 2 * 32768 * 2048 * 1024
    assert flops_moe.grouped_matmul_flops(32768, 2048, 1024) == \
        137_438_953_472
    assert flops_moe.gated_experts_flops(32768, 2048, 1024) == \
        9 * 137_438_953_472
    # rows 32768 * 2048 and result 32768 * 1024 in bf16: (67,108,864 +
    # 33,554,432) * 2 = 201,326,592; matrices 64 * 2048 * 1024 = 134,217,728
    # in bf16 268,435,456, resident in f32 536,870,912
    assert flops_moe.grouped_matmul_min_bytes(
        32768, 2048, 1024, 64, 2, 2) == \
        201_326_592 + 268_435_456 == 469_762_048
    assert flops_moe.grouped_matmul_min_bytes(
        32768, 2048, 1024, 64, 2, 4) == \
        201_326_592 + 536_870_912 == 738_197_504
    assert flops_moe.gated_experts_min_bytes(
        32768, 2048, 1024, 64, 2, 4) == 9 * 738_197_504
    # at the v5e's peaks the operations would bind over bf16 matrices
    # (0.698 against 0.574 ms a matmul); over the f32 matrices the program
    # holds, the bytes do (0.901 ms)
    assert 469_762_048 / 819e9 < 137_438_953_472 / 197e12 < \
        738_197_504 / 819e9


def test_configuration_file_holds_the_catalog_row():
    with open(os.path.join(CONFIGS, "olmoe1b7_w2048.json")) as f:
        c = json.load(f)
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["num_hidden_layers"] == 2 and list(c["reduced"]) == [
        "num_hidden_layers"]
    assert c["builder"] == "olmoe"
