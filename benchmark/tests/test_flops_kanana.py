"""`flops_kanana` against hand sums at the published widths: a layer's
parameters, a token-layer's forward by part (the issue's 52.7 / 83.9 / 18.9 /
7.1 MFLOP), the tiles the plan's kernels compute, and the bytes."""

import collections

import pytest

from benchmark import flops_kanana as fk

ATTN = (2048, 32, 512, 128, 64, 128)  # hidden, heads, kv_rank, nope, rope, vd
Plan = collections.namedtuple("Plan", "block_q block_k")


def test_a_layers_parameters_by_hand():
    q = 2048 * 32 * 192
    kv_a, kv_b, out = 2048 * 576, 512 * 32 * 256, 32 * 128 * 2048
    assert (q, kv_a, kv_b, out) == (12_582_912, 1_179_648, 4_194_304,
                                    8_388_608)
    assert fk.latent_attention_params(*ATTN) == q + kv_a + kv_b + out \
        == 26_345_472
    routed = fk.layer_params(*ATTN, expert_width=768, shared_width=1536,
                             held=16, experts=128)
    assert routed == 26_345_472 + 512 + 2 * 2048 + 2048 * 128 + 128 \
        + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768 == 111_547_008
    dense = fk.layer_params(*ATTN, dense_width=6144)
    assert dense == 26_345_472 + 512 + 4096 + 3 * 2048 * 6144 == 64_098_816
    whole = fk.params(*ATTN, 6144, 768, 1536, 16, 128, 16128, 1, 8)
    assert whole == dense + 8 * routed + 2 * 16128 * 2048 + 2048 \
        == 1_022_537_216
    # rung 2 of the memory rule, and 12 bytes a parameter in GiB
    assert fk.params(*ATTN, 6144, 768, 1536, 16, 128, 16128, 1, 6) \
        == 799_443_200
    assert whole * 12 / 2 ** 30 == pytest.approx(11.4277, abs=1e-3)


def test_a_token_layers_forward_by_part():
    parts = fk.layer_forward_flops_per_token(*ATTN, 768, 1536, 16, 128, 6,
                                             8192)
    assert parts["projections"] / 1e6 == pytest.approx(52.69, abs=0.01)
    # 4096 keys on average x 32 heads x (192 + 128) x 2
    assert parts["attention"] == 4096 * 32 * 320 * 2
    assert parts["attention"] / 1e6 == pytest.approx(83.89, abs=0.01)
    assert parts["shared"] / 1e6 == pytest.approx(18.87, abs=0.01)
    assert parts["held_experts"] / 1e6 == pytest.approx(7.08, abs=0.01)
    share = parts["attention"] / sum(parts.values())
    assert 0.51 < share < 0.52


def test_model_flops_a_token():
    got = fk.model_flops_per_token(*ATTN, 6144, 768, 1536, 16, 128, 6, 16128,
                                   1, 8, 8192)
    common = 26_345_472
    routed = common + 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    dense = common + 3 * 2048 * 6144
    attention = 9 * 3 * 4096 * 32 * 320 * 2
    assert got == pytest.approx(
        6 * (dense + 8 * routed + 2048 * 16128) + attention)


def test_the_kernels_compute_the_tiles_the_diagonal_touches():
    # the forward at (512, 512): 1 + 2 + ... + 16 tiles of 16 x 16
    assert fk.executed_pairs(Plan(512, 512), 8192) == 136 * 512 * 512
    # the q-held backward at (512, 1024): q block i sees i // 2 + 1 k blocks
    assert fk.executed_pairs(Plan(512, 1024), 8192) \
        == sum(i // 2 + 1 for i in range(16)) * 512 * 1024 == 72 * 2 ** 19
    # never under the triangle, never the square
    for plan in (Plan(512, 512), Plan(512, 1024), Plan(1024, 512)):
        assert 8192 * 8192 / 2 < fk.executed_pairs(plan, 8192) < 8192 * 8192
    # grouped rows: 8 heads a kv head, 1024 rows are 128 positions
    assert fk.executed_pairs(Plan(1024, 128), 1024, group=8) \
        == 36 * 128 * 128


def test_executed_operations_and_least_bytes():
    plans = {"hvd_flash_fwd": Plan(512, 512),
             "hvd_flash_bwd": Plan(512, 1024)}
    got = fk.flash_executed_flops(plans, 1, 32, 8192, 128, 64, 128)
    fwd = 2 * 32 * 136 * 2 ** 18 * (192 + 128)
    bwd = 2 * 32 * 72 * 2 ** 19 * (3 * 192 + 2 * 128)
    assert got == fwd + bwd
    assert fwd / 1e12 == pytest.approx(0.730, abs=1e-3)
    assert bwd / 1e12 == pytest.approx(2.010, abs=1e-3)
    # two kernels: s and dp formed twice
    two = fk.flash_executed_flops(
        {"hvd_flash_dq": Plan(512, 512), "hvd_flash_dkv": Plan(512, 1024)},
        1, 32, 8192, 128, 64, 128)
    assert two > bwd
    rows = 32 * 8192
    once = 8192 * 64 * 2
    assert fk.flash_min_bytes(["hvd_flash_fwd"], 1, 32, 8192, 128, 64, 128) \
        == rows * 2 * (2 * 128 + 64 + 2 * 128) + once + rows * 4
    # q, k, dq, dk; q2, dq2 a head; v, dO, dv; k2 and dk2 ONCE; two stats
    assert fk.flash_min_bytes(["hvd_flash_bwd"], 1, 32, 8192, 128, 64, 128) \
        == rows * 2 * (4 * 128 + 2 * 64 + 3 * 128) + 2 * once + 2 * rows * 4
