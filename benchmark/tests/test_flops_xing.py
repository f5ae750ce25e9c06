"""The Xing configuration's counts against hand-worked values, at a small
size and at the published widths as one chip holds them (the issue's own
table)."""

import pytest

from benchmark import flops, flops_moe, flops_xing

# hidden, heads, q_rank, kv_rank, nope, rope, vd, streams
PUBLISHED = (3584, 32, 768, 512, 128, 64, 128, 4)


def test_the_published_layer_by_hand():
    # 3584x768 + 768x6144 + 3584x576 + 512x8192 + 4096x3584
    assert flops_xing.latent_attention_params(*PUBLISHED[:7]) == \
        2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064 == \
        28_409_856
    # phi 14336 x 24, its bias and three scales
    assert flops_xing.hyper_connection_params(3584, 4) == 344_064 + 24 + 3
    routed = flops_xing.layer_params(*PUBLISHED, expert_width=1024, held=8,
                                     experts=64)
    # attention + its two inner norms + two connections + two block norms
    # + router + selection bias + (1 shared + 8 held) experts of 11.01 M
    assert routed == (28_409_856 + 768 + 512 + 2 * 344_091 + 2 * 3584
                      + 3584 * 64 + 64 + 9 * 3 * 3584 * 1024) == 128_426_358
    dense = flops_xing.layer_params(*PUBLISHED, dense_width=9216)
    assert dense == 28_409_856 + 1280 + 688_182 + 7168 + 99_090_432 == \
        128_196_918
    # 1 dense + 4 routed + the module + the vocabulary slice: 913.3 M
    total = flops_xing.params(*PUBLISHED, 9216, 1024, 8, 64, 16384, 1, 4)
    assert total == (dense + 5 * routed + 2 * 16384 * 3584 + 3584
                     + 2 * 3584 * 3584 + 3 * 3584) == 913_473_668
    assert total * 12 / 2 ** 30 == pytest.approx(10.21, abs=0.005)


def test_model_flops_at_a_small_size_by_hand():
    # hidden 8, 2 heads, ranks 4 and 2, nope 4, rope 2, vd 4, 2 streams
    attn = 8 * 4 + 4 * 2 * 6 + 8 * 4 + 2 * 2 * 8 + 2 * 4 * 8
    assert flops_xing.latent_attention_params(8, 2, 4, 2, 4, 2, 4) == attn \
        == 208
    common = attn + 2 * 2 * 8 * 8            # two phi of [16, 8]
    dense = common + 3 * 8 * 12
    # 4 experts, top-2, 2 held: a token expects 2 * 2 / 4 = 1 held expert
    routed = common + 8 * 4 + (1 + 1.0) * 3 * 8 * 6
    matmul = dense + 3 * routed + 2 * 8 * 8 + 2 * 8 * 20
    # attention: (q.k 6 wide + p.v 4 wide) x 3, 4 layers, length 16
    one = flops.attention_matmul_flops(1, 2, 16, 6) \
        + flops.attention_matmul_flops(1, 2, 16, 4)
    assert one == 2 * 2 * 256 * 10 / 2 == 5120
    got = flops_xing.model_flops_per_token(
        8, 2, 4, 2, 4, 2, 4, 2, 12, 6, 2, 4, 2, 20, 1, 2, 16)
    assert got == 6 * matmul + 4 * 3 * 5120 / 16


@pytest.mark.parametrize("kernels,qk,pv", [
    (["hvd_flash_fwd", "hvd_flash_bwd"], 4, 3),
    (["hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"], 5, 4)])
def test_flash_counts_follow_the_kernels_the_plan_names(kernels, qk, pv):
    wide = flops.attention_matmul_flops(1, 32, 4096, 192)
    narrow = flops.attention_matmul_flops(1, 32, 4096, 128)
    assert flops_xing.flash_executed_flops(kernels, 1, 32, 4096, 128, 64,
                                           128) == qk * wide + pv * narrow
    # one kernel for the backward: 4 x 192 + 3 x 128 = 1152 columns of
    # products a block pair, where plain attention at 128 makes 7 x 128
    if len(kernels) == 2:
        assert qk * 192 + pv * 128 == 1152


def test_flash_bytes_by_hand():
    head = 32 * 4096 * 2          # a head-wide array's bytes a column
    shared, stat = 4096 * 64 * 2, 32 * 4096 * 4
    fwd = (2 * 128 + 64 + 2 * 128) * head + shared + stat
    bwd = (4 * 128 + 3 * 64 + 3 * 128) * head + shared + 2 * stat
    assert flops_xing.flash_min_bytes(["hvd_flash_fwd"], 1, 32, 4096, 128,
                                      64, 128) == fwd
    assert flops_xing.flash_min_bytes(
        ["hvd_flash_fwd", "hvd_flash_bwd"], 1, 32, 4096, 128, 64,
        128) == fwd + bwd
    # the bound that binds at this shape is the operations'
    ops = flops_xing.flash_executed_flops(
        ["hvd_flash_fwd", "hvd_flash_bwd"], 1, 32, 4096, 128, 64, 128)
    assert ops / 197e12 > (fwd + bwd) / 819e9


def test_held_experts_counts_are_the_grouped_matmuls_over_the_held():
    rows = 4 * 4096 * 8 / 64
    assert rows == 2048
    assert flops_moe.gated_experts_flops(rows, 3584, 1024) == \
        9 * 2 * 2048 * 3584 * 1024
    # rows in and out in bf16, eight f32 matrices: the matrices bind
    got = flops_moe.gated_experts_min_bytes(rows, 3584, 1024, 8, 2, 4)
    assert got == 9 * ((2048 * 3584 + 2048 * 1024) * 2
                       + 8 * 3584 * 1024 * 4)
    assert got / 819e9 > flops_moe.gated_experts_flops(rows, 3584,
                                                        1024) / 197e12
