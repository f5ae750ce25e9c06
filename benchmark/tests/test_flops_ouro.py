"""The looped model's counts against hand-worked values, at a small size
and at Ouro-2.6B's published one."""

import pytest

from benchmark import flops, flops_ouro


def test_small_size_by_hand():
    # hidden 8, width 12: attention 4 * 64 = 256, feed-forward 3 * 96 = 288
    assert flops_ouro.layer_matmul_params(8, 12) == 256 + 288 == 544
    # 2 layers of 544 + 4 norm scales of 8; embedding and head 2 * 20 * 8;
    # final norm 8; gate 8 + its bias
    assert flops_ouro.params(8, 12, 20, 2) == \
        2 * (544 + 32) + 320 + 8 + 9 == 1489
    # 3 passes: matmul parameters USED a token 3 * (2 * 544 + 8 * 20) =
    # 3744, times 6 = 22,464; attention at 2 heads x 4, length 16: one
    # product 2 * 2 * 16^2 * 4 / 2 = 2048, six of them a layer pass, 3 * 2
    # layer passes: 73,728 over 16 tokens = 4608 a token
    assert flops.attention_matmul_flops(1, 2, 16, 4) == 2048
    assert flops_ouro.model_flops_per_token(8, 12, 20, 2, 3, 2, 4, 16) == \
        22_464 + 4608
    # one pass is the plain decoder's count with a three-matrix MLP
    assert flops_ouro.model_flops_per_token(8, 12, 20, 2, 1, 2, 4, 16) == \
        6 * (2 * 544 + 160) + 2 * 6 * 2048 / 16


def test_published_size_by_hand():
    # a layer: 4 * 2048^2 = 16,777,216 and 3 * 2048 * 5632 = 34,603,008
    assert flops_ouro.layer_matmul_params(2048, 5632) == 51_380_224
    # the published model: 48 layers held once, 2.6 B parameters
    assert flops_ouro.params(2048, 5632, 49152, 48) == \
        48 * (51_380_224 + 8192) + 2 * 100_663_296 + 2048 + 2049 == \
        2_667_974_657
    # the head, used 4 times: 4 * 100,663,296; 4 x 5 layer passes
    got = flops_ouro.model_flops_per_token(2048, 5632, 49152, 5, 4, 16, 128,
                                           4096)
    dense = 6 * (20 * 51_380_224 + 4 * 100_663_296)
    attn = 20 * 6 * (2 * 16 * 4096 ** 2 * 128 / 2) / 4096
    assert got == dense + attn == 8_581_545_984 + 1_006_632_960


@pytest.mark.parametrize("kernels,products", [
    (["hvd_flash_fwd", "hvd_flash_bwd"], 7),
    (["hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"], 9)])
@pytest.mark.parametrize("layers,passes", [(5, 4), (48, 4), (3, 1)])
def test_flash_counts_are_a_layers_times_the_layer_passes(layers, passes,
                                                          kernels, products):
    one = flops.flash_executed_flops(kernels, 1, 16, 4096, 128)
    assert one == products * flops.attention_matmul_flops(1, 16, 4096, 128)
    assert flops_ouro.flash_executed_flops(kernels, layers, passes, 1, 16,
                                           4096, 128) == layers * passes * one
    assert flops_ouro.flash_min_bytes(kernels, layers, passes, 1, 16, 16,
                                      4096, 128) == \
        layers * passes * flops.flash_min_bytes(kernels, 1, 16, 16, 4096, 128)
