"""`flops_sdar`: the visible pairs by the formula against the dense mask,
`mfu`'s count against a hand count at depth 1, the executed count against
the tiles of a plan."""

import collections

import numpy as np
import pytest

from benchmark import flops_sdar


def dense_pairs(length, block):
    i = np.arange(2 * length)[:, None]
    j = np.arange(2 * length)[None, :]
    n_i, n_j = i < length, j < length
    b_i, b_j = (i % length) // block, (j % length) // block
    return int(np.sum((n_i & n_j & (b_i == b_j)) | (n_i & ~n_j & (b_j < b_i))
                      | (~n_i & ~n_j & (b_j <= b_i))))


@pytest.mark.parametrize("length,block", [(64, 4), (64, 16), (256, 4),
                                          (128, 32), (96, 1)])
def test_visible_pairs_is_the_dense_masks_count(length, block):
    assert flops_sdar.visible_pairs(length, block) == dense_pairs(length,
                                                                  block)


def test_the_programs_rule_is_the_same_mask():
    """`ops.BlockDiffusionMask.visible`, which the kernels mask by, against
    this file's three clauses."""
    from horovod_tpu.ops import BlockDiffusionMask

    rule = BlockDiffusionMask(64, 4)
    at = np.arange(128)
    assert int(np.sum(rule.visible(at[:, None], at[None, :], np))) \
        == dense_pairs(64, 4)


def test_model_flops_by_hand_at_depth_one():
    """Published widths, 16 of 128 experts held, an eighth of the
    vocabulary (19072 ids), one layer, 4096 data tokens in blocks of 4."""
    hidden, heads, kv, d, width, experts, held, k = (2048, 32, 4, 128, 768,
                                                     128, 16, 8)
    vocab, length, block = 19072, 4096, 4
    attention = 2048 * 128 * (32 + 4 + 4 + 32)      # q k v o: 18.87 M
    assert attention == 18_874_368
    router = 2048 * 128
    experts_met = 8 * 16 / 128 * 3 * 2048 * 768     # one expert expected
    position = attention + router + experts_met
    pairs = 4096 * 4 + 4096 ** 2                    # 16.79 M a sequence
    want = (6 * (2 * position + 2048 * vocab)
            + 6 * 2 * 32 * 128 * pairs / 4096)
    got = flops_sdar.model_flops_per_token(hidden, heads, kv, d, width,
                                           experts, held, k, vocab, 1,
                                           length, block)
    assert got == pytest.approx(want, rel=1e-12)
    # a forward position: 37.7 MFLOP of projections, 33.6 of attention, 9.4
    # of held experts (the issue's sums)
    assert 2 * attention == pytest.approx(37.7e6, rel=2e-3)
    assert 2 * 2 * 32 * 128 * pairs / 8192 == pytest.approx(33.6e6, rel=2e-3)
    assert 2 * experts_met == pytest.approx(9.4e6, rel=5e-3)
    # depth adds layers, not heads
    two = flops_sdar.model_flops_per_token(hidden, heads, kv, d, width,
                                           experts, held, k, vocab, 2,
                                           length, block)
    assert two - got == pytest.approx(got - 6 * 2048 * vocab, rel=1e-12)


def test_params_is_the_issues_sum():
    sizes = (2048, 32, 4, 128, 768, 128, 16)
    for layers, millions in ((4, 456.7), (6, 646.0), (8, 835.2)):
        assert flops_sdar.params(*sizes, 19072, layers) / 1e6 \
            == pytest.approx(millions, abs=0.1)


def test_executed_flops_are_the_visited_tiles():
    Plan = collections.namedtuple("Plan", "tiles_visited block_q block_k")
    plans = {"hvd_flash_fwd": Plan(1280, 1024, 512),
             "hvd_flash_dq": Plan(1280, 1024, 512),
             "hvd_flash_dkv": Plan(1280, 1024, 512)}
    tile = 2.0 * 1024 * 512 * 128
    assert flops_sdar.flash_executed_flops(plans, 128) \
        == (2 + 3 + 4) * 1280 * tile
    # 5/16 of all tiles: above the visible pairs' share (a cut tile is
    # computed whole), under the causal half
    share = 1280 / (4 * 64 * 16)
    pairs = flops_sdar.visible_pairs(4096, 4) / 8192 ** 2
    assert pairs < share == 5 / 16 < 0.5


def test_min_bytes_count_each_tensor_once_a_kernel():
    q = 32 * 8192 * 128 * 2
    kv = 4 * 8192 * 128 * 2
    stat = 32 * 8192 * 4
    assert flops_sdar.flash_min_bytes(["hvd_flash_fwd"], 1, 32, 4, 8192,
                                      128) == 2 * q + 2 * kv + stat
    assert flops_sdar.flash_min_bytes(
        ["hvd_flash_dq", "hvd_flash_dkv"], 1, 32, 4, 8192, 128) \
        == 5 * q + 6 * kv + 4 * stat
