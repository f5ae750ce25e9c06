"""`flops_laguna` by hand: parameters a layer at ISSUE 62's count, the
operations a token needs with each layer at its own heads and its own mask,
and the flash counts by kind at the kind's heads."""

from collections import namedtuple

from benchmark import flops_laguna as fl

C, D, G, DENSE, EXPERT, SHARED, E, HELD = 2048, 128, 8, 8192, 512, 512, 256, 32
HEADS = {"full": 48, "window": 64}
KINDS = ("full", "window", "window", "window") * 2
VOCAB, LENGTH, WINDOW, TOP_K = 12544, 8192, 512, 8


def test_parameters_are_the_issues_count():
    assert fl.attention_params(C, 48, G, D) == 29_458_432   # 29.46 M
    assert fl.attention_params(C, 64, G, D) == 37_879_808   # 37.88 M
    assert fl.feed_forward_params(C, DENSE, EXPERT, SHARED, E, None) \
        == 50_331_648                                       # 50.33 M
    assert fl.feed_forward_params(C, DENSE, EXPERT, SHARED, E, HELD) \
        == 524_288 + 3_145_728 + 32 * 3_145_728              # 104.33 M
    total = fl.params(C, HEADS, G, D, DENSE, EXPERT, SHARED, E, HELD, VOCAB,
                      KINDS, 1)
    assert total == 1_118_277_376
    assert round(total * 12 / 2 ** 30, 2) == 12.50
    # rung 2 (16 held) and rung 3 (layers 0-4) of the memory rule
    assert fl.params(C, HEADS, G, D, DENSE, EXPERT, SHARED, E, 16, VOCAB,
                     KINDS, 1) == 765_955_840      # 8.56 GiB at 12 bytes
    assert fl.params(C, HEADS, G, D, DENSE, EXPERT, SHARED, E, HELD, VOCAB,
                     KINDS[:5], 1) == 691_624_960  # 7.73 GiB


def test_a_token_meets_each_layer_at_its_own_heads_and_mask():
    got = fl.model_flops_per_token(C, HEADS, G, D, DENSE, EXPERT, SHARED, E,
                                   HELD, TOP_K, VOCAB, KINDS, 1, LENGTH,
                                   WINDOW)
    routed = 524_288 + 3_145_728 + TOP_K * HELD / E * 3_145_728
    matmul = (2 * 29_458_432 + 6 * 37_879_808 + 50_331_648 + 7 * routed
              + C * VOCAB)
    full_pairs = LENGTH * LENGTH / 2
    window_pairs = WINDOW * WINDOW / 2 + (LENGTH - WINDOW) * WINDOW
    attention = (2 * 48 * D * full_pairs + 6 * 64 * D * window_pairs) / LENGTH
    assert got == 6.0 * matmul + 12.0 * attention
    # a window layer's visible pairs are an eighth of a full layer's
    assert 0.12 < window_pairs / full_pairs < 0.125
    # the kinds swapped (48 heads under the window, 64 under the triangle)
    # is another count: the heads are the kind's own
    swapped = fl.model_flops_per_token(
        C, {"full": 64, "window": 48}, G, D, DENSE, EXPERT, SHARED, E, HELD,
        TOP_K, VOCAB, KINDS, 1, LENGTH, WINDOW)
    assert swapped != got
    # the dense layer counts once, wherever the routed layers begin
    two = fl.model_flops_per_token(C, HEADS, G, D, DENSE, EXPERT, SHARED, E,
                                   HELD, TOP_K, VOCAB, KINDS, 2, LENGTH,
                                   WINDOW)
    assert two - got == 6.0 * (50_331_648 - routed)


def test_flash_counts_follow_the_plans_tiles_and_the_kinds_heads():
    Plan = namedtuple("Plan", "tiles_visited block_q block_k")
    plans = {"hvd_flash_fwd": Plan(992, 1024, 512),
             "hvd_flash_bwd": Plan(992, 1024, 512)}
    tile = 2.0 * 1024 * 512 * D
    assert fl.flash_executed_flops(plans, D) == (2 + 5) * 992 * tile
    q48, q64, kv = (1 * h * LENGTH * D * 2 for h in (48, 64, 8))
    assert fl.flash_min_bytes(["hvd_flash_fwd"], 1, 48, G, LENGTH, D, 2) \
        == 2 * q48 + 2 * kv + 48 * LENGTH * 4
    assert fl.flash_min_bytes(["hvd_flash_bwd"], 1, 64, G, LENGTH, D, 2) \
        == 3 * q64 + 4 * kv + 2 * 64 * LENGTH * 4
