"""`flops_lfm2` by hand: parameters a layer at ISSUE 65's count, the
operations a token needs, the gated pass's bytes, and the flash counts from
the plan's tiles."""

from collections import namedtuple

from benchmark import flops_lfm2 as fl

C, H, G, D, DENSE, EXPERT = 2048, 32, 8, 64, 7168, 1792
E, HELD, TAPS = 32, 8, 3
KINDS = ("conv", "conv", "full", "conv", "conv", "conv", "full", "conv",
         "conv", "conv")
VOCAB, LENGTH, TOP_K = 16384, 8192, 4


def test_parameters_are_the_issues_count():
    assert fl.conv_params(C) == 16_777_216                    # 16.78 M
    assert fl.attention_params(C, H, G, D) == 10_485_760      # 10.49 M
    assert fl.feed_forward_params(C, DENSE, EXPERT, E, None) == 44_040_192
    assert fl.feed_forward_params(C, DENSE, EXPERT, E, HELD) \
        == 65_536 + 8 * 11_010_048
    total = fl.params(C, H, G, D, DENSE, EXPERT, E, HELD, TAPS, VOCAB, KINDS,
                      2)
    assert total == 982_084_096                   # rung 1: 10.98 GiB at 12
    assert round(total * 12 / 2 ** 30, 2) == 10.98
    assert fl.params(C, H, G, D, DENSE, EXPERT, E, HELD, TAPS, VOCAB,
                     KINDS[:6], 2) == 568_647_936  # rung 2: 6.36 GiB
    # the published model whole, one table: the "8.3B"
    published = "ccfcccfcccfcccfcccfccfcc"   # 18 conv + 6 attention
    whole = fl.params(C, H, G, D, DENSE, EXPERT, E, E, TAPS, 65536,
                      tuple({"c": "conv", "f": "full"}[k]
                            for k in published), 2)
    assert round(whole / 1e9, 2) == 8.34


def test_a_token_meets_conv_mixers_attention_and_its_share_of_experts():
    got = fl.model_flops_per_token(C, H, G, D, DENSE, EXPERT, E, HELD, TOP_K,
                                   VOCAB, KINDS, 2, LENGTH)
    routed = 65_536 + TOP_K * HELD / E * 11_010_048
    matmul = (8 * 16_777_216 + 2 * 10_485_760 + 2 * 44_040_192 + 8 * routed
              + C * VOCAB)
    attention = 2 * H * D * (LENGTH * LENGTH / 2) / LENGTH
    assert got == 6.0 * matmul + 12.0 * attention
    # ISSUE 65: 365 M matrix parameters a token meets, the conv mixers' 134 M
    # the largest part beside the feed-forwards' 176 M
    assert round(matmul / 1e6) == 365
    assert round(8 * 16_777_216 / 1e6) == 134
    assert round((2 * 44_040_192 + 8 * TOP_K * HELD / E * 11_010_048) / 1e6) \
        == 176
    # a third dense layer in a routed one's place is that much more
    three = fl.model_flops_per_token(C, H, G, D, DENSE, EXPERT, E, HELD,
                                     TOP_K, VOCAB, KINDS, 3, LENGTH)
    assert three - got == 6.0 * (44_040_192 - routed)


def test_the_gated_pass_is_counted_in_the_bytes_of_one_pass():
    one = fl.gate_min_bytes(2 * 8192, C, TAPS)
    cells = 2 * 8192 * C
    assert one == {"forward": 8 * cells + TAPS * C * 4,
                   "backward": 14 * cells + 2 * TAPS * C * 4}
    # ISSUE 65: 268 MB forward, 470 MB backward a call
    assert round(one["forward"] / 1e6) == 268
    assert round(one["backward"] / 1e6) == 470
    # eight layers, each forward again in a recomputed block
    assert fl.gate_step_min_bytes(2 * 8192, C, TAPS, 8, 6) \
        == 14 * one["forward"] + 8 * one["backward"]
    # the same as the program's own plan says
    from horovod_tpu import profile
    assert profile.sconv_plan(2, 8192, C, TAPS)["bytes"] == one


def test_flash_counts_follow_the_plans_tiles():
    Plan = namedtuple("Plan", "tiles_visited block_q block_k")
    plans = {"hvd_flash_fwd": Plan(2176, 2048, 512),
             "hvd_flash_bwd": Plan(2176, 2048, 512)}
    tile = 2.0 * 2048 * 512 * D
    assert fl.flash_executed_flops(plans, D) == (2 + 5) * 2176 * tile
    q_like, kv_like, rows = (2 * H * LENGTH * D * 2, 2 * G * LENGTH * D * 2,
                             2 * H * LENGTH * 4)
    assert fl.flash_min_bytes(plans, 2, H, G, LENGTH, D) \
        == 5 * q_like + 6 * kv_like + 3 * rows
