"""`flops_kimi` against hand sums at the published widths: a KDA mixer's
parameters (the issue's 39.52 M), the layers' and the whole count (903.5 M,
10.10 GiB at 12 bytes), a token's model operations, and the chunked
recurrence's operations and bytes from its shapes at a small size."""

import pytest

from benchmark import flops, flops_kimi as fk

KINDS = ("kda", "kda", "kda", "full") * 2
SIZES = (2304, 32, 128, 4, 512, 128, 64, 128, 9216, 1024, 1024, 8, 256)


def test_a_mixers_parameters_by_hand():
    inner = 32 * 128
    matmul = 2304 * (3 * inner + 2 * 128 + 32) + 2 * 128 * inner \
        + inner * 2304
    assert fk.kda_matmul_params(2304, 32, 128) == matmul == 39_460_864
    assert fk.kda_params(2304, 32, 128, 4) \
        == matmul + 3 * 4 * inner + 32 + inner + 128 == 39_514_272
    assert fk.kda_params(2304, 32, 128, 4) / 1e6 == pytest.approx(39.52,
                                                                  abs=0.01)


def test_the_whole_count_by_hand():
    kda = 39_514_272
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 2304 + 512
    assert latent == 29_114_880
    routed = 2 * 2304 + 2304 * 256 + 256 + 3 * 2304 * 1024 \
        + 8 * 3 * 2304 * 1024
    dense = 2 * 2304 + 3 * 2304 * 9216
    assert fk.ffn_params(2304, expert_width=1024, shared_width=1024, held=8,
                         experts=256) == routed == 64_295_680
    assert fk.ffn_params(2304, dense_width=9216) == dense
    whole = fk.params(*SIZES, 20480, KINDS, 1)
    assert whole == 6 * kda + 2 * latent + dense + 7 * routed \
        + 2 * 20480 * 2304 + 2304 == 903_464_896
    assert whole * 12 / 2 ** 30 == pytest.approx(10.097, abs=1e-3)
    # rung 2 of the memory rule: 1 + 4 layers, KKKFK
    assert fk.params(*SIZES, 20480, KINDS[:5], 1) == 602_434_432


def test_model_flops_a_token():
    got = fk.model_flops_per_token(
        2304, 32, 128, 512, 128, 64, 128, 9216, 1024, 1024, 8, 256, 8, 20480,
        KINDS, 1, 8192)
    kda, latent = 39_460_864, 29_114_880 - 512
    routed = 2304 * 256 + 3 * 2304 * 1024 + 8 * 8 / 256 * 3 * 2304 * 1024
    dense = 3 * 2304 * 9216
    matmul = 6 * kda + 2 * latent + dense + 7 * routed + 2304 * 20480
    # 4096 keys on average x 32 heads x (192 + 128) x 2, forward + 2 backward
    attention = 2 * 3 * 4096 * 32 * 320 * 2
    # the issue's 2 x 128 x 128 x 3 a head and token, forward + 2 backward
    recurrence = 6 * 3 * (2 * 128 * 128 * 3) * 32
    assert fk.kda_recurrence_flops_per_token(32, 128, 128) \
        == 2 * 128 * 128 * 3 * 32
    assert got == pytest.approx(6 * matmul + attention + recurrence)
    assert flops.attention_matmul_flops(1, 32, 8192, 192) / 8192 \
        == 4096 * 32 * 192 * 2


def test_the_chunked_form_from_its_shapes():
    # one head, two chunks of 4 tokens, q and k 8 wide, v 2 wide
    C, D, Dv = 4, 8, 2
    a_chunk = (2 * 2 * C * C * D          # q k^T and k k^T
               + C * C * (D + Dv)         # the solve's triangle
               + 3 * 2 * C * D * Dv       # W S, (Q e^G) S, K^T V'
               + 2 * C * C * Dv)          # lower(q k^T) V'
    assert fk.kda_chunk_forward_flops(1, 8, 1, D, Dv, C) == 2 * a_chunk
    # three heads, two sequences
    assert fk.kda_chunk_forward_flops(2, 8, 3, D, Dv, C) == 12 * a_chunk
    # at the cell's shape: 43 GFLOP a layer and pass
    cell = fk.kda_chunk_forward_flops(1, 8192, 32, 128, 128, 64)
    assert cell == 4096 * (4 * 64 * 64 * 128 + 64 * 64 * 256
                           + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert cell / 1e9 == pytest.approx(42.9, abs=0.1)


def test_least_bytes_of_a_pass():
    rows = 2 * 8 * 3
    fwd = rows * ((2 * 8 + 2) * 2 + 4 * 8 + 4) + rows * 2 * 4
    assert fk.kda_chunk_min_bytes(2, 8, 3, 8, 2) == fwd
    # backward: every input and o's gradient read, five gradients written
    assert fk.kda_chunk_min_bytes(2, 8, 3, 8, 2, backward=True) \
        == 2 * rows * ((2 * 8 + 2) * 2 + 4 * 8 + 4) + rows * 2 * 4
    cell = fk.kda_chunk_min_bytes(1, 8192, 32, 128, 128)
    assert cell == 262144 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 4)


def test_the_kernels_terms_and_bytes():
    # two heads, 32 tokens in sub-blocks of 16, 128 wide
    terms = 2 * 32 * 16 * 128
    assert fk.kda_scores_flops(1, 32, 2, 128, 16) == 5 * terms
    assert fk.kda_scores_flops(1, 32, 2, 128, 16, backward=True) == 10 * terms
    rows = 2 * 32
    assert fk.kda_scores_min_bytes(1, 32, 2, 128, 16) \
        == rows * 128 * 8 + 2 * rows * 16 * 4
    assert fk.kda_scores_min_bytes(1, 32, 2, 128, 16, backward=True) \
        == 2 * rows * 128 * 8 + 2 * rows * 16 * 4
    # at the cell's shape the bytes bind: 0.25 GiB against 2.7 GFLOP
    assert fk.kda_scores_min_bytes(1, 8192, 32, 128, 16) / 819e9 \
        > 10 * fk.kda_scores_flops(1, 8192, 32, 128, 16) / 197e12
