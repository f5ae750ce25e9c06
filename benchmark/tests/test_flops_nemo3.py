"""`flops_nemo3` against hand counts at the rehearse size and against the
published model's size."""

import pytest

from benchmark import flops, flops_nemo3 as fn

# the rehearse size: hidden 64; Mamba-2 8 heads x 16 in 2 groups, state 16,
# 4 taps; attention 4 heads on 2 of 16; 32 experts, 4 held, latent 32,
# width 48, shared 96; vocabulary 512
HIDDEN, VOCAB, LENGTH, CHUNK, TOP_K = 64, 512, 128, 32, 3
SSM = (8, 16, 2, 16, 4)
ATTN = (4, 2, 16)
MOE = (32, 4, 32, 48, 96)
PATTERN = ("ssm", "attn", "moe")


def test_params_by_hand():
    ssm = (64 * (128 + 128 + 2 * 2 * 16 + 8) + 128 * 64   # the projections
           + 5 * (128 + 64)                                 # taps and bias
           + 3 * 8 + 128 + 64)                              # vectors, norms
    attn = 2 * 64 * 64 + 2 * 64 * 32 + 64
    moe = (64 * 32 + 32 + 2 * 64 * 32 + 2 * 64 * 96
           + 4 * 2 * 32 * 48 + 64)
    assert fn.mamba2_params(HIDDEN, *SSM) == ssm
    assert fn.params(PATTERN, HIDDEN, VOCAB, SSM, ATTN, MOE) \
        == ssm + attn + moe + 2 * 512 * 64 + 64


def test_the_published_model_is_120_billion():
    """The issue's sum: 40 M layers, 8 attention layers, 40 routed layers
    with all 512 experts, the whole vocabulary."""
    ssm = (128, 64, 8, 128, 4)
    m = fn.mamba2_params(4096, *ssm)
    a = fn.attention_matmul_params(4096, 32, 2, 128) + 4096
    e = fn.latent_moe_shared_params(4096, 512, 1024, 5376) + 512 + 4096
    assert round(m / 1e6, 2) == 109.64
    assert round(a / 1e6, 2) == 35.66
    assert round(e / 1e6, 2) == 54.53
    assert round(fn.expert_params(1024, 2688) / 1e6, 3) == 5.505
    total = fn.params(("ssm",) * 40 + ("attn",) * 8 + ("moe",) * 40, 4096,
                      131072, ssm, (32, 2, 128),
                      (512, 512, 1024, 2688, 5376))
    assert round(total / 1e9, 2) == 120.67
    # one period as one rank holds it, heads whole, and at the 2-way share
    period = ("ssm", "moe") * 4 + ("ssm", "attn", "moe")
    whole = fn.params(period, 4096, 16384, ssm, (32, 2, 128),
                      (512, 8, 1024, 2688, 5376))
    half = fn.params(period, 4096, 16384, (64, 64, 4, 128, 4), (16, 1, 128),
                     (512, 8, 1024, 2688, 5376))
    assert round(whole / 1e6, 1) == 1210.9
    assert half == 919_015_872


def test_the_scans_products_by_hand():
    H, P, G, N, _ = SSM
    inside = 2 * LENGTH * CHUNK * G * N + 2 * LENGTH * CHUNK * H * P
    between = 2 * 2 * LENGTH * H * P * N
    assert fn.ssd_forward_flops(LENGTH, H, P, G, N, CHUNK) \
        == inside + between
    assert fn.ssd_forward_flops(LENGTH, H, P, G, N, CHUNK, causal=True) \
        == inside / 2 + between
    assert fn.ssd_min_bytes(LENGTH, H, P, G, N) \
        == (2 * LENGTH * H * P + 2 * LENGTH * G * N) * 2 + LENGTH * H * 4


def test_model_flops_per_token_by_hand():
    H, P, G, N, _ = SSM
    ssm = 6 * (64 * (128 + 128 + 64 + 8) + 128 * 64) \
        + 3 * fn.ssd_forward_flops(LENGTH, H, P, G, N, CHUNK, True) / LENGTH
    attn = 6 * (2 * 64 * 64 + 2 * 64 * 32) \
        + 6 * flops.attention_matmul_flops(1, 4, LENGTH, 16) / LENGTH
    moe = 6 * (64 * 32 + 2 * 64 * 32 + 2 * 64 * 96
               + TOP_K * 4 / 32 * 2 * 32 * 48)
    got = fn.model_flops_per_token(PATTERN, HIDDEN, VOCAB, LENGTH, SSM,
                                   ATTN, MOE, TOP_K, CHUNK)
    assert got == pytest.approx(ssm + attn + moe + 6 * 64 * 512, rel=1e-12)


def test_the_experts_six_matmuls():
    rows = TOP_K * LENGTH * 4 / 32
    assert fn.ungated_experts_flops(rows, 32, 48) == 6 * 2 * rows * 32 * 48
    assert fn.ungated_experts_min_bytes(rows, 32, 48, 4, 2, 4) \
        == 6 * ((rows * 32 + rows * 48) * 2 + 4 * 32 * 48 * 4)
