"""`flops_mellum`: the visible pairs by the formula against the dense masks,
`mfu`'s count against a hand count, the issue's sums, and the executed count
of each kind against the tiles of its plan."""

import numpy as np
import pytest

from benchmark import flops_mellum

SIZES = (2304, 32, 4, 128, 896, 64, 16)  # hidden .. experts, held
KINDS = ("window", "window", "window", "full") * 2


def dense_pairs(length, window):
    i = np.arange(length)[:, None]
    j = np.arange(length)[None, :]
    return int(np.sum((j <= i) & (i - j < window)))


@pytest.mark.parametrize("length,window", [(64, 8), (256, 100), (128, 128),
                                           (96, 1), (64, 1000)])
def test_visible_pairs_is_the_dense_masks_count(length, window):
    """Under the window exactly but for the first triangle's diagonal
    surplus (`flops.py`'s convention: L^2 / 2, not L (L + 1) / 2)."""
    w = min(window, length)
    assert flops_mellum.visible_pairs(length, window) \
        == dense_pairs(length, window) - w / 2.0


def test_the_programs_rule_is_the_same_mask():
    from horovod_tpu.ops import BandMask

    at = np.arange(128)
    for window in (1, 40, 128, 500):
        assert int(np.sum(BandMask(window).visible(
            at[:, None], at[None, :], np))) == dense_pairs(128, window)


def test_model_flops_are_the_issues_sums():
    """Published widths, 16 of 64 experts held, a fourth of the vocabulary,
    two periods, 8192 tokens under a window of 1024: a step's operations by
    part, forward and backward (the issue's arithmetic at 6 a pair where it
    counts the kernels' 7)."""
    hidden, heads, kv, d, width, experts, held = SIZES
    attention = 2304 * 128 * (32 + 4 + 4 + 32)        # q k v o: 21.23 M
    assert attention == 21_233_664
    experts_met = 8 * 16 / 64 * 3 * 2304 * 896        # two experts expected
    assert experts_met == 2 * 6_193_152
    position = attention + 2304 * 64 + experts_met
    window_pairs = 1024 ** 2 / 2 + (8192 - 1024) * 1024
    full_pairs = 8192 ** 2 / 2
    want = (6 * (8 * position + 2304 * 24576)
            + 6 * 2 * 32 * 128 * (6 * window_pairs + 2 * full_pairs) / 8192)
    got = flops_mellum.model_flops_per_token(*SIZES, 8, 24576, KINDS, 8192,
                                             1024)
    assert got == pytest.approx(want, rel=1e-12)
    step = 8192 * got
    assert 6 * 8192 * attention == pytest.approx(1.04e12, rel=5e-3)
    assert 6 * 8192 * experts_met == pytest.approx(0.61e12, rel=5e-3)
    assert 6 * 8192 * 2304 * 24576 == pytest.approx(2.78e12, rel=2e-3)
    # a window layer's pairs are 23.4% of a full layer's at 8192 (43.7% at
    # 4096: hence the length)
    assert window_pairs / full_pairs == pytest.approx(0.234, abs=1e-3)
    assert flops_mellum.visible_pairs(4096, 1024) / (4096 ** 2 / 2) \
        == pytest.approx(0.4375)
    assert 20e12 < step < 23e12   # the issue's 22.6 counts the kernels' 7


def test_params_is_the_issues_sum():
    for layers, millions in ((4, 595.2), (8, 1077.1)):
        assert flops_mellum.params(*SIZES, 24576, layers) / 1e6 \
            == pytest.approx(millions, abs=0.1)


def test_executed_flops_are_the_visited_tiles_by_kind():
    """The cell's plans: a window layer's kernels visit a third of a full
    layer's tiles at the table's blocks, above the pairs' 23.4% (a cut tile
    is computed whole)."""
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.ops import BandMask

    def plans(rule):
        return {n: p for b in (False, True) for n, p in profile.flash_plan(
            1, 32, 8192, 128, 8, jnp.bfloat16, b, mask=rule).items()}

    window, full = plans(BandMask(1024)), plans(BandMask(8192))
    for by_kernel in (window, full):
        assert sorted(by_kernel) == ["hvd_flash_bwd", "hvd_flash_fwd"]
        for name, p in by_kernel.items():
            tile = 2.0 * p.block_q * p.block_k * 128
            matmuls = {"hvd_flash_fwd": 2, "hvd_flash_bwd": 5}[name]
            assert flops_mellum.flash_executed_flops({name: p}, 128) \
                == matmuls * p.tiles_visited * tile
    ratio = flops_mellum.flash_executed_flops(window, 128) \
        / flops_mellum.flash_executed_flops(full, 128)
    assert 0.234 < ratio < 0.40
    # the triangle by its tiles is a little over L^2 / 2 by the formula
    from benchmark import flops
    by_formula = flops.flash_executed_flops(sorted(full), 1, 32, 8192, 128)
    assert 1.0 < flops_mellum.flash_executed_flops(full, 128) / by_formula \
        < 1.1
