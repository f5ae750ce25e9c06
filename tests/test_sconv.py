"""The pass between the projections of a double-gated short convolution
(`ops/sconv.py::gated_conv`: u = B * z, causal taps, y = G * c) against the
explicit sum over three shifted copies, forward and backward, at lengths
that are and are not a block's multiple and at batch 2; causality; the plan
that says which path a call takes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.ops import sconv


def _shifted_copies(proj, w):
    """y = G * sum_j w[j] * (B z)[t - (taps - 1 - j)], one batch row at a
    time, each shifted copy written out: zeros, then the sequence's start."""
    taps, C = w.shape
    out = []
    for row in proj.astype(jnp.float32):
        b, g, z = row[:, :C], row[:, C:2 * C], row[:, 2 * C:]
        u = b * z
        c = sum(w[j] * jnp.concatenate(
            [jnp.zeros((taps - 1 - j, C)), u[:u.shape[0] - (taps - 1 - j)]])
            for j in range(taps))
        out.append(g * c)
    return jnp.stack(out)


def _operands(B, L, C, taps, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, L, 3 * C)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (taps, C)),
            jax.random.normal(ks[2], (B, L, C)).astype(dtype))


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


# 1024 is `kda_conv.BLOCK_ROWS`, the block a kernel would take: 48 and 1100
# are no multiple of it or of a sublane tile's 16 rows; 4 taps too.
@pytest.mark.parametrize("B,L,C,taps", [
    (1, 48, 128, 3), (2, 128, 256, 3), (2, 1100, 128, 3), (2, 1024, 128, 3),
    (2, 37, 64, 4), (1, 8, 128, 1)])
def test_forward_and_backward_are_the_three_shifted_copies(B, L, C, taps):
    proj, w, cot = _operands(B, L, C, taps, jnp.float32)
    got, vjp = jax.vjp(sconv.gated_conv, proj, w)
    want, ref_vjp = jax.vjp(_shifted_copies, proj, w)
    _close(got, want, 1e-6)
    for g, r in zip(vjp(cot), ref_vjp(cot)):
        _close(g, r, 1e-5)


def test_bf16_operands_are_widened_and_rounded_once():
    """The two products and the tap sum are f32 from the widened input: the
    result is the f32 sum's rounding, to half a bf16 ulp."""
    proj, w, _ = _operands(2, 64, 128, 3, jnp.bfloat16, seed=1)
    got = sconv.gated_conv(proj, w)
    assert got.dtype == jnp.bfloat16
    want = _shifted_copies(proj, w)
    assert jnp.array_equal(got, want.astype(jnp.bfloat16))


def test_no_tap_reaches_across_the_batch():
    """Row 1 of a batch of two is what it is alone: the taps before a
    sequence's start read zeros, not the row before."""
    proj, w, _ = _operands(2, 40, 128, 3, jnp.float32, seed=2)
    both = sconv.gated_conv(proj, w)
    assert jnp.array_equal(both[1], sconv.gated_conv(proj[1:], w)[0])
    assert jnp.array_equal(both[0], sconv.gated_conv(proj[:1], w)[0])


@pytest.mark.parametrize("t", [0, 1, 17, 39])
def test_the_pass_is_causal(t):
    """Changing token t moves no output before t, and moves output t."""
    proj, w, _ = _operands(2, 40, 128, 3, jnp.float32, seed=3)
    moved = proj.at[:, t].add(1.0)
    a, b = sconv.gated_conv(proj, w), sconv.gated_conv(moved, w)
    assert jnp.array_equal(a[:, :t], b[:, :t])
    assert not jnp.array_equal(a[:, t], b[:, t])
    # and reaches taps - 1 tokens ahead, no further
    assert jnp.array_equal(a[:, t + 3:], b[:, t + 3:])


def test_the_last_tap_meets_the_current_token():
    """With the taps (0, 0, 1) the convolution is the identity: y = G B z."""
    proj, _, _ = _operands(1, 16, 128, 3, jnp.float32, seed=4)
    w = jnp.zeros((3, 128)).at[2].set(1.0)
    C = 128
    _close(sconv.gated_conv(proj, w),
           proj[..., C:2 * C] * proj[..., :C] * proj[..., 2 * C:], 1e-6)
    # and with (1, 0, 0) it reads two tokens behind
    w = jnp.zeros((3, 128)).at[0].set(1.0)
    u = proj[..., :C] * proj[..., 2 * C:]
    _close(sconv.gated_conv(proj, w)[:, 2:],
           proj[:, 2:, C:2 * C] * u[:, :-2], 1e-6)
    assert not jnp.any(sconv.gated_conv(proj, w)[:, :2])


def test_the_plan_says_the_path_and_a_one_pass_forms_bytes():
    plan = hvd.profile.sconv_plan(2, 8192, 2048, 3, jnp.bfloat16)
    assert plan == sconv.gate_plan(2, 8192, 2048, 3, jnp.bfloat16)
    assert plan["path"] == "jnp"
    cells = 2 * 8192 * 2048
    # 8 bytes a token and channel forward, 14 backward, and the taps
    assert plan["bytes"]["forward"] == 8 * cells + 3 * 2048 * 4
    assert plan["bytes"]["backward"] == 14 * cells + 2 * 3 * 2048 * 4


def test_columns_that_are_no_three_blocks_are_refused():
    with pytest.raises(ValueError, match="three blocks"):
        sconv.gated_conv(jnp.zeros((1, 8, 100)), jnp.zeros((3, 32)))
