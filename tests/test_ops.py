"""Pallas kernel tests. The kernel itself runs in interpret mode on the
CPU backend (exactly the code path the TPU compiles); numerical ground
truth is dense attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")


def _dense(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D ** -0.5
    if causal:
        L = s.shape[-1]
        mask = np.tril(np.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# `vmem_budget` values that force `flash_plan` down one path whatever the
# shape: nothing fits 0 (gridded), everything fits 2**40 (resident).
_PATHS = {"gridded": 0, "resident": 2 ** 40}
_path = pytest.mark.parametrize("path", sorted(_PATHS))


def _rand_qkv(B, L, H, D, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
                 for _ in range(3))


@_path
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_interpret_matches_dense(causal, path):
    from horovod_tpu.ops.flash_attention import _pallas_forward_lse
    B, L, H, D = 2, 256, 2, 64  # L multiple of BLOCK_Q=128
    q, k, v = _rand_qkv(B, L, H, D)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _pallas_forward_lse(
        qt, kt, vt, D ** -0.5, causal, interpret=True,
        vmem_budget=_PATHS[path])[0].transpose(0, 2, 1, 3)
    expected = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_fallback_and_grads():
    """Public API on CPU uses the blockwise fallback; values and grads
    must match dense attention."""
    from horovod_tpu.ops import flash_attention
    B, L, H, D = 1, 64, 1, 8
    q, k, v = _rand_qkv(B, L, H, D, seed=3)

    out = flash_attention(q, k, v, causal=True)
    expected = _dense(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4)


@_path
@pytest.mark.parametrize("bq,bk", [(256, 512), (128, 128), (512, 256)])
def test_flash_kernel_block_shapes_interpret(bq, bk, path):
    """(256, 512): the production default's unequal q/k tiling, where
    every visible causal block straddles the diagonal. (128, 128): equal
    tiling at L=512 has fully-below-diagonal blocks, exercising the
    mask-skip (straddles=False) branch the default tiling never hits —
    on the resident path, the unmasked loop before the peel. (512, 256):
    a q block over two k blocks, so the resident peel is two blocks."""
    from horovod_tpu.ops.flash_attention import _pallas_forward_lse
    B, L, H, D = 1, 512, 1, 32
    q, k, v = _rand_qkv(B, L, H, D, seed=7)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _pallas_forward_lse(
        qt, kt, vt, D ** -0.5, True, interpret=True, block_q=bq,
        block_k=bk, vmem_budget=_PATHS[path])[0].transpose(0, 2, 1, 3)
    expected = _dense(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,bq,bk", [
    (True, None, None),    # production tiling via the public custom-VJP
    (False, None, None),
    (True, 128, 128),      # equal tiling: exercises straddles=False in
                           # both backward kernels (fully-visible blocks)
])
def test_flash_pallas_backward_interpret(causal, bq, bk):
    """The Pallas backward kernels (dQ / dK+dV, used on TPU) must match
    dense-attention gradients; exercised in interpret mode."""
    from horovod_tpu.ops.flash_attention import (
        _flash, _pallas_backward, _pallas_forward_lse)
    B, L, H, D = 1, 512, 1, 32
    q, k, v = _rand_qkv(B, L, H, D, seed=11)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    w = jnp.asarray(np.random.RandomState(12).randn(B, H, L, D),
                    jnp.float32)

    if bq is None:
        def loss_flash(qt, kt, vt):
            return jnp.sum(_flash(qt, kt, vt, D ** -0.5, causal, True) * w)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(qt, kt, vt)
    else:
        out, lse = _pallas_forward_lse(qt, kt, vt, D ** -0.5, causal,
                                       True, block_q=bq, block_k=bk)
        g_flash = _pallas_backward(qt, kt, vt, out, lse, w, D ** -0.5,
                                   causal, True, block_q=bq, block_k=bk)

    def loss_dense(q, k, v):
        return jnp.sum(
            _dense(q, k, v, causal).transpose(0, 2, 1, 3) * w)

    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd.transpose(0, 2, 1, 3)),
            rtol=2e-4, atol=2e-4)


def _k_held_bytes(B, H, L, D, group, dtype, block_q=None, block_k=None):
    """What the one-kernel backward holds in its first form, held by the k
    block: what `flash_plan` chooses where everything fits."""
    from horovod_tpu.ops.flash_attention import flash_plan
    fused = flash_plan(B, H, L, D, group, dtype, True, block_q, block_k,
                       2 ** 40)
    assert {n: p.held for n, p in fused.items()} == {"hvd_flash_bwd": "k"}
    return fused["hvd_flash_bwd"].resident_bytes


def _split_budget(monkeypatch, *call):
    """A `vmem_budget` one byte short of what the one-kernel backward
    holds by the k block, with its second form (held by the q block, which
    holds less at these shapes) out of the order `flash_plan` tries:
    the backward's two kernels, resident (each holds less)."""
    import importlib
    # the module: `ops` hands out the function under the same name
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_BWD_HELD", ("k",))
    return _k_held_bytes(*call) - 1


@pytest.mark.parametrize("causal,H,G,bqp,bk", [
    (True, 4, 4, 256, 512),   # group 1; peel: 1 block fwd/dQ, 2 dK/dV
    (True, 4, 4, 512, 256),   # peel: 2 blocks fwd/dQ, 1 dK/dV
    (False, 4, 4, 256, 512),  # not causal: one loop, no peel
    (False, 4, 2, 512, 256),
    (True, 4, 2, 256, 512),   # group 2
    (True, 4, 2, 512, 256),
    (True, 4, 1, 512, 256),   # group 4 (MQA)
    (True, 4, 1, 256, 512),
    (True, 4, 4, 128, 128),   # equal blocks: the longest loops
    (True, 3, 1, 256, 512),   # group 3, bqp < bk
    (True, 3, 1, 512, 256),   # group 3, bqp > bk
    (False, 6, 2, 256, 512),  # group 3 of two kv heads, not causal
])
def test_flash_resident_path_interpret(monkeypatch, causal, H, G, bqp, bk):
    """The resident kernels (k/v, or q/dO/lse/delta, whole in VMEM and
    walked by a loop inside the kernel): out, dQ, dK and dV against dense
    attention, and against the gridded kernels on the same blocks, which
    visit the same tiles in the same order with the same arithmetic. The
    backward three ways: one kernel (`hvd_flash_bwd`, what everything
    fitting chooses), the two resident kernels (a budget the one does not
    fit), the two gridded ones."""
    from horovod_tpu.ops.flash_attention import (
        _pallas_backward, _pallas_forward_lse, flash_plan)
    B, L, D = 1, 1024, 32
    group = H // G
    q, k, v = _rand_gqa(B, L, H, G, D, seed=21)
    w = jnp.asarray(np.random.RandomState(22).randn(B, L, H, D),
                    jnp.float32)
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    budgets = dict(_PATHS, split=_split_budget(
        monkeypatch, B, H, L, D, group, q.dtype, bqp * group, bk))
    names = {"gridded": ["hvd_flash_dq", "hvd_flash_dkv"],
             "split": ["hvd_flash_dq", "hvd_flash_dkv"],
             "resident": ["hvd_flash_bwd"]}
    got = {}
    for path, budget in budgets.items():
        for backward in (False, True):
            plans = flash_plan(B, H, L, D, group, q.dtype, backward,
                               bqp * group, bk, budget)
            assert {p.path for p in plans.values()} == {
                "gridded" if path == "gridded" else "resident"}
            if backward:
                assert list(plans) == names[path]
        out, lse = _pallas_forward_lse(
            t(q), t(k), t(v), D ** -0.5, causal, True, bqp * group, bk,
            budget)
        got[path] = (out,) + _pallas_backward(
            t(q), t(k), t(v), out, lse, t(w), D ** -0.5, causal, True,
            bqp * group, bk, budget)
    dense = lambda q, k, v: _dense_gqa(q, k, v, causal)  # noqa: E731
    want = (dense(q, k, v),) + jax.grad(
        lambda q, k, v: jnp.sum(dense(q, k, v) * w),
        argnums=(0, 1, 2))(q, k, v)
    for r, s, g, d, nm in zip(got["resident"], got["split"], got["gridded"],
                              want, ("out", "dq", "dk", "dv")):
        tol = 2e-5 if nm == "out" else 2e-4
        np.testing.assert_allclose(np.asarray(t(r)), np.asarray(d),
                                   rtol=tol, atol=tol, err_msg=nm)
        for other in (s, g):
            np.testing.assert_allclose(np.asarray(r), np.asarray(other),
                                       rtol=1e-6, atol=1e-6, err_msg=nm)


@pytest.mark.parametrize("other", ["gridded", "split"])
def test_flash_resident_equals_gridded_in_bf16(monkeypatch, other):
    """bf16 inputs, as the models feed them: under the default budget the
    shape goes down the resident path, its backward one kernel; under a
    budget it does not fit, down the gridded one; under a budget the
    one-kernel backward alone does not fit, the two resident backward
    kernels. They agree to bf16 rounding (2^-8 relative) in the output
    and all three gradients."""
    from horovod_tpu.ops.flash_attention import (
        _pallas_backward, _pallas_forward_lse, flash_plan)
    B, L, H, D = 1, 1024, 2, 64
    bf16 = jnp.bfloat16
    q, k, v = (x.transpose(0, 2, 1, 3).astype(bf16)
               for x in _rand_qkv(B, L, H, D, seed=31))
    w = jnp.asarray(np.random.RandomState(32).randn(B, H, L, D), bf16)
    budget = (2 ** 18 if other == "gridded"
              else _split_budget(monkeypatch, B, H, L, D, 1, bf16))
    got = []
    for budget, path, kernels in (
            (None, "resident", 1),
            (budget, "gridded" if other == "gridded" else "resident", 2)):
        kw = {} if budget is None else {"vmem_budget": budget}
        for backward in (False, True):
            plans = flash_plan(B, H, L, D, 1, bf16, backward, **kw)
            assert {p.path for p in plans.values()} == {path}
            assert len(plans) == (kernels if backward else 1)
        out, lse = _pallas_forward_lse(q, k, v, D ** -0.5, True, True,
                                       **kw)
        got.append((out,) + _pallas_backward(q, k, v, out, lse, w,
                                             D ** -0.5, True, True, **kw))
    for r, g, nm in zip(got[0], got[1], ("out", "dq", "dk", "dv")):
        r, g = (np.asarray(x, np.float32) for x in (r, g))
        assert np.max(np.abs(r - g)) <= 2 ** -8 * np.max(np.abs(g)), nm


def _q_held_budget(B, H, L, D, group, dtype, block_q=None, block_k=None):
    """A `vmem_budget` under which `flash_plan` takes the one-kernel
    backward's SECOND form, held by the q block: one byte short of what the
    first holds (q, dO, dQ, lse, delta and dQ's f32 accumulator, of the kv
    head's whole query group), which is more than the second's k, v, dk, dv
    and two accumulators at every shape here."""
    from horovod_tpu.ops.flash_attention import flash_plan
    budget = _k_held_bytes(B, H, L, D, group, dtype, block_q, block_k) - 1
    plans = flash_plan(B, H, L, D, group, dtype, True, block_q, block_k,
                       budget)
    assert {n: (p.path, p.held) for n, p in plans.items()} == {
        "hvd_flash_bwd": ("resident", "q")}, plans
    bwd = plans["hvd_flash_bwd"]
    assert bwd.grid == (B * H // group, L * group // bwd.block_q)
    return budget


@pytest.mark.parametrize("causal,H,G,bqp,bk", [
    (True, 4, 4, 256, 512),   # group 1; a k block of two q blocks
    (False, 4, 4, 512, 256),  # not causal: one loop, no peel
    (True, 4, 2, 256, 512),   # group 2
    (True, 4, 2, 512, 256),   # a q block of two k blocks
    (False, 4, 2, 128, 128),
    (True, 8, 1, 128, 512),   # group 8, the block-diffusion cell's
    (True, 8, 1, 256, 128),
    (False, 8, 1, 128, 256),
])
def test_flash_bwd_held_by_the_q_block_interpret(causal, H, G, bqp, bk):
    """The one-kernel backward's second form (k, v, dk and dv whole in VMEM,
    a q block a grid step, dQ carried by the loop, dK and dV summed in two
    f32 accumulators in scratch): dq, dk and dv against the gradient of
    `_blockwise_reference`, and against the gridded two kernels on the same
    inputs and blocks, which add the same tiles to a q block's and a k
    block's sums in the same order."""
    from horovod_tpu.ops.flash_attention import (
        _blockwise_reference, _pallas_backward, _pallas_forward_lse)
    B, L, D = 1, 1024, 32
    group = H // G
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = (t(x) for x in _rand_gqa(B, L, H, G, D, seed=41))
    w = jnp.asarray(np.random.RandomState(42).randn(B, H, L, D),
                    jnp.float32)
    budgets = {"gridded": 0, "q-held": _q_held_budget(
        B, H, L, D, group, q.dtype, bqp * group, bk)}
    got = {}
    for path, budget in budgets.items():
        out, lse = _pallas_forward_lse(q, k, v, D ** -0.5, causal, True,
                                       bqp * group, bk, budget)
        got[path] = _pallas_backward(q, k, v, out, lse, w, D ** -0.5,
                                     causal, True, bqp * group, bk, budget)
    _, vjp = jax.vjp(lambda q, k, v: _blockwise_reference(
        q, k, v, D ** -0.5, causal), q, k, v)
    for r, g, d, nm in zip(got["q-held"], got["gridded"], vjp(w),
                           ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(r), np.asarray(d), rtol=2e-4,
                                   atol=2e-4, err_msg=nm)
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-6,
                                   atol=1e-6, err_msg=nm)


@pytest.mark.parametrize("H,G", [(4, 2), (8, 1), (6, 2)])
def test_flash_bwd_held_by_the_q_block_equals_gridded_in_bf16(H, G):
    """bf16 inputs, as the models feed them, on the plan's own blocks: dQ,
    dK and dV of the q-held one kernel and of the gridded two agree to bf16
    rounding (both sum a block's tiles in f32 and round once)."""
    from horovod_tpu.ops.flash_attention import (
        _pallas_backward, _pallas_forward_lse)
    B, L, D = 1, 1024, 64
    bf16 = jnp.bfloat16
    q, k, v = (x.transpose(0, 2, 1, 3).astype(bf16)
               for x in _rand_gqa(B, L, H, G, D, seed=43))
    w = jnp.asarray(np.random.RandomState(44).randn(B, H, L, D), bf16)
    got = []
    for budget in (0, _q_held_budget(B, H, L, D, H // G, bf16)):
        out, lse = _pallas_forward_lse(q, k, v, D ** -0.5, True, True,
                                       vmem_budget=budget)
        got.append(_pallas_backward(q, k, v, out, lse, w, D ** -0.5, True,
                                    True, vmem_budget=budget))
    for g, r, nm in zip(got[0], got[1], ("dq", "dk", "dv")):
        g, r = (np.asarray(x, np.float32) for x in (g, r))
        assert np.max(np.abs(r - g)) <= 2 ** -8 * np.max(np.abs(g)), nm


# B, H, L, D of the benchmark's cells: `lm1b4_1chip` and `lm1b4_dp4` (2
# sequences of 2048 a chip) and `olmoe1b7_1chip` and `ouro2b6_1chip` (one
# of 4096), 16 heads x 128, bf16, group 1. Expected: blocks, grid, path.
@pytest.mark.parametrize("B,L,expected", [
    (2, 2048, {"hvd_flash_fwd": (512, 512, (32, 4)),
               "hvd_flash_bwd": (512, 1024, (32, 2))}),
    (1, 4096, {"hvd_flash_fwd": (512, 512, (16, 8)),
               "hvd_flash_bwd": (512, 1024, (16, 4))}),
])
def test_flash_plan_benchmark_shapes_are_resident(B, L, expected):
    """`flash_plan` alone: the benchmark's shapes choose the resident
    path with one grid step per (batch*head, block) — the gridded grid
    had a third axis — the whole backward one kernel, and the VMEM sum
    each reports is under the limit it sets."""
    from horovod_tpu.ops.flash_attention import (RESIDENT_VMEM_BUDGET,
                                                 flash_plan)
    H, D = 16, 128
    plans = {**flash_plan(B, H, L, D, 1, jnp.bfloat16),
             **flash_plan(B, H, L, D, 1, jnp.bfloat16, backward=True)}
    assert sorted(plans) == sorted(expected)
    for name, (bq, bk, grid) in expected.items():
        plan = plans[name]
        assert (plan.path, plan.block_q, plan.block_k, plan.grid) == (
            "resident", bq, bk, grid), (name, plan)
        assert plan.grid_steps == grid[0] * grid[1]
        assert 0 < plan.resident_bytes <= RESIDENT_VMEM_BUDGET
        assert plan.resident_bytes < plan.vmem_bytes < plan.vmem_limit_bytes
    # k + v, bf16, two buffers; the backward: q + dO + dQ's block and the
    # two 8-wide f32 stripes padded to 128 lanes, two buffers, and dQ's f32
    # accumulator, one.
    assert plans["hvd_flash_fwd"].resident_bytes == 2 * 2 * L * D * 2
    assert plans["hvd_flash_bwd"].resident_bytes == 2 * (
        3 * L * D * 2 + 2 * L * 128 * 4) + L * D * 4


# (B, L) a chip of the four LM cells, then lengths past what the
# one-kernel backward holds in 24 MiB by the k block (D=128, bf16: 8 MiB at
# 2048, 16 at 4096, 32 at 8192, where it holds 24 by the q block) and by
# either, then a budget nothing fits. Expected: {kernel: path}.
@pytest.mark.parametrize("B,L,budget,expected", [
    pytest.param(2, 2048, None, {"hvd_flash_bwd": "resident"},
                 id="lm1b4_1chip"),
    pytest.param(2, 2048, None, {"hvd_flash_bwd": "resident"},
                 id="lm1b4_dp4"),
    pytest.param(1, 4096, None, {"hvd_flash_bwd": "resident"},
                 id="olmoe1b7_1chip"),
    pytest.param(1, 4096, None, {"hvd_flash_bwd": "resident"},
                 id="ouro2b6_1chip"),
    pytest.param(1, 8192, None, {"hvd_flash_bwd": "resident"}, id="L8192"),
    pytest.param(1, 16384, None, {"hvd_flash_dq": "resident",
                                  "hvd_flash_dkv": "gridded"}, id="L16384"),
    pytest.param(2, 2048, 0, {"hvd_flash_dq": "gridded",
                              "hvd_flash_dkv": "gridded"}, id="budget0"),
])
def test_flash_plan_backward_kernels(B, L, budget, expected):
    """The backward is ONE kernel exactly where its whole-sequence
    operands with its accumulators fit the budget, held by the k block
    (every causal benchmark cell) or by the q block, and the two kernels of
    old, each resident or gridded as before, where they do not."""
    from horovod_tpu.ops.flash_attention import (RESIDENT_VMEM_BUDGET,
                                                 flash_plan)
    kw = {} if budget is None else {"vmem_budget": budget}
    plans = flash_plan(B, 16, L, 128, 1, jnp.bfloat16, backward=True, **kw)
    assert {n: p.path for n, p in plans.items()} == expected
    # by the k block: q, dO, dQ and the two stripes, two buffers, and dQ's
    # accumulator; by the q block: k, v, dk, dv and two accumulators
    forms = {"k": 2 * (3 * L * 128 * 2 + 2 * L * 128 * 4) + L * 128 * 4,
             "q": 2 * 4 * L * 128 * 2 + 2 * L * 128 * 4}
    budget = RESIDENT_VMEM_BUDGET if budget is None else budget
    fits = [held for held, whole in forms.items() if whole <= budget]
    assert [p.held for n, p in plans.items() if n == "hvd_flash_bwd"] \
        == fits[:1]
    if fits:
        assert plans["hvd_flash_bwd"].resident_bytes == forms[fits[0]]


def test_flash_plan_past_the_budget_is_gridded():
    """A length whose whole-sequence operands do not fit the budget
    takes the gridded path, kernel by kernel (L=16384: dK/dV's 48 MiB do
    not fit 24, k + v do), with the grid of old and no VMEM limit asked;
    so does a block pair neither of which tiles the other."""
    from horovod_tpu.ops.flash_attention import flash_plan
    bf16 = jnp.bfloat16
    plans = flash_plan(1, 16, 16384, 128, 1, bf16, backward=True)
    dkv = plans["hvd_flash_dkv"]
    assert (dkv.path, dkv.block_q, dkv.block_k, dkv.grid,
            dkv.grid_steps) == ("gridded", 512, 1024, (16, 16, 32), 8192)
    assert dkv.resident_bytes == 0 and dkv.vmem_limit_bytes is None
    assert plans["hvd_flash_dq"].path == "resident"
    for backward in (False, True):
        for plan in flash_plan(1, 16, 8192, 128, 1, bf16,
                               backward).values():
            assert plan.path == "resident"  # the longest shape swept
        for plan in flash_plan(1, 16, 32768, 128, 1, bf16,
                               backward).values():
            assert plan.path == "gridded" and len(plan.grid) == 3
    # A head group does not save dK/dV at this length: held by the q block
    # it has k, v, the results and two accumulators whole, 48 MiB.
    grouped = flash_plan(1, 8, 16384, 128, 2, bf16, backward=True)
    assert {n: p.path for n, p in grouped.items()} == {
        "hvd_flash_dq": "resident", "hvd_flash_dkv": "gridded"}
    odd = flash_plan(1, 2, 768, 128, 1, bf16, block_q=384, block_k=256)
    assert odd["hvd_flash_fwd"].path == "gridded"
    # The same for the backward: no static peel, so not the one kernel.
    odd = flash_plan(1, 2, 768, 128, 1, bf16, backward=True, block_q=384,
                     block_k=256)
    assert {n: p.path for n, p in odd.items()} == {
        "hvd_flash_dq": "gridded", "hvd_flash_dkv": "gridded"}


def test_flash_default_block_policy():
    """Pins the swept block-preference table (_default_blocks) and the
    invariant that the chosen blocks always divide L: D- and L-aware
    (L=8192 sweeps: bigger q blocks at L>=4096), one definition for
    plain and ring paths."""
    from horovod_tpu.ops.flash_attention import (_default_blocks,
                                                 _pick_block)
    # (D, L, backward) -> swept preference
    assert _default_blocks(64, 2048) == (256, 1024)
    assert _default_blocks(64, 2048, backward=True) == (512, 1024)
    assert _default_blocks(64, 8192) == (512, 1024)
    assert _default_blocks(64, 8192, backward=True) == (1024, 1024)
    assert _default_blocks(128, 2048) == (256, 512)
    assert _default_blocks(128, 8192) == (512, 512)
    assert _default_blocks(128, 8192, backward=True) == (512, 1024)
    # L unknown (ring callers pass shard length; None = conservative)
    assert _default_blocks(64) == (256, 1024)
    # The picked block always divides L, falling back down the ladder.
    for D in (64, 128):
        for L in (256, 384, 2048, 4096, 8192, 12288):
            for backward in (False, True):
                pq, pk = _default_blocks(D, L, backward)
                for pref in (pq, pk):
                    b = _pick_block(L, pref)
                    assert b is not None and L % b == 0 and b <= pref


def test_flash_fallback_tail_block():
    """L not a multiple of BLOCK_Q (160 = 128 + 32 tail): the blockwise
    fallback must cover the remainder, full shape, values AND grads."""
    from horovod_tpu.ops import flash_attention
    B, L, H, D = 1, 160, 1, 8
    q, k, v = _rand_qkv(B, L, H, D, seed=5)

    out = flash_attention(q, k, v, causal=True)
    assert out.shape == (B, L, H, D)
    expected = _dense(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    g_flash = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    g_dense = jax.grad(lambda q, k, v: jnp.sum(
        _dense(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_step_carry(causal):
    """Two chained `flash_ring_step` calls (q at global offset Lq, k/v
    blocks arriving diagonal-first, the ring order) must equal dense
    attention of the q shard over the concatenated sequence — validates
    the carried online-softmax state and global-offset masking."""
    from horovod_tpu.ops.flash_attention import flash_ring_step
    BH, Lq, D = 2, 256, 32
    rng = np.random.RandomState(21)
    q = jnp.asarray(rng.randn(BH, Lq, D), jnp.float32)
    k_blocks = [jnp.asarray(rng.randn(BH, Lq, D), jnp.float32)
                for _ in range(2)]
    v_blocks = [jnp.asarray(rng.randn(BH, Lq, D), jnp.float32)
                for _ in range(2)]
    scale = D ** -0.5

    o = jnp.zeros((BH, Lq, D), jnp.float32)
    m = jnp.full((BH, Lq, 8), -jnp.inf, jnp.float32)
    l = jnp.zeros((BH, Lq, 8), jnp.float32)
    # q is the SECOND shard (offset Lq); ring delivers own (diagonal)
    # k/v block first, then the previous shard's.
    for kv_idx in (1, 0):
        o, m, l = flash_ring_step(
            q, k_blocks[kv_idx], v_blocks[kv_idx], o, m, l,
            q_offset=jnp.int32(Lq), kv_offset=jnp.int32(kv_idx * Lq),
            causal=causal, scale=scale, interpret=True)
    l1 = l[:, :, :1]
    out = o / jnp.where(l1 == 0.0, 1.0, l1)

    k_full = jnp.concatenate(k_blocks, axis=1)
    v_full = jnp.concatenate(v_blocks, axis=1)
    s = jnp.einsum("bqd,bkd->bqk", q, k_full) * scale
    if causal:
        rows = Lq + np.arange(Lq)[:, None]
        cols = np.arange(2 * Lq)[None, :]
        s = jnp.where(jnp.asarray(rows >= cols)[None], s, -jnp.inf)
    expected = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1),
                          v_full)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_transformer_flash_matches_dense():
    from horovod_tpu.models import Transformer, TransformerConfig
    base = dict(vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
                mlp_dim=64, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    dense_model = Transformer(TransformerConfig(**base))
    flash_model = Transformer(TransformerConfig(attention="flash", **base))
    variables = dense_model.init(jax.random.PRNGKey(0), tokens)
    expected = dense_model.apply(variables, tokens)
    out = flash_model.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# GQA/MQA (grouped kv heads)


def _dense_gqa(q, k, v, causal):
    """Dense reference for q [B,L,H,D], k/v [B,L,G,D]: repeat kv across
    each query-head group."""
    H, G = q.shape[2], k.shape[2]
    if H != G:
        k = jnp.repeat(k, H // G, axis=2)
        v = jnp.repeat(v, H // G, axis=2)
    return _dense(q, k, v, causal)


def _rand_gqa(B, L, H, G, D, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("G,causal", [(2, True), (2, False), (1, True)])
def test_flash_gqa_interpret_matches_dense(G, causal):
    """Grouped-rows GQA kernel layout (G=1 is MQA: every query head on
    one kv head) must match dense attention with repeated kv."""
    from horovod_tpu.ops.flash_attention import _pallas_forward
    B, L, H, D = 2, 256, 4, 32
    q, k, v = _rand_gqa(B, L, H, G, D, seed=5)
    out = _pallas_forward(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), D ** -0.5, causal,
                          interpret=True).transpose(0, 2, 1, 3)
    expected = _dense_gqa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G,D", [(2, 32), (4, 32), (1, 32), (2, 64)])
def test_flash_gqa_backward_interpret(G, D):
    """Values AND all three gradients of the Pallas path (custom VJP,
    interpret mode) for grouped kv heads (G = H: none), against dense
    attention that repeats kv. Pins: the in-kernel dK/dV group reduction
    and the grouped causal masks."""
    from horovod_tpu.ops.flash_attention import _flash
    B, L, H = 1, 512, 4
    q, k, v = _rand_gqa(B, L, H, G, D, seed=9)
    w = jnp.asarray(np.random.RandomState(10).randn(B, L, H, D),
                    jnp.float32)

    def loss_flash(q, k, v):
        out = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                     v.transpose(0, 2, 1, 3), D ** -0.5, True,
                     True).transpose(0, 2, 1, 3)
        return jnp.sum(out * w), out

    def loss_dense(q, k, v):
        return jnp.sum(_dense_gqa(q, k, v, True) * w)

    (_, out), g_flash = jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_gqa(q, k, v, True)),
        rtol=2e-5, atol=2e-5)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, nm in zip(g_flash, g_dense, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4, err_msg=nm)


def test_flash_attention_gqa_fallback_and_validation():
    """Public API on CPU (blockwise fallback): GQA values/grads match
    dense; mismatched head counts raise."""
    from horovod_tpu.ops import flash_attention
    B, L, H, G, D = 1, 48, 4, 2, 16  # L not 128-aligned -> fallback
    q, k, v = _rand_gqa(B, L, H, G, D, seed=13)

    out = flash_attention(q, k, v, causal=True)
    expected = _dense_gqa(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda q, k, v: jnp.sum(
        _dense_gqa(q, k, v, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError, match="num_kv_heads"):
        flash_attention(q, k[:, :, :1].repeat(3, 2), v, causal=True)


def test_pick_rows_block_policy():
    """Grouped row-block picking: bqp positions * group rows stays at
    or under the swept row preference, bqp | L, and group=1 defers to
    the plain picker."""
    from horovod_tpu.ops.flash_attention import (_pick_block,
                                                 _pick_rows_block)
    assert _pick_rows_block(8192, 512, 1) == _pick_block(8192, 512) == 512
    assert _pick_rows_block(8192, 512, 2) == 512      # 256 pos x 2
    assert _pick_rows_block(8192, 512, 3) == 384      # 128 pos x 3
    assert _pick_rows_block(8192, 512, 6) == 384      # 64 pos x 6
    assert _pick_rows_block(8192, 512, 12) == 384     # 32 pos x 12
    assert _pick_rows_block(8192, 1024, 4) == 1024    # 256 pos x 4
    assert _pick_rows_block(256, 512, 2) == 512       # 256 pos x 2


def test_transformer_gqa_flash_matches_dense():
    """Transformer with grouped kv heads: the flash path (fallback on
    CPU) must match the dense path on the same params (both rotate by
    the model's `_rotary`, outside the attention); the kv projections must
    actually shrink to G heads."""
    from horovod_tpu.models import Transformer, TransformerConfig
    base = dict(vocab_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, embed_dim=32, mlp_dim=64,
                dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    dense_model = Transformer(TransformerConfig(**base))
    flash_model = Transformer(TransformerConfig(attention="flash", **base))
    variables = dense_model.init(jax.random.PRNGKey(0), tokens)
    key_kernel = variables["params"]["block_0"]["attn"]["key"]["kernel"]
    assert key_kernel.shape == (32, 2, 8)  # (embed, G, head_dim)
    expected = dense_model.apply(variables, tokens)
    out = flash_model.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
