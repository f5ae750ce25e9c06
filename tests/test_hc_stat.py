"""`ops.hc_stat`: a hyper-connection's per-token sum of squares and
projection from one read of the streams. The kernel in Pallas' interpreter
against the jnp form and against autodiff of the plain expressions, both
results and every gradient; which path `hc_plan` names for which shapes."""

import jax
import jax.numpy as jnp
import pytest

jax.config.update("jax_default_matmul_precision", "highest")

from horovod_tpu import profile  # noqa: E402
from horovod_tpu.ops import hc_stat as hs  # noqa: E402


def _case(n, dtype, C=256, lead=(2, 32), seed=0):
    """(X [n, *lead, C] in `dtype`, phi [n*C, 2n + n*n] f32, a weight for
    each of the two results)."""
    K = 2 * n + n * n
    ks = jax.random.split(jax.random.PRNGKey(seed + n), 4)
    X = jax.random.normal(ks[0], (n,) + lead + (C,), jnp.float32)
    phi = 0.1 * jax.random.normal(ks[1], (n * C, K), jnp.float32)
    return (X.astype(dtype), phi, jax.random.normal(ks[2], lead),
            jax.random.normal(ks[3], lead + (K,)))


def _plain(X, phi):
    """The two results as `hc_maps` wrote them before the op: one reduction
    over the stream and the lane dimension, and an einsum."""
    n, C = X.shape[0], X.shape[-1]
    xf = X.astype(jnp.float32)
    return (jnp.sum(xf * xf, axis=(0, -1)),
            jnp.einsum("n...c,nck->...k", X,
                       phi.reshape(n, C, -1).astype(X.dtype),
                       preferred_element_type=jnp.float32))


def _grads(fn, X, phi, w_s, w_p):
    return jax.grad(lambda X, phi: sum(
        jnp.sum(w * out) for w, out in zip((w_s, w_p), fn(X, phi))),
        argnums=(0, 1))(X, phi)


def _off(a, b):
    """max |a - b| over max |b|, in f32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# bf16: X's gradient is rounded once to bf16 on every path (2^-8 of its
# value), and phi's is f32 here where autodiff rounds it to bf16.
TOL = {"float32": 1e-5, "bfloat16": 2 ** -7}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_kernel_jnp_and_plain_forms_agree_values_and_gradients(n, dtype):
    X, phi, w_s, w_p = _case(n, dtype)
    assert hs.hc_plan(n, 64, 256, phi.shape[1], dtype)["path"] == "kernel"
    kernel = lambda X, phi: hs.hc_stat(X, phi, interpret=True)  # noqa: E731
    tol = TOL[jnp.dtype(dtype).name]
    want = _plain(X, phi)
    for fn in (kernel, hs.hc_stat):
        got = fn(X, phi)
        assert got[0].shape == (2, 32) and got[1].shape == (
            2, 32, phi.shape[1])
        assert got[0].dtype == got[1].dtype == jnp.float32
        # the results are f32 sums of exact products on every path
        assert _off(got[0], want[0]) < 1e-5 and _off(got[1], want[1]) < 1e-5
    want = _grads(_plain, X, phi, w_s, w_p)
    for fn in (kernel, hs.hc_stat):
        got = _grads(fn, X, phi, w_s, w_p)
        assert got[0].dtype == X.dtype and got[1].dtype == phi.dtype
        assert _off(got[0], want[0]) < tol
        assert _off(got[1], want[1]) < tol


def test_several_grid_steps(monkeypatch):
    """Two tiles of 32 tokens, not only the one-step call of the small
    cases."""
    monkeypatch.setattr(hs, "BLOCK_ROWS", 32)
    X, phi, _, _ = _case(4, jnp.float32)
    plan = hs.hc_plan(4, 64, 256, 24, jnp.float32)
    assert (plan["rows"], plan["steps"]) == (32, 2)
    got, want = hs.hc_stat(X, phi, interpret=True), _plain(X, phi)
    assert _off(got[0], want[0]) < 1e-5 and _off(got[1], want[1]) < 1e-5


def test_the_norms_factor_comes_out_of_the_product():
    """`hc_maps`' `rsqrt(sumsq / nC + eps) * proj` is the projection of the
    normalised streams."""
    X, phi, _, _ = _case(4, jnp.float32)
    sumsq, proj = hs.hc_stat(X, phi)
    inv = jax.lax.rsqrt(sumsq / (4 * 256) + 1e-6)
    want = jnp.einsum("n...c,nck->...k", X * inv[None, ..., None],
                      phi.reshape(4, 256, -1))
    assert _off(inv[..., None] * proj, want) < 1e-5


PLANS = {
    # Xing4.0's connection: [4, 4096, 3584] bf16 against 24 columns
    "xing": ((4, 4096, 3584, 24, jnp.bfloat16), dict(
        path="kernel", rows=hs.BLOCK_ROWS, steps=4096 // hs.BLOCK_ROWS,
        passes=1, evaluations=1)),
    # f32 streams: sublane tiles of 8 tokens, the same tile
    "f32_streams": ((4, 4096, 3584, 24, jnp.float32), dict(
        path="kernel", rows=hs.BLOCK_ROWS, passes=1)),
    # fewer tokens than a tile: one tile of them all
    "short": ((2, 96, 256, 8, jnp.bfloat16), dict(
        path="kernel", rows=96, steps=1)),
    # a width that is no whole number of lanes: jnp, two passes
    "ragged_width": ((4, 4096, 3000, 24, jnp.bfloat16), dict(
        path="jnp", passes=2, steps=0)),
    # tokens that no sublane tile divides: jnp
    "odd_tokens": ((2, 1021, 256, 8, jnp.bfloat16), dict(path="jnp")),
    # so wide that not even one sublane tile of tokens fits: jnp
    "no_tile_fits": ((4, 4096, 2 ** 20, 24, jnp.bfloat16), dict(
        path="jnp")),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_hc_plan_answers_from_the_shapes_without_a_chip(case):
    args, want = PLANS[case]
    plan = profile.hc_plan(*args)
    assert plan == hs.hc_plan(*args)
    assert {k: plan[k] for k in want} == want
    if plan["path"] == "kernel":
        n, T, C, K, dtype = args
        assert T % plan["rows"] == 0 and plan["steps"] == T // plan["rows"]
        assert plan["vmem_bytes"] <= hs.VMEM_BUDGET_BYTES
        assert plan["vmem_bytes"] == hs._block_bytes(
            n, plan["rows"], C, K, jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("hc_remat,block_remat", [
    (False, False), (True, False), (True, True)])
def test_a_step_evaluates_the_statistic_once_whatever_is_recomputed(
        hc_remat, block_remat):
    plan = hs.hc_plan(4, 4096, 3584, 24, jnp.bfloat16, hc_remat=hc_remat,
                      block_remat=block_remat)
    assert plan["evaluations"] == 1


def test_a_ragged_width_takes_the_jnp_path_even_when_asked_to_interpret():
    X, phi, w_s, w_p = _case(2, jnp.float32, C=200)
    text = str(jax.make_jaxpr(
        lambda X, phi: hs.hc_stat(X, phi, interpret=True))(X, phi))
    assert "pallas_call" not in text
    got, want = hs.hc_stat(X, phi, interpret=True), _plain(X, phi)
    assert _off(got[0], want[0]) < 1e-5 and _off(got[1], want[1]) < 1e-5
    got = _grads(hs.hc_stat, X, phi, w_s, w_p)
    want = _grads(_plain, X, phi, w_s, w_p)
    assert _off(got[0], want[0]) < 1e-5 and _off(got[1], want[1]) < 1e-5


def test_the_interpreted_call_is_the_kernel_and_off_a_tpu_the_call_is_jnp():
    X, phi, _, _ = _case(2, jnp.float32)
    interpreted = str(jax.make_jaxpr(
        lambda X, phi: hs.hc_stat(X, phi, interpret=True))(X, phi))
    assert "pallas_call" in interpreted and profile.HC_STAT in interpreted
    assert "pallas_call" not in str(jax.make_jaxpr(hs.hc_stat)(X, phi))
