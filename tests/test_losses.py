"""Chunked LM cross entropy: identical values and gradients to the
dense log_softmax form (the streaming loss is a memory optimization,
not an approximation)."""

import numpy as np

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from horovod_tpu.ops.losses import chunked_softmax_cross_entropy  # noqa: E402


def _dense_loss(hidden, kernel, targets):
    logits = (hidden @ kernel).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None], axis=-1))


def test_chunked_xent_matches_dense_values_and_grads():
    B, L, D, V = 2, 64, 16, 50
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(B, L, D), jnp.float32)
    kernel = jnp.asarray(rng.randn(D, V) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, V, (B, L)))

    for chunk in (16, 32, 64):
        loss = chunked_softmax_cross_entropy(hidden, kernel, targets,
                                             chunk=chunk)
        dense = _dense_loss(hidden, kernel, targets)
        np.testing.assert_allclose(float(loss), float(dense), rtol=1e-6)

    g_c = jax.grad(
        lambda h, k: chunked_softmax_cross_entropy(h, k, targets,
                                                   chunk=16),
        argnums=(0, 1))(hidden, kernel)
    g_d = jax.grad(_dense_loss, argnums=(0, 1))(hidden, kernel, targets)
    for got, exp in zip(g_c, g_d):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_xent_rejects_indivisible_chunk():
    hidden = jnp.zeros((1, 10, 4))
    kernel = jnp.zeros((4, 7))
    targets = jnp.zeros((1, 10), jnp.int32)
    try:
        chunked_softmax_cross_entropy(hidden, kernel, targets, chunk=3)
    except ValueError as e:
        assert "divisible" in str(e)
    else:
        raise AssertionError("expected ValueError")


# --------------------------------------------------------------------------
# The gradient formed in the forward pass, over rows chosen from the shapes
# --------------------------------------------------------------------------

import pytest  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from horovod_tpu import profile  # noqa: E402
from horovod_tpu.ops import losses  # noqa: E402
from horovod_tpu.parallel import data_parallel_mesh  # noqa: E402

SHAPES = [(2, 64, 16, 50, 16), (1, 128, 16, 50, 16), (4, 32, 8, 96, 32),
          (1, 8, 4, 7, 8)]
# Hidden states' dtype over an f32 head (bf16: OLMoE's case), with the
# tolerances of the value and of the gradients against the dense form.
DTYPES = {"f32": (jnp.float32, 1e-6, 1e-5, 1e-6),
          "bf16": (jnp.bfloat16, 2e-2, 5e-2, 2e-3)}


def _inputs(B, L, D, V, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, L, D), dtype),
            jnp.asarray(rng.randn(D, V) * 0.1, jnp.float32),
            jnp.asarray(rng.randint(0, V, (B, L))))


@pytest.fixture
def small_budget(monkeypatch):
    """A budget of 24 rows of f32 logits at V=50, so that the rows of these
    small shapes are decided as the benchmark's are: by the budget in one
    case, by the caller's `chunk` in the others."""
    monkeypatch.setattr(losses, "LOGITS_BUDGET_BYTES", 24 * 50 * 4)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_value_and_both_gradients_match_dense(shape, dtype, small_budget):
    B, L, D, V, chunk = shape
    dtype, rtol, g_rtol, g_atol = DTYPES[dtype]
    hidden, kernel, targets = _inputs(B, L, D, V, dtype)
    loss, grads = jax.value_and_grad(
        lambda h, k: chunked_softmax_cross_entropy(h, k, targets,
                                                   chunk=chunk),
        argnums=(0, 1))(hidden, kernel)
    # the dense form with the projection in the hidden states' dtype
    dense, dense_grads = jax.value_and_grad(
        lambda h, k: _dense_loss(h, k.astype(dtype), targets),
        argnums=(0, 1))(hidden, kernel)
    np.testing.assert_allclose(float(loss), float(dense), rtol=rtol)
    for got, exp in zip(grads, dense_grads):
        assert got.dtype == exp.dtype and got.shape == exp.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=g_rtol, atol=g_atol)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_rows_are_the_plans_and_never_fewer_than_the_callers(shape,
                                                             small_budget):
    """The scan of the traced function runs `loss_plan`'s iterations over
    its rows, which are at least B * chunk and divide B * L."""
    B, L, D, V, chunk = shape
    plan = profile.loss_plan(B, L, D, V, chunk, jnp.float32)
    assert plan["rows"] >= B * chunk
    assert plan["rows"] * plan["iterations"] == B * L
    hidden, kernel, targets = _inputs(B, L, D, V)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h: chunked_softmax_cross_entropy(h, kernel, targets,
                                                chunk=chunk)))(hidden)
    (scan,) = _eqns(jaxpr.jaxpr, "scan")
    assert scan.params["length"] == plan["iterations"]
    assert tuple(scan.invars[-2].aval.shape) == (plan["iterations"],
                                                 plan["rows"], D)


@pytest.mark.parametrize("scale", [0.5, 1.0 / 3.0])
def test_cotangent_that_is_not_one(scale):
    """`scale * loss + other` with an auxiliary output: the backward rule
    scales what the forward left by the incoming cotangent."""
    hidden, kernel, targets = _inputs(2, 64, 16, 50)

    def total(loss_fn):
        def f(h, k):
            loss = loss_fn(h, k, targets)
            other = jnp.sum(h[:, 0] ** 2) + jnp.sum(k[0])
            return scale * loss + other, loss
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    (v, aux), grads = total(lambda h, k, t: chunked_softmax_cross_entropy(
        h, k, t, chunk=16))(hidden, kernel)
    (v_d, aux_d), grads_d = total(_dense_loss)(hidden, kernel)
    np.testing.assert_allclose(float(v), float(v_d), rtol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux_d), rtol=1e-6)
    for got, exp in zip(grads, grads_d):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-5, atol=1e-6)


def test_under_vmap_one_token_a_call_is_the_per_token_dense_loss():
    """OLMoE's `token_nll`: the function under `jax.vmap` on [1, 1, D] with
    `chunk=1`; the gradient through the `custom_vjp` batches too."""
    hidden, kernel, targets = _inputs(1, 24, 16, 50)

    def token_nll(hid, kernel):
        return jax.vmap(lambda h, t: chunked_softmax_cross_entropy(
            h[None, None], kernel, t[None, None], chunk=1))(hid, targets[0])

    logp = jax.nn.log_softmax(hidden[0] @ kernel)
    dense = -jnp.take_along_axis(logp, targets[0][:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(np.asarray(token_nll(hidden[0], kernel)),
                               np.asarray(dense), rtol=1e-6)
    g = jax.grad(lambda h, k: token_nll(h, k).mean(), argnums=(0, 1))(
        hidden[0], kernel)
    g_d = jax.grad(_dense_loss, argnums=(0, 1))(hidden, kernel, targets)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g_d[0][0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(g_d[1]),
                               rtol=1e-5, atol=1e-6)


def test_under_shard_map_equals_the_unsharded_call():
    """Four data-parallel shards of the batch, each with its own scan over
    its own rows; mean of the losses and of the head's gradients
    (`check_vma=False` as `make_train_step`'s own `shard_map`)."""
    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:4])
    axis = mesh.axis_names[0]
    hidden, kernel, targets = _inputs(8, 32, 16, 50)

    def local(h, k, t):
        loss, (dh, dk) = jax.value_and_grad(
            lambda h, k: chunked_softmax_cross_entropy(h, k, t, chunk=16),
            argnums=(0, 1))(h, k)
        return (jax.lax.pmean(loss, axis), dh / mesh.size,
                jax.lax.pmean(dk, axis))

    loss, dh, dk = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(), P(axis)),
        out_specs=(P(), P(axis), P()), check_vma=False))(
            hidden, kernel, targets)
    exp, (dh_e, dk_e) = jax.value_and_grad(
        lambda h, k: chunked_softmax_cross_entropy(h, k, targets, chunk=16),
        argnums=(0, 1))(hidden, kernel)
    np.testing.assert_allclose(float(loss), float(exp), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_e),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_e),
                               rtol=1e-5, atol=1e-7)


def _eqns(jaxpr, name):
    """Every equation called `name` in a jaxpr and the jaxprs inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


def test_value_and_grad_makes_three_passes_of_the_head(small_budget):
    """The structure, on the jaxpr: one scan, three contractions with a
    vocabulary-wide operand or result in it and none outside (the form
    under `jax.checkpoint` had four: the logits twice), the head cast to
    the compute dtype once and outside the scan, and no f32 array of all
    B * L rows by V anywhere."""
    B, L, D, V, chunk = 2, 64, 12, 50, 8
    hidden, kernel, targets = _inputs(B, L, D, V, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda h, k: chunked_softmax_cross_entropy(h, k, targets,
                                                   chunk=chunk),
        argnums=(0, 1)))(hidden, kernel).jaxpr
    (scan,) = _eqns(jaxpr, "scan")
    assert scan.params["length"] == profile.loss_plan(
        B, L, D, V, chunk, jnp.bfloat16)["iterations"] == 8

    def over_vocab(eqn):
        return any(V in v.aval.shape for v in eqn.invars + eqn.outvars)

    assert len([e for e in _eqns(jaxpr, "dot_general")
                if over_vocab(e)]) == 3
    assert len([e for e in _eqns(scan.params["jaxpr"].jaxpr, "dot_general")
                if over_vocab(e)]) == 3
    casts = [e for e in _eqns(jaxpr, "convert_element_type")
             if e.outvars[0].aval.dtype == jnp.bfloat16
             and e.outvars[0].aval.shape == (D, V)]
    assert len(casts) == 1 and casts[0] in jaxpr.eqns
    assert not _eqns(jaxpr, "checkpoint") and not _eqns(jaxpr, "remat")

    def avals(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (v.aval for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from avals(sub)

    assert not [a for a in avals(jaxpr)
                if a.dtype == jnp.float32 and a.shape[-1:] == (V,)
                and int(np.prod(a.shape[:-1])) >= B * L]
