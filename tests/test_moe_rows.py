"""The rows of a routed layer moved by their live count (`ops/moe_rows.py`):
both kernels in Pallas' interpreter, and the jnp path, against autodiff of
the expressions `moe_ffn` had (`x[order % T]`, `ys[inv]`, the einsum over the
k choices): values and every gradient, at counts of 0, 1, a tile's edge, a
tile's middle and all rows; dead rows full of NaN; the plan; `moe_ffn` on
the kernels alone, holding a part of the experts and holding them all."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import profile
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops import moe_act as ma
from horovod_tpu.ops import moe_rows as mr
from horovod_tpu.parallel import expert

jax.config.update("jax_default_matmul_precision", "highest")

T, K, D, TILE = 64, 4, 256, 32
COUNTS = {"none": 0, "one": 1, "a_tiles_edge": 2 * TILE,
          "inside_a_tile": 2 * TILE + 13, "all": K * T}


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 rows and two column blocks of 128, so that sizes a CPU
    test can afford cross tiles and blocks as [16384, 3584] crosses the
    real ones."""
    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)
    monkeypatch.setattr(mr, "TILE_ROWS", TILE)
    monkeypatch.setattr(mr, "RESIDENT_BYTES", T * 128 * 12)


def _operands(dtype, n, seed=0):
    """(x, ys, weights, order, inv, n_live, the two cotangents): a random
    routing's permutation; the rows of `ys` and of its side's cotangent
    from `n` on are NaN, as a grouped matmul may leave them."""
    rng = np.random.RandomState(seed)
    flat = jnp.asarray(rng.randint(0, 8, (T, K)), jnp.int32).T.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    dead = (jnp.arange(K * T) >= n)[:, None]
    wide = lambda rows: jnp.asarray(rng.randn(rows, D), dtype)  # noqa: E731
    return (wide(T), jnp.where(dead, jnp.nan, wide(K * T)),
            jnp.asarray(rng.rand(K, T), jnp.float32), order, inv,
            jnp.int32(n), jnp.where(dead, jnp.nan, wide(K * T)), wide(T))


def _plain(x, ys, weights, order, inv, n_live):
    """The held branch of `moe_ffn` before the op, autodiff's to
    transpose."""
    mine = (jnp.arange(K * T) < n_live)[:, None]
    xs = jnp.where(mine, x[order % T], 0)
    rows = jnp.where(mine, ys, 0)[inv].reshape(K, T, D)
    w = jnp.where(inv.reshape(K, T) < n_live, weights, 0.0)
    y = jnp.einsum("ktd,kt->td", rows, w,
                   preferred_element_type=jnp.float32)
    return xs, y.astype(x.dtype)


def _through_the_op(interpret, x, ys, weights, order, inv, n_live):
    return (mr.dispatch(x, order, inv, n_live, K, 1, interpret)[0],
            mr.combine(ys, weights, order, inv, n_live, interpret))


def _values_and_gradients(fn, operands):
    x, ys, weights, order, inv, n_live, g_xs, g_y = operands
    out, vjp = jax.vjp(lambda x, ys, w: fn(x, ys, w, order, inv, n_live),
                       x, ys, weights)
    return out + vjp((g_xs, g_y))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["kernel", "jnp"])
@pytest.mark.parametrize("count", sorted(COUNTS))
def test_values_and_gradients_are_autodiffs_of_the_plain_expressions(
        small_tiles, count, path, dtype):
    n = COUNTS[count]
    operands = _operands(jnp.dtype(dtype), n)
    got = _values_and_gradients(functools.partial(
        _through_the_op, True if path == "kernel" else None), operands)
    want = _values_and_gradients(_plain, operands)
    # What the kernel writes of the buffer: the live tiles, the last whole.
    written = -(-max(n, 1) // TILE) * TILE if path == "kernel" else K * T
    tol = 1e-5 if dtype == "float32" else 2e-2   # a bf16 rounding
    for name, a, b in zip(("xs", "y", "dx", "dys", "dw"), got, want):
        if a.shape[0] == K * T:
            a, b = a[:written], b[:written]
        assert bool(jnp.all(jnp.isfinite(a))), name  # no NaN came through
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol,
            rtol=tol, err_msg=name)
    xs, dys = got[0], got[3]
    for rows in (xs, dys):  # zeros from the count to the tile's end
        assert float(jnp.max(jnp.abs(rows[n:written]), initial=0.0)) == 0.0


def test_the_weights_gradient_is_zero_where_the_slot_is_dead(small_tiles):
    operands = _operands(jnp.float32, 45)
    inv = operands[4]
    dw = _values_and_gradients(
        functools.partial(_through_the_op, True), operands)[4]
    dead = inv.reshape(K, T) >= 45
    assert float(jnp.max(jnp.abs(jnp.where(dead, dw, 0.0)))) == 0.0
    assert float(jnp.min(jnp.abs(jnp.where(dead, 1.0, dw)))) > 0.0


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_two_copies_of_the_rows_sum_their_gradients_in_the_transpose(
        small_tiles, path):
    """`dispatch(copies=2)`: the same rows twice, and the gradient by x of
    two uses is the transpose of the two cotangents' sum, whose dead rows
    are NaN in both: one `hvd_moe_sum` call, and no sum of [k*T, D] arrays
    before it."""
    x, _, _, order, inv, n_live, g_a, _ = _operands(jnp.float32, 77)
    g_b = jnp.roll(g_a, 3, axis=1)
    interpret = True if path == "kernel" else None
    two = lambda x: mr.dispatch(x, order, inv, n_live, K, 2,  # noqa: E731
                                interpret)
    (a, b), vjp = jax.vjp(two, x)
    np.testing.assert_array_equal(a[:77], b[:77])
    want = jax.vjp(lambda x: x[order % T], x)[1](jnp.where(
        (jnp.arange(K * T) < 77)[:, None], g_a + g_b, 0.0))[0]
    np.testing.assert_allclose(vjp((g_a, g_b))[0], want, atol=1e-5,
                               rtol=1e-5)
    text = str(jax.make_jaxpr(lambda x: jax.vjp(two, x)[1]((g_a, g_b)))(x))
    assert text.count("name=%s" % profile.MOE_SUM) == (path == "kernel")
    if path == "kernel":
        assert not re.search(r"f32\[%d,%d\] = add" % (K * T, D), text)


def test_rows_out_beside_its_dots(small_tiles):
    """`rows_out` alone: the scale, and each live row's product with the
    other operand's row, whose dead rows are NaN."""
    x, ys, weights, order, inv, n_live, _, _ = _operands(jnp.float32, 77)
    scale = weights.reshape(-1)[order]
    out, dots = mr.rows_out(x, order % T, n_live, scale=scale, other=ys,
                            interpret=True)
    want = scale[:, None] * x[order % T]
    np.testing.assert_allclose(out[:77], want[:77], rtol=1e-6)
    assert float(jnp.max(jnp.abs(out[77:96]))) == 0.0
    np.testing.assert_allclose(
        dots[:77], jnp.sum(x[order % T] * ys, axis=1)[:77], rtol=1e-5,
        atol=1e-5)
    assert float(jnp.max(jnp.abs(dots[77:]))) == 0.0


def _pallas_names(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return {name for name in profile.MOE_ROWS_KERNELS
            if "name=%s\n" % name in text or "name=%s " % name in text}


def test_the_interpreted_call_is_the_kernels_and_off_a_tpu_the_call_is_jnp(
        small_tiles):
    operands = _operands(jnp.float32, 45)
    both = lambda interpret: _pallas_names(  # noqa: E731
        lambda *a: _values_and_gradients(
            functools.partial(_through_the_op, interpret), a), *operands)
    assert both(True) == set(profile.MOE_ROWS_KERNELS)
    assert both(None) == set()
    assert not set(profile.MOE_ROWS_KERNELS) & set(profile.MOE_GMM_KERNELS)


def test_a_ragged_width_takes_the_jnp_path_even_when_asked_to_interpret(
        small_tiles):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(T, 96), jnp.float32)
    order = jnp.asarray(rng.permutation(K * T), jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    fn = lambda x: mr.dispatch(x, order, inv, jnp.int32(40), K,  # noqa: E731
                               1, True)[0]
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(x))
    np.testing.assert_array_equal(fn(x)[:40], x[order % T][:40])
    assert float(jnp.max(jnp.abs(fn(x)[40:]))) == 0.0


PLANS = {
    # (T, k, D, dtype, backend) -> (path, columns resident at a time)
    "xings_shape_on_a_tpu": ((4096, 4, 3584, "bfloat16", "tpu"),
                             ("kernel", 896)),
    "olmoes_shape_every_row_live_on_a_tpu": (
        (4096, 8, 2048, "bfloat16", "tpu"), ("kernel", 1024)),
    "olmoes_shape_off_the_tpu": ((4096, 8, 2048, "bfloat16", "cpu"),
                                 ("jnp", 0)),
    "a_width_of_192": ((4096, 8, 192, "bfloat16", "tpu"), ("jnp", 0)),
    "a_width_that_is_no_multiple_of_128": (
        (4096, 4, 3600, "bfloat16", "tpu"), ("jnp", 0)),
    "a_buffer_that_is_no_whole_tile": (
        (1000, 4, 3584, "bfloat16", "tpu"), ("jnp", 0)),
    "off_the_tpu": ((4096, 4, 3584, "bfloat16", "cpu"), ("jnp", 0)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_rows_plan_says_which_path_a_call_takes(monkeypatch, case):
    """The plan is of the shapes and the backend alone: a layer that holds
    every expert (OLMoE's: no `held`, the count k * T) takes the kernels
    as one that holds a part does."""
    (tokens, k, width, dtype, backend), (path, cols) = PLANS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    plan = profile.moe_rows_plan(tokens, k, width, jnp.dtype(dtype))
    assert plan == mr.rows_plan(tokens, k, width, jnp.dtype(dtype))
    assert plan["path"] == path and plan["buffer_rows"] == k * tokens
    if path == "jnp":
        assert plan["calls_a_layer"] == {"forward": 0, "backward": 0}
        return
    assert plan["tile_rows"] == mr.TILE_ROWS == 1024
    assert plan["tile_rows"] % gm.SUB_ROWS_DRHS == 0
    assert plan["block_cols"] == cols and width % cols == 0
    assert plan["calls_a_layer"] == {"forward": 2, "backward": 2}
    assert plan["vmem_bytes"] <= mr.RESIDENT_BYTES + (16 << 20) \
        <= mr._VMEM_LIMIT_BYTES


# --------------------------------------------------------------------------
# Through `moe_ffn`, with the grouped matmuls' kernels between the two
# --------------------------------------------------------------------------

def _interpret_every_kernel(monkeypatch):
    """From here on `moe_ffn` runs the rows' two kernels, the grouped
    matmuls' three and the activation's two in Pallas' interpreter, the
    latter five on tiles of 32 rows too."""
    monkeypatch.setattr(gm, "BLOCK_ROWS", 32)
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    monkeypatch.setattr(ma, "TILE_ROWS", TILE)
    monkeypatch.setattr(ma, "activated_matmul", functools.partial(
        ma.activated_matmul, interpret=True))
    monkeypatch.setattr(expert, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=True))
    monkeypatch.setattr(expert, "layer_visits", functools.partial(
        gm.layer_visits, interpret=True))
    for name in ("dispatch", "combine"):
        monkeypatch.setattr(mr, name, functools.partial(
            getattr(mr, name), interpret=True))


def _layer(seed=0, E=8, F=128):
    """A gated layer of a width the activation's kernels take too."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, 128)),
        router=jax.random.normal(ks[1], (128, E)),
        w_gate=0.3 * jax.random.normal(ks[2], (E, 128, F)),
        w_up=0.3 * jax.random.normal(ks[3], (E, 128, F)),
        w_down=0.15 * jax.random.normal(ks[4], (E, F, 128)),
        g=jax.random.normal(ks[5], (T, 128)))


@pytest.mark.parametrize("held", [(0, 2), (3, 3), (6, 2), (0, 8)])
def test_a_held_layer_on_kernels_alone_is_the_layer_on_jnp(
        small_tiles, monkeypatch, held):
    """`moe_ffn(held=)` with every kernel of the routed feed-forward in the
    interpreter (the rows', the activation's, and the grouped matmuls',
    whose matrices' gradient multiplies the dead rows of the last live part
    by zero): values and the gradients by x, the router and ALL the experts'
    matrices equal the jnp path's. `w_down`'s too: its left operand is the
    activation formed again by the backward's kernel, zeros from the count
    to the tile's end where the grouped matmuls before it leave what they
    find (NaN in the interpreter)."""
    c = _layer()
    sl = slice(held[0], held[0] + held[1])

    def loss(x, router, w):
        y, stats = expert.moe_ffn(
            x, router, w["w_up"][sl], w["w_down"][sl], capacity_factor=None,
            top_k=K, w_gate=w["w_gate"][sl], scoring="sigmoid", scale=2.0,
            held=held)
        return jnp.sum(c["g"] * y), stats["held"]

    w = {k: c[k] for k in ("w_up", "w_down", "w_gate")}
    both = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    want = both(c["x"], c["router"], w)
    _interpret_every_kernel(monkeypatch)
    got = both(c["x"], c["router"], w)
    assert int(got[0][1]) == int(want[0][1])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _dense_held(x, rows, router, w_in, w_out, bias, held, k):
    """A latent layer that holds `held` of its relu2 experts, the plain
    way: every held expert on every token, masked by the router's choice
    (`route` itself, so the choice is the layer's)."""
    logits = x.astype(jnp.float32) @ router
    weights, experts, _ = expert.route(logits, k, True, "sigmoid", bias, 5.0)
    y = jnp.zeros_like(rows)
    for e in range(held[0], held[0] + held[1]):
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        out = expert.relu2(rows @ w_in[e - held[0]]) @ w_out[e - held[0]]
        y = y + w[:, None] * out
    return y


@pytest.mark.parametrize("path", ["kernels", "jnp"])
@pytest.mark.parametrize("held", [(0, 2), (5, 3), (14, 2)])
def test_few_held_of_many_choices_never_fills_the_cut_buffer_up(
        small_tiles, monkeypatch, held, path):
    """Nemotron's shape: more choices a token (k = 6 of 16) than experts
    held, relu2 experts without a gate in a latent. The buffer is count x T
    rows from the dispatch to the combine (a token picks an expert once),
    never k x T: the output and the gradients by the tokens, the latent
    rows, the router and both matrices equal the dense masked
    computation's, on the kernels alone and on jnp."""
    k, E, R, F, D = 6, 16, 128, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(held[0]), 7)
    args = dict(
        x=jax.random.normal(ks[0], (T, D)),
        rows=jax.random.normal(ks[1], (T, R)),
        router=jax.random.normal(ks[2], (D, E)),
        w_in=0.3 * jax.random.normal(ks[3], (held[1], R, F)),
        w_out=0.3 * jax.random.normal(ks[4], (held[1], F, R)))
    bias = 0.1 * jax.random.normal(ks[5], (E,))
    g = jax.random.normal(ks[6], (T, R))

    def layer(a):
        y, stats = expert.moe_ffn(
            a["x"], a["router"], a["w_in"], a["w_out"], capacity_factor=None,
            act=expert.relu2, top_k=k, scoring="sigmoid", bias=bias,
            scale=5.0, held=held, rows=a["rows"])
        return jnp.sum(g * y), stats["held"]

    def dense(a):
        return jnp.sum(g * _dense_held(a["x"], a["rows"], a["router"],
                                       a["w_in"], a["w_out"], bias, held, k))

    if path == "kernels":
        _interpret_every_kernel(monkeypatch)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda a: layer(a)[0]))(args))
    cut, whole = held[1] * T, k * T
    assert cut < whole
    assert re.search(r"\[%d,%d\]" % (cut, R), jaxpr)
    assert not re.search(r"\[%d,(%d|%d)\]" % (whole, R, F), jaxpr), \
        "a k x T-row array between the dispatch and the combine"
    for name in profile.MOE_ROWS_KERNELS + profile.MOE_ACT_KERNELS:
        assert (("name=%s" % name) in jaxpr) == (path == "kernels"), name
    (loss, n_live), grads = jax.value_and_grad(layer, has_aux=True)(args)
    want, want_grads = jax.value_and_grad(dense)(args)
    assert 0 < int(n_live) <= cut
    np.testing.assert_allclose(loss, want, rtol=1e-4)
    for key in sorted(args):
        assert bool(jnp.all(jnp.isfinite(grads[key]))), key
        np.testing.assert_allclose(
            grads[key], want_grads[key], rtol=1e-4,
            atol=1e-4 * float(jnp.max(jnp.abs(want_grads[key]))),
            err_msg=key)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_a_layer_that_holds_every_expert_on_kernels_alone_is_the_layer_on_jnp(
        small_tiles, monkeypatch, gated):
    """`moe_ffn(capacity_factor=None)` without `held` (OLMoE's layer: the
    count is k * T, a constant) with every kernel in the interpreter: the
    output and the gradients by x, the router and ALL the experts' matrices
    (no row is dead, so `w_down`'s too) equal the jnp path's, and
    `held=(0, E)` is the same layer but for its statistic. Each direction
    moves the rows by one call of each kernel."""
    c = _layer()
    E = c["router"].shape[1]
    w = {k: c[k] for k in ("w_up", "w_down") + (("w_gate",) if gated else ())}

    def loss(held, x, router, w):
        y, stats = expert.moe_ffn(
            x, router, w["w_up"], w["w_down"], capacity_factor=None,
            top_k=K, w_gate=w.get("w_gate"), renormalize=False, held=held)
        assert ("held" in stats) == (held is not None)
        assert stats["assignments"].shape == (E,)
        return jnp.sum(c["g"] * y), stats["dropped"]

    def both(held):
        return jax.value_and_grad(
            functools.partial(loss, held), argnums=(0, 1, 2), has_aux=True)(
                c["x"], c["router"], w)

    want = both(None)
    assert _pallas_names(lambda: both(None)) == set()
    _interpret_every_kernel(monkeypatch)
    text = str(jax.make_jaxpr(lambda: both(None))())
    for name in profile.MOE_ROWS_KERNELS:  # once forward, once backward
        assert len(re.findall(r"name=%s\b" % name, text)) == 2, name
    for held in (None, (0, E)):
        got = both(held)
        assert int(got[0][1]) == 0
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("held", [(3, 3), None], ids=["held", "all"])
def test_a_layer_forms_its_groups_visits_once_for_all_its_matmuls(
        small_tiles, monkeypatch, held):
    """A gated layer has three grouped products over the same rows and of
    each two gradients: what their kernels are told of the groups
    (`grouped_matmul.layer_visits`: both forms of `visits`, and what the
    two share once) is formed ONCE a layer, forward and backward together,
    where every call formed its own (nine traces of twenty small
    instructions a layer)."""
    c = _layer()
    sl = slice(None) if held is None else slice(held[0], held[0] + held[1])
    calls = {"groups": 0, "forms": []}
    groups, forms = gm._groups_tiles, gm._visits

    def counted_groups(*args):
        calls["groups"] += 1
        return groups(*args)

    def counted_forms(of, visit_empty):
        calls["forms"].append(visit_empty)
        return forms(of, visit_empty)

    def loss(x, w):
        y, _ = expert.moe_ffn(
            x, c["router"], w["w_up"][sl], w["w_down"][sl],
            capacity_factor=None, top_k=K, w_gate=w["w_gate"][sl], held=held)
        return jnp.sum(c["g"] * y)

    _interpret_every_kernel(monkeypatch)
    monkeypatch.setattr(gm, "_groups_tiles", counted_groups)
    monkeypatch.setattr(gm, "_visits", counted_forms)
    w = {k: c[k] for k in ("w_up", "w_down", "w_gate")}
    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(c["x"], w)
    assert calls == {"groups": 1, "forms": [False, True]}
