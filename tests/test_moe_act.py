"""The activation between a held routed layer's grouped matmuls, over the
live tiles alone (`ops/moe_act.py`): both kernels in Pallas' interpreter
against autodiff of the plain expressions (``act(g) * h``, ``act(h)``) in
f32: values and every gradient, at counts of 0, 1, a tile's edge, a tile's
middle and all rows; the zeros behind the count and the tiles never written;
dead rows full of NaN and Inf; an activation no table names; the plan; and
`activated_matmul`, the op `moe_ffn` calls: which path it takes, what it
keeps for its backward, and its gradients against the plain expression's."""

import re

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import profile
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops import moe_act as ma
from horovod_tpu.parallel import expert

S, F, TILE = 128, 256, 32
COUNTS = {"none": 0, "one": 1, "a_tiles_edge": 2 * TILE,
          "inside_a_tile": 2 * TILE + 13, "all": S}
FORMS = {"silu_gated": (nn.silu, True), "relu2": (expert.relu2, False)}


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 rows and two column blocks of 128, so that a buffer a CPU
    test can afford crosses tiles and blocks as [65536, 768] crosses the
    real ones."""
    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)
    monkeypatch.setattr(ma, "TILE_ROWS", TILE)
    monkeypatch.setattr(ma, "BLOCK_BYTES", TILE * 128 * 4)


def _operands(dtype, n, poison=jnp.nan, seed=0):
    """(g, h, da) whose rows from `n` on hold `poison`, as the grouped
    matmuls on both sides may leave them, and the same with zeros there."""
    rng = np.random.RandomState(seed)
    dead = (jnp.arange(S) >= n)[:, None]
    clean = [jnp.asarray(rng.randn(S, F), dtype) for _ in range(3)]
    return ([jnp.where(dead, poison, a).astype(dtype) for a in clean],
            [jnp.where(dead, 0, a) for a in clean])


def _values_and_gradients(fn, gated, g, h, da):
    """(a, dg, dh), or (a, dh) without a gate, by autodiff of `fn`."""
    out, vjp = jax.vjp(fn, g, h)
    return (out,) + vjp(da)[0 if gated else 1:]


def _kernels(act, gated, n):
    """The two kernels as `activated_matmul`'s rule calls them: (a, dg, dh)
    or (a, dh), and the backward's second `a`."""
    def fn(g, h, da):
        args = (act, g if gated else None, h, jnp.int32(n))
        tiles = ma._tiles(S, F, h.dtype)
        a, = ma._pallas_act(*args, None, tiles, True)
        *grads, again = ma._pallas_act(*args, da, tiles, True)
        return (a,) + tuple(grads), again
    return fn


def _plain(act, gated, n, dtype):
    """The expression `_experts` had, in f32 and rounded once, the dead
    rows selected away."""
    def fn(g, h):
        g, h = g.astype(jnp.float32), h.astype(jnp.float32)
        a = act(g) * h if gated else act(h)
        return jnp.where((jnp.arange(S) < n)[:, None], a, 0.0).astype(dtype)
    return fn


def _written(n):
    """What the kernel writes of the buffer: the live tiles, the last
    whole."""
    return -(-max(n, 1) // TILE) * TILE


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("count", sorted(COUNTS))
def test_values_and_gradients_are_autodiffs_of_the_plain_expression(
        small_tiles, count, form, dtype):
    (act, gated), n, dtype = FORMS[form], COUNTS[count], jnp.dtype(dtype)
    poisoned, clean = _operands(dtype, n)
    got, again = _kernels(act, gated, n)(*poisoned)
    want = _values_and_gradients(_plain(act, gated, n, dtype), gated, *clean)
    # the backward forms `a` once more, for the last matrices' gradient
    np.testing.assert_array_equal(again[:_written(n)], got[0][:_written(n)])
    tol = 1e-5 if dtype == jnp.float32 else 2e-2   # a bf16 rounding
    for name, a, b in zip(("a", "dg" if gated else "dh", "dh"), got, want):
        assert a.dtype == dtype and a.shape == (S, F), name
        live, behind = a[:_written(n)], a[_written(n):]
        assert bool(jnp.all(jnp.isfinite(live))), name  # no NaN came through
        np.testing.assert_allclose(
            np.asarray(live, np.float32),
            np.asarray(b[:_written(n)], np.float32), atol=tol, rtol=tol,
            err_msg=name)
        # zeros from the count to the tile's end, and no tile behind it was
        # written: the interpreter's buffer is NaN where nothing was stored
        assert float(jnp.max(jnp.abs(live[n:]), initial=0.0)) == 0.0, name
        assert bool(jnp.all(jnp.isnan(behind))), name


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "minus_inf"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_poisoned_dead_rows_give_finite_results_and_gradients(
        small_tiles, form, poison):
    """What the dead rows of `g`, `h` and the cotangent hold is selected
    away, never multiplied by zero: the last live tile comes back finite,
    and equal to what clean operands give, bit for bit."""
    (act, gated), n = FORMS[form], 2 * TILE + 13
    poisoned, clean = _operands(jnp.float32, n, poison)
    fn = _kernels(act, gated, n)
    got, want = fn(*poisoned), fn(*clean)
    for a, b in zip(got[0] + (got[1],), want[0] + (want[1],)):
        assert bool(jnp.all(jnp.isfinite(a[:_written(n)])))
        np.testing.assert_array_equal(a[:_written(n)], b[:_written(n)])


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_derivative_is_the_callables_own_not_a_named_tables(
        small_tiles, gated):
    """`act` is any callable of jnp: its derivative is taken by `jax.vjp`
    inside the kernel's body."""
    def act(v):  # in no table of names
        return jnp.tanh(v) * 0.5 + 0.1 * v * v

    n = S - 5
    poisoned, clean = _operands(jnp.float32, n)
    got, _ = _kernels(act, gated, n)(*poisoned)
    want = _values_and_gradients(_plain(act, gated, n, jnp.float32), gated,
                                 *clean)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_the_kernel_rounds_once_where_the_plain_expression_rounds_twice(
        small_tiles):
    """bf16 operands: the kernel's `a` is the f32 product rounded once, so
    it lies at least as near the f32 result as XLA's expression in bf16
    (which rounds `act(g)` before the product)."""
    (g, h, _), _ = _operands(jnp.bfloat16, S)
    exact = nn.silu(g.astype(jnp.float32)) * h.astype(jnp.float32)
    kernel = _kernels(nn.silu, True, S)(g, h, h)[0][0]
    plain = nn.silu(g) * h   # XLA's, in bf16
    np.testing.assert_array_equal(kernel, exact.astype(jnp.bfloat16))
    off = lambda a: float(jnp.max(jnp.abs(  # noqa: E731
        a.astype(jnp.float32) - exact)))
    assert off(kernel) <= off(plain)


def _pallas_names(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return {name: len(re.findall(r"name=%s\b" % name, text))
            for name in profile.MOE_ACT_KERNELS + profile.MOE_GMM_KERNELS}


G, N = 4, 128   # groups and the last matmul's width


def _layer_operands(n, seed=0):
    """(g, h) poisoned from `n` on, w_out [G, F, N], group sizes that sum to
    `n`, the result's cotangent (finite: the combine's kernel writes it)."""
    (g, h, _), _ = _operands(jnp.float32, n, seed=seed)
    rng = np.random.RandomState(seed + 1)
    w_out = jnp.asarray(0.1 * rng.randn(G, F, N), jnp.float32)
    cuts = np.sort(rng.randint(0, n + 1, G - 1))
    sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [n]])), jnp.int32)
    return g, h, w_out, sizes, jnp.asarray(rng.randn(S, N), jnp.float32)


def _op(act, gated, n, sizes, interpret):
    return lambda g, h, w_out: ma.activated_matmul(
        act, h, jnp.int32(n), w_out, sizes, g if gated else None, interpret)


def _plain_op(act, gated, n, sizes):
    """The expression `_experts` has: XLA's fusion and `lax.ragged_dot`,
    autodiff's to transpose; the dead rows zeros."""
    def fn(g, h, w_out):
        live = (jnp.arange(S) < n)[:, None]
        g, h = jnp.where(live, g, 0.0), jnp.where(live, h, 0.0)
        return jax.lax.ragged_dot(act(g) * h if gated else act(h), w_out,
                                  sizes)
    return fn


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("count", sorted(COUNTS))
def test_the_op_and_its_gradients_are_the_plain_expressions(
        small_tiles, monkeypatch, count, form):
    """`activated_matmul` on kernels alone (the activation's two and the
    grouped matmul's three, on tiles of 32 rows): the result's live rows and
    the gradients by the gate, h and the matrices equal autodiff of the
    plain expression, finite though the operands' dead rows hold NaN."""
    monkeypatch.setattr(gm, "BLOCK_ROWS", TILE)
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    (act, gated), n = FORMS[form], COUNTS[count]
    g, h, w_out, sizes, dy = _layer_operands(n)
    out, vjp = jax.vjp(_op(act, gated, n, sizes, True), g, h, w_out)
    want, want_vjp = jax.vjp(_plain_op(act, gated, n, sizes), g, h, w_out)
    with jax.default_matmul_precision("highest"):
        got = (out,) + vjp(dy)[0 if gated else 1:]
        want = (want,) + want_vjp(jnp.where(
            (jnp.arange(S) < n)[:, None], dy, 0.0))[0 if gated else 1:]
    names = ("y", "dg", "dh", "dw") if gated else ("y", "dh", "dw")
    for name, a, b in zip(names, got, want):
        if a.shape[0] == S:   # rows: the groups' alone are defined
            a, b = a[:n], b[:n]
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_interpreted_call_is_the_kernels_and_off_a_tpu_the_call_is_xlas(
        small_tiles, form):
    """One kernel call forward and one backward, under their own names,
    between the grouped matmul's; what the rule keeps for its backward of
    the [S, F] arrays is the operands (g and h, or h), never `a`; off a
    TPU, not asked to interpret, the plain expression and no kernel."""
    act, gated = FORMS[form]
    g, h, w_out, sizes, dy = _layer_operands(45)

    def both(interpret):
        def f(g, h, w_out, dy):
            out, vjp = jax.vjp(_op(act, gated, 45, sizes, interpret),
                               g, h, w_out)
            return out, vjp(dy)
        return _pallas_names(f, g, h, w_out, dy)

    assert both(True) == dict.fromkeys(
        profile.MOE_ACT_KERNELS + profile.MOE_GMM_KERNELS, 1)
    assert set(both(None).values()) == {0}
    clean = [jnp.where((jnp.arange(S) < 45)[:, None], x, 0.0) for x in (g, h)]
    np.testing.assert_array_equal(
        _op(act, gated, 45, sizes, None)(*clean, w_out),
        _plain_op(act, gated, 45, sizes)(*clean, w_out))
    assert set(profile.MOE_ACT_KERNELS) <= set(profile.KERNELS)
    assert not set(profile.MOE_ACT_KERNELS) & (
        set(profile.MOE_GMM_KERNELS) | set(profile.MOE_ROWS_KERNELS))
    _, residuals = jax.vjp(_op(act, gated, 45, sizes, True), g, h, w_out)
    kept = [x for x in jax.tree_util.tree_leaves(residuals)
            if getattr(x, "shape", ()) == (S, F)]
    assert len(kept) == (2 if gated else 1)
    assert all(bool(jnp.any(jnp.isnan(x))) for x in kept)  # the operands


def test_a_ragged_width_takes_xlas_path_even_when_asked_to_interpret(
        small_tiles):
    """F = 96: no tile takes it; the activation is XLA's, the matmul the
    interpreted kernel."""
    g, h, w_out, sizes, _ = _layer_operands(S)
    g, h, w_out = g[:, :96], h[:, :96], w_out[:, :96]
    names = _pallas_names(_op(nn.silu, True, S, sizes, True), g, h, w_out)
    assert names[profile.MOE_ACT] == 0 and names[profile.MOE_GMM] == 1
    np.testing.assert_allclose(
        _op(nn.silu, True, S, sizes, True)(g, h, w_out),
        _plain_op(nn.silu, True, S, sizes)(g, h, w_out), atol=1e-5,
        rtol=1e-5)


PLANS = {
    # (rows, F, dtype, gated, held, backend) -> (path, rows, columns)
    "sdars_gate_on_a_tpu": ((65536, 768, "bfloat16", True, True, "tpu"),
                            ("kernel", 768)),
    "nemotrons_relu2_on_a_tpu": (
        (32768, 2688, "bfloat16", False, True, "tpu"), ("kernel", 896)),
    "xings_gate_on_a_tpu": ((16384, 1024, "bfloat16", True, True, "tpu"),
                            ("kernel", 1024)),
    "olmoes_gate_every_expert_held": (
        (32768, 1024, "bfloat16", True, False, "tpu"), ("xla", 0)),
    "sdars_gate_off_the_tpu": ((65536, 768, "bfloat16", True, True, "cpu"),
                               ("xla", 0)),
    "a_width_of_192": ((65536, 192, "bfloat16", True, True, "tpu"),
                       ("xla", 0)),
    "a_buffer_that_is_no_whole_tile": (
        (65000, 768, "bfloat16", True, True, "tpu"), ("xla", 0)),
    "a_buffer_of_one_small_tile": ((64, 768, "bfloat16", True, True, "tpu"),
                                   ("xla", 0)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_act_plan_says_which_path_a_call_takes(monkeypatch, case):
    (rows, width, dtype, gated, held, backend), (path, cols) = PLANS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    plan = profile.moe_act_plan(rows, width, jnp.dtype(dtype), gated=gated,
                                held=held)
    assert plan == ma.act_plan(rows, width, jnp.dtype(dtype), gated, held)
    assert plan["path"] == path and plan["buffer_rows"] == rows
    if path == "xla":
        assert plan["calls_a_layer"] == {"forward": 0, "backward": 0}
        assert plan["grid_steps"] == 0
        return
    assert plan["tile_rows"] == ma.TILE_ROWS
    assert plan["tile_rows"] % gm.SUB_ROWS_DRHS == 0
    assert plan["block_cols"] == cols and width % cols == 0
    assert plan["grid_steps"] == rows // plan["tile_rows"] * (width // cols)
    assert plan["calls_a_layer"] == {"forward": 1, "backward": 1}
    assert plan["vmem_bytes"] <= ma._VMEM_LIMIT_BYTES


def test_the_layers_of_a_model_share_one_lowering_of_each_call(small_tiles):
    """The kernels' calls are jitted: two layers' calls are one `jit` of
    `_pallas_act` each in the jaxpr, by the same traced function."""
    g, h, w_out, sizes, _ = _layer_operands(45)

    def two_layers(g, h, w_out):
        op = _op(nn.silu, True, 45, sizes, True)
        return op(g, h, w_out) + op(g, h, 2 * w_out)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "jit" \
                    and eqn.params["name"] == "_pallas_act":
                yield eqn.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    inner = list(calls(jax.make_jaxpr(two_layers)(g, h, w_out).jaxpr))
    assert len(inner) == 2 and inner[0] is inner[1]


def _kernels_in_a_models_lowering(depth, recomputed):
    """{kernel name: times it is lowered} in the module a TPU would be given
    for the gradient of a `depth`-layer model whose layers hold 6 of 16
    experts, the first `recomputed` under `block_remat`."""
    from horovod_tpu import models
    from horovod_tpu.parallel import router_aux_losses

    cfg = models.TransformerConfig(
        vocab_size=128, num_layers=depth, num_heads=2, num_kv_heads=2,
        head_dim=64, embed_dim=128, mlp_dim=128, moe_dim=256, max_seq_len=256,
        attention="dense", moe_experts=16, moe_every=1, moe_top_k=4,
        moe_capacity_factor=None, moe_gated=True, moe_held=(4, 6),
        block_remat=recomputed, dtype=jnp.bfloat16)
    model = models.Transformer(cfg)
    ids = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)["params"])

    def loss(params, ids):
        out, state = model.apply({"params": params}, ids,
                                 mutable=["intermediates"])
        return jnp.mean(out.astype(jnp.float32)) \
            + router_aux_losses(state["intermediates"])[0]

    text = jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    return {name: names.count(name) for name in set(names)}


def test_a_models_layers_share_each_kernels_lowerings_at_any_depth(
        monkeypatch):
    """The start's guard. A `pl.pallas_call` costs a quarter of a second of a
    step's lowering each time it is lowered, so the routed layer's kernels
    are jitted calls that the layers of a model share: the module holds each
    once for the layers that keep their forward, once for those that run it
    again (`block_remat`: the recomputation's policy is ONE object for all of
    them, or JAX's partial evaluation of each jitted call is keyed apart
    layer by layer), once for the forward run again, and the backward's own
    forms - whatever the depth."""
    from horovod_tpu.models import transformer

    assert transformer._keep_hc_stat() is transformer._keep_hc_stat()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shallow = _kernels_in_a_models_lowering(4, 2)
    assert shallow == _kernels_in_a_models_lowering(6, 3)
    assert shallow == {
        # kept, recomputed, run again; the combine's transpose
        profile.MOE_ROWS: 4,
        # kept, recomputed (run again, its result is not used); the
        # dispatch's transpose
        profile.MOE_SUM: 3,
        # the first products [., 128] x [128, 256], the last the other way
        profile.MOE_GMM: 6, profile.MOE_GMM_DLHS: 2, profile.MOE_GMM_DRHS: 2,
        profile.MOE_ACT: 3, profile.MOE_ACT_BWD: 1}
