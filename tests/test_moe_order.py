"""How a routed layer that holds a part of the experts forms the sorted order
of its rows (`parallel/expert.held_order`, PR 55): by counting over the bins
it holds and one sort that carries the weights, against `sort_assignments`'
two argsorts and the turn to the front; `moe_ffn` on it against `moe_ffn` on
the argsorts, bit for bit, every kernel in Pallas' interpreter; the plan; and
the paths that hold every expert or a capacity, whose jaxpr stays what it
was."""

import functools
import hashlib
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

T, K, E = 96, 4, 12


def _chosen(seed, allowed):
    """[T, K] int32: each token's K distinct experts out of `allowed`, in a
    random order."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(np.stack([rng.permutation(allowed)[:K]
                                 for _ in range(T)]), jnp.int32)


# name -> ((first, count), the experts the router may pick)
ROUTINGS = {
    "the_first_bins": ((0, 3), range(E)),
    "bins_in_the_middle": ((5, 4), range(E)),
    "the_last_bins": ((9, 3), range(E)),
    "one_bin": ((4, 1), range(E)),
    "an_empty_bin_among_the_held": ((2, 4), [0, 1, 2, 3, 5, 6, 7, 8, 9, 11]),
    "every_assignment_held": ((3, 6), range(3, 9)),
    "none_held": ((0, 3), range(4, E)),
}


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_counted_order_is_the_sorted_order_turned_to_the_front(case):
    (first, count), allowed = ROUTINGS[case]
    experts = _chosen(len(case), list(allowed))
    weights = jnp.asarray(
        np.random.RandomState(1).rand(K, T), jnp.float32)
    kT = K * T
    flat, order, inv, group_sizes = expert.sort_assignments(experts, E)
    start = int(jnp.sum(group_sizes[:first]))
    sizes = group_sizes[first:first + count]
    n_live = int(jnp.sum(sizes))
    assert n_live == {"every_assignment_held": kT, "none_held": 0}.get(
        case, n_live)
    if case == "an_empty_bin_among_the_held":
        assert 0 in [int(v) for v in sizes]
    want_order = order[(jnp.arange(kT) + start) % kT]
    want_inv = (inv - start) % kT

    got_order, got_inv, scale = jax.jit(
        expert.held_order, static_argnums=2)(flat, weights, first, sizes)
    np.testing.assert_array_equal(got_order[:n_live], want_order[:n_live])
    live = want_inv < n_live
    np.testing.assert_array_equal(got_inv[live], want_inv[live])
    assert bool(jnp.all(got_inv[~live] >= n_live))
    # Behind the run every dead assignment once: a permutation, by which
    # the weights' gradient is sorted back.
    np.testing.assert_array_equal(jnp.sort(got_order), jnp.arange(kT))
    np.testing.assert_array_equal(scale, weights.reshape(-1)[got_order])


def test_no_gradient_flows_through_the_carried_weights():
    experts = _chosen(3, list(range(E)))
    flat, _, _, group_sizes = expert.sort_assignments(experts, E)
    weights = jnp.ones((K, T), jnp.float32)
    d_w = jax.grad(lambda w: jnp.sum(
        expert.held_order(flat, w, 2, group_sizes[2:5])[2]))(weights)
    assert float(jnp.max(jnp.abs(d_w))) == 0.0


PLANS = {
    # (experts, held) -> (order, bins)
    "sdars_16_of_128": ((128, (0, 16)), ("count", 16)),
    "nemotrons_8_of_512": ((512, (8, 8)), ("count", 8)),
    "told_it_holds_them_all": ((64, (0, 64)), ("argsort", 0)),
    "not_told_what_it_holds": ((64, None), ("argsort", 0)),
    "nothing_said": ((None, None), ("argsort", 0)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_plan_says_how_the_order_is_formed(case):
    (experts, held), (order, bins) = PLANS[case]
    plan = profile.moe_rows_plan(4096, 8, 2048, jnp.bfloat16,
                                 experts=experts, held=held)
    assert (plan["order"], plan["bins"]) == (order, bins)
    assert mr.order_plan(experts, held) == (order, bins)
    # The rest of the plan is of the shapes and the backend, as it was.
    rest = mr.rows_plan(4096, 8, 2048, jnp.bfloat16)
    assert {k: v for k, v in plan.items() if k not in ("order", "bins")} \
        == {k: v for k, v in rest.items() if k not in ("order", "bins")}


# --------------------------------------------------------------------------
# Through `moe_ffn`, every kernel in the interpreter, against the argsorts
# --------------------------------------------------------------------------

TILE = 32


@pytest.fixture
def interpreted(monkeypatch):
    """`moe_ffn` on the rows' two kernels, the grouped matmuls' three and
    the activation's two in Pallas' interpreter, on tiles of 32 rows."""
    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)
    monkeypatch.setattr(gm, "BLOCK_ROWS", 32)
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    monkeypatch.setattr(mr, "TILE_ROWS", TILE)
    monkeypatch.setattr(mr, "RESIDENT_BYTES", 64 * 128 * 12)
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


# The routed layers of the four cells that hold a part of their experts, cut
# small: (tokens, width, experts, k, held, expert width, `moe_ffn`'s further
# arguments, a latent's width or None).
LAYERS = {
    # top-8 of 128 by softmax, renormalised, 16 held, silu-gated
    "sdar": (64, 128, 32, 4, (8, 4), 128, dict(gated=True), None),
    # top-8 of 64 by softmax, 16 held: a quarter live
    "mellum": (64, 128, 16, 4, (4, 4), 128, dict(gated=True), None),
    # top-4 of 64 by sigmoid with a selection bias and a scale, 8 held
    "xing": (64, 256, 16, 2, (0, 2), 128,
             dict(gated=True, scoring="sigmoid", scale=2.5, bias=True), None),
    # top-22 of 512 by sigmoid, 8 held: more choices than experts held (the
    # buffer cut to count * T rows), relu2 without a gate, in a latent
    "nemotron": (64, 64, 16, 6, (13, 2), 128,
                 dict(gated=False, scoring="sigmoid", scale=5.0, bias=True,
                      act=expert.relu2), 128),
}


def _layer(case):
    tokens, D, experts, k, held, F, how, latent = LAYERS[case]
    how = dict(how)
    R = D if latent is None else latent
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 8)
    args = dict(
        x=jax.random.normal(ks[0], (tokens, D)),
        router=jax.random.normal(ks[1], (D, experts)),
        w_in=0.3 * jax.random.normal(ks[2], (held[1], R, F)),
        w_out=0.15 * jax.random.normal(ks[3], (held[1], F, R)))
    if how.pop("gated"):
        args["w_gate"] = 0.3 * jax.random.normal(ks[4], (held[1], R, F))
    if latent is not None:
        args["rows"] = jax.random.normal(ks[5], (tokens, R))
    if how.pop("bias", False):
        how["bias"] = 0.1 * jax.random.normal(ks[6], (experts,))
    g = jax.random.normal(ks[7], (tokens, R))

    def loss(a):
        y, stats = expert.moe_ffn(
            a["x"], a["router"], a["w_in"], a["w_out"], capacity_factor=None,
            top_k=k, w_gate=a.get("w_gate"), held=held, rows=a.get("rows"),
            **how)
        return jnp.sum(g * y) + stats["load_balance_loss"], (y, stats)

    return jax.value_and_grad(loss, has_aux=True), args


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_a_held_layer_on_the_counted_order_is_the_layer_on_the_argsorts(
        interpreted, monkeypatch, case):
    """The same rows in the same order: y, every statistic and the
    gradients by the tokens, the latent rows, the router and every matrix
    are the argsorts' path's bit for bit."""
    both, args = _layer(case)
    text = str(jax.make_jaxpr(both)(args))
    # the running count down the bins' rows; the order's sort and d_w's
    assert text.count("cumsum[axis=1") == 1 and text.count(" sort[") == 2
    got = both(args)
    with monkeypatch.context() as m:
        m.setattr(mr, "order_plan", lambda experts, held: ("argsort", 0))
        both, _ = _layer(case)  # a trace of its own
        text = str(jax.make_jaxpr(both)(args))
        assert "cumsum[axis=1" not in text and text.count(" sort[") == 2
        want = both(args)
    held = int(got[0][1][1]["held"])
    assert 0 < held < LAYERS[case][0] * LAYERS[case][3]
    got, want = (jax.tree_util.tree_flatten_with_path(t)[0]
                 for t in (got, want))
    for (path, a), (_, b) in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a))), path
        np.testing.assert_array_equal(a, b, err_msg=str(path))


# --------------------------------------------------------------------------
# Every expert held, and a capacity: the jaxpr they had
# --------------------------------------------------------------------------

# sha256 (16 digits) of the jaxpr of the gradient below, object addresses
# taken out, as PR 55's parent traced it (jax 0.9.0). A change MEANT to move
# one of these paths replaces its hash; PR 55's counted order is beside them
# and does not.
_UNMOVED = {"every_expert_held": "1b25490538080b58",
            "a_capacity": "c917a2089e7d8b26"}


@pytest.mark.parametrize("case", sorted(_UNMOVED))
def test_all_held_and_capacity_paths_keep_their_jaxpr(case):
    tokens, D, experts, F, k = 64, 32, 8, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    args = (jax.random.normal(ks[0], (tokens, D)),
            jax.random.normal(ks[1], (D, experts)),
            jax.random.normal(ks[2], (experts, D, F)),
            jax.random.normal(ks[3], (experts, F, D)),
            jax.random.normal(ks[4], (experts, D, F)))
    factor = {"every_expert_held": None, "a_capacity": 1.25}[case]

    def loss(x, router, w_in, w_out, w_gate):
        y, stats = expert.moe_ffn(x, router, w_in, w_out, factor, top_k=k,
                                  w_gate=w_gate)
        return jnp.sum(y) + stats["load_balance_loss"]

    with jax.default_matmul_precision("highest"):  # whatever a module set
        text = str(jax.make_jaxpr(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
    assert text.count(" sort[") == 2  # `sort_assignments`' two argsorts
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _UNMOVED[case]
