"""Latent attention with YaRN, scores of two products in the flash kernels,
hyper-connections, sigmoid routing with a selection bias, a shared expert,
an expert layer told which experts it holds, `first_k_dense`, and the
multi-token prediction module: the system against the plain reference
`benchmark/references/xing.py` at small sizes, values and gradients."""

import dataclasses
import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import xing as reference  # noqa: E402
from horovod_tpu import models, parallel, profile  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy)
from horovod_tpu.parallel import expert  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

VOCAB, HIDDEN, HEADS, LENGTH, LAM = 256, 64, 2, 64, 0.3
YARN = {"factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
EXPERTS, HELD, TOP_K = 8, (2, 3), 2


def _cfg(**over):
    base = dict(
        vocab_size=VOCAB, num_layers=3, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, mlp_gated=True, max_seq_len=LENGTH, attention="dense",
        kv_lora_rank=16, q_lora_rank=24, qk_nope_dim=32, qk_rope_dim=16,
        v_head_dim=32, rope_yarn=models.Yarn(64.0, 32, 1, 4096, 1.0, 1.0),
        moe_experts=EXPERTS, moe_every=1, first_k_dense=1, moe_dim=32,
        moe_top_k=TOP_K, moe_capacity_factor=None, moe_gated=True,
        moe_scoring="sigmoid", moe_route_scale=2.0, moe_shared_dim=32,
        moe_held=HELD, hc_mult=4, mtp_depth=1, dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"num_layers": cfg.num_layers, "first_k_dense": cfg.first_k_dense,
            "n": cfg.hc_mult, "eps": cfg.norm_eps,
            "hc_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_clamp": cfg.hc_res_clamp, "nope": cfg.qk_nope_dim,
            "rope": cfg.qk_rope_dim, "rope_base": cfg.rope_base,
            "yarn": YARN, "top_k": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "route_scale": cfg.moe_route_scale, "held": held}


def _seeded(cfg, batch=1, seed=0):
    """(model, parameters with every vector moved off its initial value —
    norm scales, the hyper-connections' scales and biases, the selection
    bias — and alpha at a scale at which the maps vary from token to
    token, tokens [batch, LENGTH])."""
    model = models.Transformer(cfg)
    k_p, k_t, k_n = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (batch, LENGTH), 0, VOCAB, jnp.int32)
    params = model.init(k_p, tokens)["params"]
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(k_n, len(flat))
    out = []
    for key, (path, x) in zip(keys, flat):
        name = getattr(path[-1], "key", "")
        if name == "alpha":
            x = jax.random.uniform(key, x.shape, x.dtype, 0.2, 0.4)
        elif name == "bias":  # of a hyper-connection
            n = int(round((-2 + (4 + 4 * x.shape[0]) ** 0.5) / 2))
            x = jnp.concatenate([jnp.zeros((2 * n,)), 1.5 * jnp.eye(
                n).reshape(-1)]) + 0.5 * jax.random.normal(key, x.shape)
        elif x.ndim == 1:  # norm scales, the selection bias
            x = x + 0.3 * jax.random.normal(key, x.shape)
        out.append(x)
    return model, jax.tree_util.tree_unflatten(tree, out), tokens


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


# --------------------------------------------------------------------------
# The hyper-connection
# --------------------------------------------------------------------------

def _hc_case(n, seed=0, C=32, T=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k = 2 * n + n * n
    p = {"phi": 0.1 * jax.random.normal(ks[0], (n * C, k)),
         "bias": jnp.concatenate([jnp.zeros((2 * n,)),
                                  1.5 * jnp.eye(n).reshape(-1)])
         + 0.5 * jax.random.normal(ks[1], (k,)),
         "alpha": jax.random.uniform(ks[2], (3,), minval=0.2, maxval=0.4)}
    X = jax.random.normal(ks[3], (n, 1, T, C))
    w = 0.2 * jax.random.normal(ks[4], (C, C))
    return p, X, w


@pytest.mark.parametrize("n", [2, 4])
def test_hyper_connection_maps_and_mixed_state_agree_with_the_reference(n):
    p, X, w = _hc_case(n)
    arch = {"eps": 1e-6, "hc_iters": 20, "hc_eps": 1e-6,
            "hc_clamp": (-30.0, 30.0)}
    pre, post, res = transformer.hc_maps(
        X, p["phi"], p["bias"], p["alpha"], 20, 1e-6, (-30.0, 30.0), 1e-6)
    Xr = jnp.moveaxis(X[:, 0], 0, 1)  # [T, n, C]
    r_pre, r_post, r_res = reference.hyper_connection_maps(Xr, p, arch)
    for got, want in ((pre[0], r_pre), (post[0], r_post), (res[0], r_res)):
        _close(got, want, 2e-6)
    # doubly stochastic, and not the identity: the iterations did work
    assert float(jnp.max(jnp.abs(jnp.sum(res, -1) - 1))) < 1e-4
    assert float(jnp.max(jnp.abs(jnp.sum(res, -2) - 1))) < 1e-4
    assert float(jnp.max(jnp.abs(res - jnp.eye(n)))) > 0.1
    assert float(jnp.std(res[0, :, 0, 0])) > 1e-2  # varies by token
    branch = lambda h: jnp.tanh(h @ w)  # noqa: E731
    got = transformer.hc_write(res, post, X,
                               branch(transformer.hc_read(pre, X)))
    want, _ = reference.hyper_connected(Xr, p, arch, branch)
    _close(jnp.moveaxis(got[:, 0], 0, 1), want, 2e-6)


def test_one_stream_with_identity_maps_is_the_plain_residual():
    X = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    one = jnp.ones((2, 8, 1))
    assert jnp.array_equal(transformer.hc_read(one, X), X[0])
    got = transformer.hc_write(one[..., None], one, X, y)
    assert jnp.array_equal(got[0], X[0] + y)
    # and a 1 x 1 map comes out of the iterations as 1
    m = transformer.sinkhorn(jnp.exp(jnp.full((3, 1, 1), 0.7)), 20, 1e-6)
    _close(m, jnp.ones((3, 1, 1)), 2e-6)


def test_hc_stats_reads_the_largest_deviation_of_the_sums():
    model, params, tokens = _seeded(_cfg())
    _, state = model.apply({"params": params}, tokens, return_hidden=True,
                           mutable=["intermediates"])
    off = float(models.hc_stats(state["intermediates"]))
    assert 0.0 <= off < 1e-4
    few = models.Transformer(_cfg(hc_sinkhorn_iters=1))
    _, state = few.apply({"params": params}, tokens, return_hidden=True,
                         mutable=["intermediates"])
    assert float(models.hc_stats(state["intermediates"])) > 1e-2
    with pytest.raises(ValueError, match="no HyperConnection"):
        models.hc_stats({})


# --------------------------------------------------------------------------
# Latent attention and YaRN
# --------------------------------------------------------------------------

def test_yarn_frequencies_and_scale_by_hand():
    got = transformer.yarn_inv_freq(64, 10000.0,
                                    models.Yarn(64.0, 32, 1, 4096, 1.0, 1.0))
    want = reference.yarn_inv_freq(64, 10000.0, 64, 32, 1, 4096)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    plain = [10000.0 ** (-i / 32) for i in range(32)]
    # the correction range of 32 and 1 turns over 4096 positions: (10, 23)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], [f / 64 for f in plain[23:]],
                               rtol=1e-12)
    g = 1 - (15 - 10) / 13
    assert got[15] == pytest.approx(plain[15] / 64 * (1 - g) + plain[15] * g)
    assert transformer.yarn_mscale(64.0, 1.0) == pytest.approx(1.41589,
                                                               abs=1e-5)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_latent_attention_agrees_with_the_reference(attention):
    cfg = _cfg(attention=attention)
    module = transformer.LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    p = module.init(jax.random.PRNGKey(1), x, pos)["params"]
    p = jax.tree_util.tree_map(
        lambda t: t + 0.2 if t.ndim == 1 else t, p)  # the norms' scales
    got = module.apply({"params": p}, x, pos)[0]
    want = reference.latent_attention(x[0], p, _arch(cfg))
    _close(got, want, 5e-6)


# --------------------------------------------------------------------------
# The flash kernels with scores of two products (Pallas' interpreter)
# --------------------------------------------------------------------------

def _two_product_case(B=1, H=4, L=256, D=128, D2=64, G=None):
    G = G or H
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    shapes = [(B, H, L, D), (B, G, L, D), (B, G, L, D), (B, H, L, D2),
              (B, 1, L, D2), (B, H, L, D)]
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def _dense_two_products(q, k, v, q2, k2, scale):
    H, G = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k)
         + jnp.einsum("bhqd,bxkd->bhqk", q2, k2)) * scale
    L = q.shape[2]
    s = jnp.where(jnp.arange(L)[:, None] >= jnp.arange(L)[None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# how the backward runs: one kernel, two resident kernels, caller's blocks,
# and a head group (two query heads a kv head) beside the shared key
TWO_PRODUCT_CASES = {
    "one_kernel": dict(), "two_kernels": dict(split=True),
    "blocks_128_256": dict(blocks=(128, 256)),
    "blocks_256_128_split": dict(blocks=(256, 128), split=True),
    "grouped": dict(G=2)}


@pytest.mark.parametrize("case", list(TWO_PRODUCT_CASES))
def test_flash_two_products_forward_and_every_gradient(case, monkeypatch):
    how = TWO_PRODUCT_CASES[case]
    q, k, v, q2, k2, g = _two_product_case(G=how.get("G"))
    scale = 0.11
    want, vjp = jax.vjp(lambda *a: _dense_two_products(*a, scale),
                        q, k, v, q2, k2)
    want_grads = vjp(g)
    bq, bk = how.get("blocks", (None, None))
    B, H, L, D = q.shape
    plans = fa.flash_plan(B, H, L, D, H // k.shape[1], q.dtype, True,
                          block_q=bq, block_k=bk, shared_dim=64)
    assert list(plans) == [profile.FLASH_BWD]
    budget = fa.RESIDENT_VMEM_BUDGET
    if how.get("split"):
        # Since PR 56 a budget under the one kernel's sum gives the one
        # kernel held by the q block (`tests/test_kanana.py`); the two
        # resident kernels are what is left with neither form allowed.
        monkeypatch.setattr(fa, "_BWD_HELD", ())
        assert {n: p.path for n, p in fa.flash_plan(
            B, H, L, D, 1, q.dtype, True, block_q=bq, block_k=bk,
            shared_dim=64).items()} == {profile.FLASH_DQ: "resident",
                                        profile.FLASH_DKV: "resident"}
    out, lse = fa._pallas_forward_lse(q, k, v, scale, True, True, bq, bk,
                                      shared=(q2, k2))
    _close(out, want, 5e-6)
    grads = fa._pallas_backward(q, k, v, out, lse, g, scale, True, True, bq,
                                bk, vmem_budget=budget, shared=(q2, k2))
    # dQ, dK, dV, then the second product's: dQ2 a head, dK2 ONE key's,
    # the sum over the heads
    for got, want_g in zip(grads, want_grads):
        _close(got, want_g, 2e-5)
    assert grads[4].shape == k2.shape


@pytest.mark.parametrize("interpret", [True, None])
def test_flash_two_products_custom_vjp(interpret):
    q, k, v, q2, k2, g = _two_product_case(L=128)
    want = jax.grad(lambda *a: jnp.sum(_dense_two_products(*a, 0.1) * g),
                    argnums=(0, 1, 2, 3, 4))(q, k, v, q2, k2)
    got = jax.grad(lambda *a: jnp.sum(fa._flash_shared(
        *a, 0.1, True, interpret) * g), argnums=(0, 1, 2, 3, 4))(
            q, k, v, q2, k2)
    for a, b in zip(got, want):
        _close(a, b, 2e-5)


def test_flash_plan_answers_for_two_score_widths():
    plan = profile.flash_plan(1, 32, 4096, 128, shared_dim=64)
    assert plan[profile.FLASH_FWD].path == "resident"
    bwd = profile.flash_plan(1, 32, 4096, 128, backward=True, shared_dim=64)
    assert list(bwd) == [profile.FLASH_BWD]
    assert bwd[profile.FLASH_BWD].resident_bytes == 22 * 2 ** 20
    plain = profile.flash_plan(1, 32, 4096, 128, backward=True)
    assert plain[profile.FLASH_BWD].resident_bytes == 16 * 2 ** 20
    # past that budget the one kernel is held by the q block (PR 56;
    # `tests/test_kanana.py` holds the plans by length): no plan is empty
    far = profile.flash_plan(1, 32, 8192, 128, backward=True, shared_dim=64)
    assert [(n, p.held) for n, p in far.items()] == [
        (profile.FLASH_BWD, "q")]
    # and without a second product the plan is what it was
    assert profile.flash_plan(2, 16, 2048, 128, backward=True) == \
        profile.flash_plan(2, 16, 2048, 128, backward=True, shared_dim=0)


REFUSED_FLASH = {
    "a_mask_by_rule": (dict(mask=fa.BlockDiffusionMask(64, 4)), ValueError,
                       "cannot be combined with q_shared"),
    "one_without_the_other": (dict(k_shared=None), ValueError, "together"),
    "a_wider_v": (dict(v_wide=True), ValueError, "as wide as k")}


@pytest.mark.parametrize("case", list(REFUSED_FLASH))
def test_flash_two_products_refuses_by_name(case):
    over, error, match = REFUSED_FLASH[case]
    q, k, v, q2, k2, _ = (t.transpose(0, 2, 1, 3)
                          for t in _two_product_case(L=128))
    if over.pop("v_wide", False):
        v = jnp.concatenate([v, v], axis=-1)
    kw = dict(q_shared=q2, k_shared=k2)
    kw.update(over)
    with pytest.raises(error, match=match):
        fa.flash_attention(q, k, v, **kw)


def test_gridded_kernels_take_a_second_product():
    """Until PR 56 a refusal ("resident kernels only"); now the gridded
    forward, dQ and dK/dV hold the second pair of operands too."""
    q, k, v, q2, k2, g = _two_product_case(L=128)
    want, vjp = jax.vjp(lambda *a: _dense_two_products(*a, 0.1),
                        q, k, v, q2, k2)
    assert fa.flash_plan(*q.shape, 1, q.dtype, vmem_budget=0, shared_dim=64)[
        profile.FLASH_FWD].path == "gridded"
    out, lse = fa._pallas_forward_lse(q, k, v, 0.1, True, True,
                                      vmem_budget=0, shared=(q2, k2))
    _close(out, want, 5e-6)
    grads = fa._pallas_backward(q, k, v, out, lse, g, 0.1, True, True,
                                vmem_budget=0, shared=(q2, k2))
    for got, want_g in zip(grads, vjp(g)):
        _close(got, want_g, 2e-5)


# --------------------------------------------------------------------------
# The router, the shared expert, the experts held
# --------------------------------------------------------------------------

def test_sigmoid_router_bias_moves_the_choice_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, EXPERTS))
    bias = jnp.zeros((EXPERTS,)).at[5].set(3.0).at[0].set(-3.0)
    w0, e0, s = expert.route(logits, TOP_K, True, "sigmoid", None, 2.0)
    w1, e1, s1 = expert.route(logits, TOP_K, True, "sigmoid", bias, 2.0)
    assert jnp.array_equal(s, jax.nn.sigmoid(logits)) and \
        jnp.array_equal(s, s1)
    assert not jnp.array_equal(e0, e1)
    assert bool(jnp.all(jnp.any(e1 == 5, axis=-1)))   # always chosen
    assert not bool(jnp.any(e1 == 0))                 # never
    # the weights are the chosen SCORES, renormalised, times 2: no bias
    picked = jnp.take_along_axis(s, e1, axis=-1)
    _close(w1, 2.0 * picked / jnp.sum(picked, -1, keepdims=True), 1e-6)
    _close(jnp.sum(w1, -1), jnp.full((64,), 2.0), 1e-6)
    raw, _, _ = expert.route(logits, TOP_K, False, "sigmoid", bias, 1.0)
    _close(raw, picked, 1e-6)
    # against the reference's mask and weights
    chosen = reference.top_k_mask(s + bias, TOP_K)
    assert jnp.array_equal(
        jnp.any(jax.nn.one_hot(e1, EXPERTS, dtype=bool), axis=-2), chosen)
    with pytest.raises(ValueError, match="sigmoid"):
        expert.route(logits, TOP_K, True, "softmax", bias)


@pytest.fixture(params=["jnp", "kernel"])
def rows_path(request, monkeypatch):
    """How a held layer moves its rows (`ops/moe_rows.py`): by jnp, as the
    CPU does, or by the two kernels in Pallas' interpreter, on tiles small
    enough that a test's buffer crosses them. Returns `_layer`'s sizes:
    the kernels take a width that is a multiple of 128."""
    if request.param == "jnp":
        return {}
    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import moe_rows as mr

    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)
    monkeypatch.setattr(mr, "TILE_ROWS", 32)
    for name in ("dispatch", "combine"):
        monkeypatch.setattr(mr, name, functools.partial(
            getattr(mr, name), interpret=True))
    return {"D": 128, "T": 64}


def _rows_kernels(fn, *args):
    """How often the jaxpr of `fn(*args)` calls each kernel of
    `ops/moe_rows.py`."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return {name: _count(jaxpr, lambda eqn, name=name: (
        eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == name))
        for name in profile.MOE_ROWS_KERNELS}


def _layer(E=16, D=32, F=24, T=96, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, D)),
        router=jax.random.normal(ks[1], (D, E)),
        w_gate=0.3 * jax.random.normal(ks[2], (E, D, F)),
        w_up=0.3 * jax.random.normal(ks[3], (E, D, F)),
        w_down=0.3 * jax.random.normal(ks[4], (E, F, D)),
        bias=0.5 * jax.random.normal(ks[5], (E,)))


def _held_call(c, first, count, x=None, weights=None):
    w = weights or c
    sl = slice(first, first + count)
    return expert.moe_ffn(
        c["x"] if x is None else x, c["router"], w["w_up"][sl],
        w["w_down"][sl], capacity_factor=None, top_k=4, w_gate=w["w_gate"][sl],
        scoring="sigmoid", bias=c["bias"], scale=2.0, held=(first, count))


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_the_shares_of_the_ranks_add_up_to_the_uncut_layer(ranks, rows_path):
    c = _layer(**rows_path)
    calls = _rows_kernels(lambda x: _held_call(c, 0, 2, x)[0], c["x"])
    assert set(calls.values()) == {1 if rows_path else 0}
    E = c["router"].shape[1]
    whole, stats = expert.moe_ffn(
        c["x"], c["router"], c["w_up"], c["w_down"], capacity_factor=None,
        top_k=4, w_gate=c["w_gate"], scoring="sigmoid", bias=c["bias"],
        scale=2.0)
    count = E // ranks
    parts = [_held_call(c, r * count, count) for r in range(ranks)]
    _close(sum(y for y, _ in parts), whole, 1e-5)
    held = [int(s["held"]) for _, s in parts]
    assert sum(held) == 4 * c["x"].shape[0] == int(stats["assignments"].sum())
    for r, (_, s) in enumerate(parts):  # every rank routes over ALL experts
        assert jnp.array_equal(s["assignments"], stats["assignments"])
        assert held[r] == int(stats["assignments"][r * count:(r + 1)
                                                   * count].sum())
        assert int(s["dropped"]) == 0


def test_held_gradients_add_up_and_reach_no_absent_expert(rows_path):
    c = _layer(**rows_path)
    g = jax.random.normal(jax.random.PRNGKey(9), c["x"].shape)

    def whole(x, w):
        return jnp.sum(g * expert.moe_ffn(
            x, c["router"], w["w_up"], w["w_down"], capacity_factor=None,
            top_k=4, w_gate=w["w_gate"], scoring="sigmoid", bias=c["bias"],
            scale=2.0)[0])

    def cut(x, w):
        return sum(jnp.sum(g * _held_call(c, f, 4, x, w)[0])
                   for f in (0, 4, 8, 12))

    w = {k: c[k] for k in ("w_up", "w_down", "w_gate")}
    want = jax.grad(whole, argnums=(0, 1))(c["x"], w)
    got = jax.grad(cut, argnums=(0, 1))(c["x"], w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 2e-5)


def test_a_models_step_moves_the_held_rows_by_the_kernels_alone(rows_path):
    """The lowered step of a small model with held experts: each routed
    layer calls `hvd_moe_rows` and `hvd_moe_sum` once forward and once
    backward, and no gather makes a [k*T, D] array. By jnp, off the TPU:
    no kernel, and the rows in sorted order gathered once each way."""
    cfg = _cfg(num_layers=2, embed_dim=128, moe_top_k=2)
    model, params, tokens = _seeded(cfg)
    step = lambda p: jax.grad(  # noqa: E731
        lambda q: _system(model, q, tokens)[2])(p)
    routed = 2  # the second block's and the module's
    calls = _rows_kernels(step, params)
    buffer = (cfg.moe_top_k * LENGTH, cfg.embed_dim)
    gathers = _count(jax.make_jaxpr(step)(params).jaxpr, lambda eqn: (
        eqn.primitive.name == "gather"
        and eqn.outvars[0].aval.shape == buffer))
    if rows_path:
        assert calls == {profile.MOE_ROWS: 2 * routed,
                         profile.MOE_SUM: 2 * routed}
        assert gathers == 0
    else:
        assert set(calls.values()) == {0} and gathers == 2 * routed


@pytest.mark.parametrize("shared", [True, False])
def test_routed_layer_with_a_shared_expert_agrees_with_the_reference(shared):
    module = expert.MoeMlp(
        num_experts=EXPERTS, mlp_dim=32, capacity_factor=None, top_k=TOP_K,
        gated=True, dtype=jnp.float32, scoring="sigmoid", route_scale=2.0,
        held=HELD, shared_dim=32 if shared else None)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    p = module.init(jax.random.PRNGKey(1), x)["params"]
    p = dict(p, select_bias=0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                                    (EXPERTS,)))
    assert p["w_gate"].shape[0] == HELD[1] and p["router"].shape[1] == EXPERTS
    y, state = module.apply({"params": p}, x, mutable=["intermediates"])
    arch = {"top_k": TOP_K, "norm_topk_prob": True, "route_scale": 2.0,
            "held": HELD}
    if not shared:
        zero = {"kernel": jnp.zeros((HIDDEN, 32))}
        p = dict(p, shared_gate=zero, shared_up=zero,
                 shared_down={"kernel": jnp.zeros((32, HIDDEN))})
    want, chosen = reference.routed_ffn(x[0], p, arch)
    _close(y[0], want, 5e-6)
    stats = parallel.routing_stats(state["intermediates"])
    assert jnp.array_equal(jnp.any(jax.nn.one_hot(
        stats["chosen"][0], EXPERTS, dtype=bool), axis=-2), chosen)
    share = float(jnp.sum(chosen[:, HELD[0]:HELD[0] + HELD[1]])
                  / (TOP_K * LENGTH))
    assert float(stats["held_share"][0]) == pytest.approx(share)
    # no gradient reaches the selection bias
    grads = jax.grad(lambda q: jnp.sum(module.apply({"params": q}, x) ** 2))(
        dict(p) if shared else {k: v for k, v in p.items()
                                if not k.startswith("shared")})
    assert float(jnp.max(jnp.abs(grads["select_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["router"]))) > 0.0


# --------------------------------------------------------------------------
# The whole model
# --------------------------------------------------------------------------

def _system(model, params, tokens, chunk=16):
    (hid, hid_mtp), state = model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"])
    rows = jnp.concatenate([hid, hid_mtp], axis=1)
    targets = jnp.concatenate([jnp.roll(tokens, -1, 1),
                               jnp.roll(tokens, -2, 1)], axis=1)
    per_row = 1.0 / tokens.size
    weights = jnp.concatenate([jnp.full(tokens.shape, per_row),
                               jnp.full(tokens.shape, LAM * per_row)], axis=1)
    loss = chunked_softmax_cross_entropy(
        rows, params["lm_head"]["kernel"], targets, chunk=chunk,
        weights=weights)
    return hid, hid_mtp, loss, state["intermediates"]


MODEL_CASES = {"dense": dict(), "flash": dict(attention="flash"),
               "hc_remat": dict(hc_remat=True),
               "two_streams": dict(hc_mult=2)}


@pytest.mark.parametrize("case", list(MODEL_CASES))
def test_hidden_states_losses_and_gradients_agree_with_the_reference(case):
    cfg = _cfg(**MODEL_CASES[case])
    model, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    hid, hid_mtp, loss, inter = _system(model, params, tokens)
    ref = reference.forward(params, tokens[0], arch, LAM)
    _close(hid[0], ref["hidden"], 1e-5)
    _close(hid_mtp[0], ref["hidden_mtp"], 1e-5)
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=1e-5)
    assert float(ref["loss"]) == pytest.approx(
        float(ref["ce"] + LAM * ref["ce_mtp"]), rel=1e-6)
    stats = parallel.routing_stats(inter)
    assert jnp.array_equal(jnp.any(jax.nn.one_hot(
        stats["chosen"][:, :LENGTH], EXPERTS, dtype=bool), axis=-2),
        ref["chosen"])  # 2 routed layers and the module's
    assert ref["chosen"].shape[0] == 3
    got = jax.grad(lambda p: _system(model, p, tokens)[2])(params)
    want = jax.grad(lambda p: reference.forward(p, tokens[0], arch,
                                                LAM)["loss"])(params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", ["no_shared", "one_iteration",
                                   "no_module_loss", "uncut_router"])
def test_the_comparison_tells_the_mechanism(wrong):
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    hid, _, loss, _ = _system(model, params, tokens)
    arch = _arch(cfg)
    if wrong == "no_shared":
        ref = reference.forward(params, tokens[0], arch, LAM, shared=0.0)
    elif wrong == "one_iteration":
        ref = reference.forward(params, tokens[0], arch, LAM, iters=1)
    elif wrong == "uncut_router":  # a router that sees the held ones only
        ref = reference.forward(params, tokens[0], dict(arch, top_k=1), LAM)
    else:
        ref = reference.forward(params, tokens[0], arch, LAM)
        assert abs(float(loss) - float(ref["ce"])) > 1e-2 * float(ref["ce"])
        return
    assert float(jnp.max(jnp.abs(hid[0] - ref["hidden"]))) > 1e-2


def test_first_k_dense_and_the_expert_width_shape_the_blocks():
    model, params, _ = _seeded(_cfg())
    assert "mlp_gate" in params["block_0"] and \
        "moe_mlp" not in params["block_0"]
    for b in ("block_1", "block_2", "mtp_block"):
        moe = params[b]["moe_mlp"]
        assert moe["w_gate"].shape == (HELD[1], HIDDEN, 32)
        assert moe["shared_up"]["kernel"].shape == (HIDDEN, 32)
        assert moe["router"].shape == (HIDDEN, EXPERTS)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (HIDDEN, 96)
    assert params["block_0"]["hc_attn"]["phi"].shape == (4 * HIDDEN, 24)
    assert params["mtp_proj"]["kernel"].shape == (2 * HIDDEN, HIDDEN)
    # `moe_every` keeps its meaning beside it: every second block from 1
    every = models.Transformer(_cfg(moe_every=2, num_layers=4))
    shapes = jax.eval_shape(every.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, LENGTH), jnp.int32))["params"]
    assert ["moe_mlp" in shapes["block_%d" % i] for i in range(4)] == \
        [False, True, False, True]


def test_the_options_off_are_the_model_as_it_was():
    plain = models.TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, max_seq_len=LENGTH, moe_experts=4, moe_every=2,
        moe_capacity_factor=None, dtype=jnp.float32)
    off = dataclasses.replace(plain, first_k_dense=0, hc_mult=1, mtp_depth=0)
    tokens = jnp.arange(LENGTH, dtype=jnp.int32)[None] % VOCAB
    a = models.Transformer(plain)
    params = a.init(jax.random.PRNGKey(0), tokens)["params"]
    assert str(jax.make_jaxpr(lambda p: a.apply({"params": p}, tokens))(
        params)) == str(jax.make_jaxpr(lambda p: models.Transformer(
            off).apply({"params": p}, tokens))(params))
    assert "select_bias" not in params["block_1"]["moe_mlp"]


REFUSED = {
    "tp_axis with latent attention": (dict(
        tp_axis="tp", moe_experts=None, moe_held=None, mlp_gated=False),
        "tp_axis cannot"),
    "sp_axis with hyper-connections": (dict(
        sp_axis="sp", kv_lora_rank=None, rope_yarn=None), "sp_axis cannot"),
    "a looped stack with the module": (dict(num_passes=2), "num_passes"),
    "ep_axis with held experts": (dict(
        ep_axis="ep", moe_capacity_factor=None), "dropless|moe_held"),
    "held experts with a capacity": (dict(moe_capacity_factor=1.25),
                                     "moe_held is the dropless"),
    "ring attention with latent attention": (dict(attention="ring"),
                                             "kv_lora_rank"),
    "yarn without latent attention": (dict(kv_lora_rank=None), "rope_yarn"),
    "two prediction modules": (dict(mtp_depth=2), "mtp_depth=2"),
    "no stream": (dict(hc_mult=0), "hc_mult=0"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    over, match = REFUSED[case]
    with pytest.raises(ValueError, match=match):
        _cfg(**over)


def test_latent_keys_without_latent_queries_are_built():
    """Until PR 56 a refusal ("needs q_lora_rank"); now the queries come
    straight from the state (`tests/test_kanana.py` holds the layer to its
    reference)."""
    cfg = _cfg(q_lora_rank=None)
    _, params, _ = _seeded(cfg)
    attn = params["block_0"]["attn"]
    assert "q" in attn and not {"q_a", "q_norm", "q_b"} & set(attn)
    assert attn["q"]["kernel"].shape == (HIDDEN, HEADS, 32 + 16)


@pytest.mark.parametrize("field,over", [
    ("first_k_dense", dict(first_k_dense=1)),
    ("moe_dim", dict(moe_dim=32)),
    ("moe_scoring", dict(moe_scoring="sigmoid")),
    ("moe_shared_dim", dict(moe_shared_dim=32)),
    ("hc_mult", dict(hc_mult=2)),
    ("mtp_depth", dict(mtp_depth=1))])
def test_tp_axis_refuses_each_new_field_by_its_name(field, over):
    with pytest.raises(ValueError, match=field):
        models.TransformerConfig(tp_axis="tp", **over)


def test_moe_ffn_refuses_held_with_a_capacity_or_an_axis():
    c = _layer()
    with pytest.raises(ValueError, match="held"):
        expert.moe_ffn(c["x"], c["router"], c["w_up"][:4], c["w_down"][:4],
                       capacity_factor=1.25, held=(0, 4))
    with pytest.raises(ValueError, match="matrices held"):
        expert.moe_ffn(c["x"], c["router"], c["w_up"][:3], c["w_down"][:3],
                       capacity_factor=None, held=(0, 4))


def test_the_program_names_the_new_parts():
    model, params, tokens = _seeded(_cfg())
    text = jax.jit(lambda p: jax.grad(lambda q: _system(
        model, q, tokens)[2])(p)).lower(params).as_text(debug_info=True)
    for scope in profile.HC_SCOPES + (profile.MTP, profile.MOE_SHARED):
        assert scope in text, scope
    # a block under the module and under a connection keeps its halves
    assert "%s/%s/mtp_block/attn" % (profile.MTP, profile.BLOCK) in text
    assert "%s/mtp_block/%s/hc_mlp/%s" % (profile.BLOCK, profile.HC,
                                          profile.HC_MAP) in text


# --------------------------------------------------------------------------
# The statistic of a connection is formed once a step (PR 35)
# --------------------------------------------------------------------------

def _count(jaxpr, wanted):
    """Equations of `jaxpr`, and of every jaxpr inside one, that `wanted`
    holds for."""
    total = 0
    for eqn in jaxpr.eqns:
        total += bool(wanted(eqn))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _count(inner, wanted)
    return total


def _is_stat_kernel(eqn):
    return (eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == profile.HC_STAT)


@pytest.mark.parametrize("block_remat", [0, 1])
def test_a_recomputation_keeps_the_statistic_and_forms_no_second(
        monkeypatch, block_remat):
    """Under `hc_remat`, and in a block that `block_remat` runs again, the
    backward pass holds no second evaluation: each of the 6 connections (2
    blocks and the module's) calls the kernel once in the whole step (and
    the kernel of phi's gradient once), its
    two results are saved by name, and no reduction over the stream and
    the lane dimension (the form the statistic had) is in the program."""
    from horovod_tpu.ops import hc_stat as hs

    # The kernel itself (in the interpreter), so that an evaluation is one
    # equation to count.
    real = hs.hc_stat
    monkeypatch.setattr(hs, "hc_stat", lambda X, phi: real(
        X, phi, interpret=True))
    cfg = _cfg(num_layers=2, embed_dim=128, hc_remat=True,
               block_remat=block_remat)
    model, params, tokens = _seeded(cfg)
    loss = lambda p: _system(model, p, tokens)[2]  # noqa: E731
    step = jax.make_jaxpr(jax.grad(loss))(params)
    assert _count(step.jaxpr, _is_stat_kernel) == 6
    assert _count(step.jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
                  and eqn.params["name"] == profile.HC_STAT_DPHI) == 6
    forward = jax.make_jaxpr(loss)(params)
    assert _count(forward.jaxpr, _is_stat_kernel) == 6
    streams = (cfg.hc_mult, 1, LENGTH, cfg.embed_dim)

    def reduces_the_streams(eqn):
        return (eqn.primitive.name == "reduce_sum"
                and eqn.invars[0].aval.shape == streams
                and {0, 3} <= set(eqn.params["axes"]))

    assert _count(step.jaxpr, reduces_the_streams) == 0
    # the sum of squares and the projection of each carry the name
    assert _count(forward.jaxpr, lambda eqn: eqn.primitive.name == "name"
                  and eqn.params["name"] == profile.HC_STAT) == 2 * 6
    for c in range(6):
        plan = profile.hc_plan(cfg.hc_mult, LENGTH, cfg.embed_dim, 24,
                               jnp.float32, hc_remat=True,
                               block_remat=c < 2 * block_remat)
        assert (plan["path"], plan["evaluations"]) == ("kernel", 1)


def test_without_streams_the_blocks_recomputation_is_what_it_was(
        monkeypatch):
    """A model with `hc_mult` 1 names nothing, so `block_remat`'s policy
    (keep the named values alone) keeps nothing: the step lowers to the
    text of the recomputation without a policy."""
    cfg = models.TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, max_seq_len=LENGTH, block_remat=1, dtype=jnp.float32)
    tokens = jnp.arange(LENGTH, dtype=jnp.int32)[None] % VOCAB
    params = models.Transformer(cfg).init(jax.random.PRNGKey(0),
                                          tokens)["params"]

    def text():
        model = models.Transformer(cfg)
        return jax.jit(jax.grad(lambda p: jnp.sum(model.apply(
            {"params": p}, tokens) ** 2))).lower(params).as_text()

    with_policy = text()
    monkeypatch.setattr(transformer, "_keep_hc_stat", lambda: None)
    assert text() == with_policy
    # and the recomputation is there: the first block's, not the second's
    step = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(models.Transformer(
        cfg).apply({"params": p}, tokens) ** 2)))(params)
    assert _count(step.jaxpr, lambda eqn: eqn.primitive.name == "remat2") == 1
