"""Full and window attention layers of DIFFERENT head counts in one stack on
the normal train path (`TransformerConfig.attention_shapes`: heads, rotary
base, rotated width and YaRN by kind, a `models.Layer`'s `shape`), the gate a
head (`attention_gate`), a dense layer ahead of sigmoid-routed experts of
which a device holds a part beside a shared one: the system against the plain
reference `benchmark/references/laguna.py` at small sizes, values and
gradients."""

import hashlib
import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import laguna as reference  # noqa: E402
from horovod_tpu import models, profile  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Attention, AttentionShape, Yarn)
from horovod_tpu.ops import BandMask  # noqa: E402
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy)
from horovod_tpu.parallel import expert  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

VOCAB, HIDDEN, LENGTH, WINDOW, HEAD_DIM = 96, 64, 32, 8, 16
HEADS, KV_HEADS = {"full": 6, "window": 8}, 2
EXPERTS, HELD, TOP_K, SCALE = 16, (4, 4), 3, 2.5
KINDS = ("full", "window", "window", "window", "full")  # a period and one
# The published `rope_parameters` of the two kinds, at a context the test's
# positions exceed (so that the interpolated frequencies are in play).
FULL = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
        "original_max_position_embeddings": 8, "beta_fast": 64.0,
        "beta_slow": 1.0, "partial_rotary_factor": 0.5}
SLIDING = {"rope_type": "default", "rope_theta": 10000.0,
           "partial_rotary_factor": 1}
YARN = Yarn(FULL["factor"], FULL["beta_fast"], FULL["beta_slow"],
            FULL["original_max_position_embeddings"])
SHAPES = (("full", AttentionShape(HEADS["full"], FULL["rope_theta"],
                                  HEAD_DIM // 2, YARN)),
          ("window", AttentionShape(HEADS["window"], SLIDING["rope_theta"])))


def _cfg(attention="dense", length=LENGTH, window=WINDOW, **over):
    base = dict(
        vocab_size=VOCAB, num_layers=len(KINDS), num_heads=HEADS["full"],
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, embed_dim=HIDDEN,
        mlp_dim=96, mlp_gated=True, moe_dim=24, max_seq_len=length,
        attention=attention, attention_types=KINDS, attention_window=window,
        attention_shapes=SHAPES, attention_gate="head",
        moe_experts=EXPERTS, moe_every=1, first_k_dense=1, moe_top_k=TOP_K,
        moe_capacity_factor=None, moe_gated=True, moe_renormalize=True,
        moe_scoring="sigmoid", moe_route_scale=SCALE, moe_shared_dim=24,
        moe_held=HELD, dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"kinds": cfg.attention_types, "dense": cfg.first_k_dense,
            "eps": cfg.norm_eps, "heads": HEADS, "full": FULL,
            "window": SLIDING, "sliding_window": cfg.attention_window,
            "top_k": TOP_K, "route_scale": SCALE, "held": held}


def _seeded(cfg, seed=0, length=LENGTH):
    k_p, k_t, k_s = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (1, length), 0, VOCAB, jnp.int32)
    model = models.Transformer(cfg)
    params = model.init(k_p, tokens)["params"]
    # norm scales away from 1, so that a scale that is left out shows; the
    # matrices of attention and of the routed layer large, so that attention
    # is sharp (what a query sees, and at which angle, decides its output),
    # the gates are away from a half and the router's scores from a tie
    flat = jax.tree_util.tree_leaves_with_path(params)
    keys = jax.random.split(k_s, len(flat))

    def drawn(path, x, key):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:  # no gradient reaches it: at its zeros
            return x
        if x.ndim == 1:
            return x + 0.3 * jax.random.normal(key, x.shape)
        if "attn" in name and "out" not in name:
            return 2.0 * x
        if "moe_mlp" in name and "shared" not in name:
            return 4.0 * x
        return x

    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [drawn(path, x, k) for (path, x), k in zip(flat, keys)])
    return model, params, tokens


def _system_loss(model, params, tokens):
    hid = model.apply({"params": params}, tokens, return_hidden=True)
    return chunked_softmax_cross_entropy(
        hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
        chunk=16)


# f32 against f32 through five sharp layers: rounding grows a layer at a
# time (6e-7 of a state's largest entry after block 0, 1e-5 after block 4);
# a reference of another model is off by 5% and more.
TOL, TOL_GRAD = 1e-5, 1e-4


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def _captured(model, params, tokens):
    _, state = model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"],
        capture_intermediates=lambda mdl, name: isinstance(
            mdl, (models.transformer.Block, Attention))
        and name == "__call__")
    return state["intermediates"]


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels themselves, in interpret mode, under
    `ops.flash_attention` on the CPU (which takes the blockwise jnp form
    otherwise)."""
    real, real_gated = fa._flash, fa._flash_gated
    monkeypatch.setattr(
        fa, "_flash", lambda q, k, v, scale, causal, interpret, rule=None:
        real(q, k, v, scale, causal, True, rule))
    # a gated stack's calls (PR 63: the gate's product inside the kernels)
    monkeypatch.setattr(
        fa, "_flash_gated", lambda q, k, v, gate, scale, causal, interpret,
        rule=None: real_gated(q, k, v, gate, scale, causal, True, rule))


# --- (a) the model against the reference ------------------------------------

@pytest.mark.parametrize("block_remat", [0, 3])
@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_loss_logits_and_gradients_agree_with_the_reference(attention,
                                                            block_remat):
    cfg = _cfg(attention, block_remat=block_remat)
    model, params, tokens = _seeded(cfg, seed=block_remat)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)
    ref = jax.jit(lambda p: reference.forward(p, tokens[0], _arch(cfg)))(
        params)
    ref_grads = jax.jit(lambda p: reference.gradient(
        p, tokens[0], _arch(cfg)))(params)
    _close(loss, ref["loss"], TOL)
    _close(model.apply({"params": params}, tokens)[0], ref["logits"],
           TOL_GRAD)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        if "select_bias" not in jax.tree_util.keystr(path):
            assert np.max(np.abs(r)) > 0, path  # every parameter is reached
        _close(g, r, TOL_GRAD)


def test_the_kernels_under_the_model_agree_with_the_reference(interpreted):
    """128 positions, a window of 40, the flash kernels themselves
    (interpret mode) under both kinds of layer: the causal pair at group 3
    in the full layers, the band-ruled pair at group 4 in the window ones,
    a block recomputed."""
    cfg = _cfg("flash", length=128, window=40, block_remat=2)
    model, params, tokens = _seeded(cfg, seed=1, length=128)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.forward(p, tokens[0], _arch(cfg))["loss"]))(
            params)
    _close(loss, ref_loss, TOL)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        _close(g, r, TOL_GRAD)


@pytest.mark.parametrize("seed", [1, 3])
def test_states_branches_and_routing_agree_with_the_reference(seed):
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed)
    inter = _captured(model, params, tokens)
    ref = reference.forward(params, tokens[0], _arch(cfg))
    for i in range(cfg.num_layers):
        block = inter["block_%d" % i]
        _close(block["__call__"][0][0], ref["states"][i], TOL_GRAD)
        _close(block["attn"]["__call__"][0][0], ref["attn"][i], TOL_GRAD)
    routing = expert.routing_stats(inter)
    chosen = jnp.any(jax.nn.one_hot(routing["chosen"], EXPERTS,
                                    dtype=jnp.bool_), axis=-2)
    assert jnp.array_equal(chosen, ref["chosen"])
    assert float(jnp.max(jnp.abs(ref["margin"]))) == 0.0
    assert int(routing["dropped"]) == 0
    held = routing["assignments"][:, HELD[0]:HELD[0] + HELD[1]].sum(axis=1)
    assert jnp.array_equal(held, ref["held_rows"])
    # computed with the sets it would choose itself, nothing moves
    same = reference.forward(params, tokens[0], _arch(cfg),
                             follow=ref["chosen"])
    for k in ("states", "attn", "nll", "loss", "margin"):
        assert jnp.array_equal(same[k], ref[k]), k


# --- (b) the table, the shapes by kind and the gate --------------------------

def test_a_layer_carries_its_kinds_shape_and_the_module_reads_it():
    cfg = _cfg()
    table = cfg.layers()
    assert [layer.kind for layer in table] == list(KINDS)
    assert [layer.shape for layer in table] == [
        dict(SHAPES)[kind] for kind in KINDS]
    assert [layer.branches[1] for layer in table] == ["mlp"] + ["moe"] * 4
    _, params, _ = _seeded(cfg)
    for i, kind in enumerate(KINDS):
        a = params["block_%d" % i]["attn"]
        assert a["query"]["kernel"].shape == (HIDDEN, HEADS[kind], HEAD_DIM)
        assert a["key"]["kernel"].shape == (HIDDEN, KV_HEADS, HEAD_DIM)
        assert a["out"]["kernel"].shape == (HEADS[kind], HEAD_DIM, HIDDEN)
        assert a["gate"]["kernel"].shape == (HIDDEN, HEADS[kind])
        assert a["gate"]["kernel"].dtype == jnp.float32
    # a kind without a pair keeps the configuration's own fields
    one = _cfg(attention_shapes=SHAPES[1:])
    assert [layer.shape for layer in one.layers()] == [
        None if kind == "full" else SHAPES[1][1] for kind in KINDS]
    assert hash(cfg) == hash(_cfg())  # one record by kind, hashable


def test_the_rotations_are_the_published_ones():
    """The program's YaRN over the ROTATED slice gives the config's
    `attention_factor` and the reference's table at the published sizes (64
    of a head's 128 channels at base 500000, factor 64 over 4096 positions);
    the window layers' table is the plain one at base 10000 over the whole
    head; a partial rotation turns the first `rotary_dim` channels in
    rotate-half pairs inside the slice and passes the others."""
    published = {"factor": 64, "original_max_position_embeddings": 4096,
                 "beta_fast": 64, "beta_slow": 1,
                 "attention_factor": 1.4158883083359672}
    yarn = Yarn(64, 64, 1, 4096, mscale=1, mscale_all_dim=0)
    t = models.transformer
    m = t.yarn_mscale(yarn.factor, yarn.mscale) \
        / t.yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    assert abs(m - published["attention_factor"]) < 1e-12
    assert abs(0.1 * math.log(64) + 1 - published["attention_factor"]) < 1e-12
    want, factor = reference.yarn_frequencies(64, 500000, published)
    assert factor == published["attention_factor"]
    got = t.yarn_inv_freq(64, 500000, yarn)
    assert len(got) == 32
    np.testing.assert_allclose(got, want, rtol=1e-12)
    plain = reference.plain_frequencies(64, 500000)
    assert got[0] == plain[0] and got[-1] == plain[-1] / 64
    assert any(p / 64 < g < p for g, p in zip(got, plain))  # the blend
    assert reference.plain_frequencies(128, 10000)[1] == 10000 ** (-2 / 128)
    # the module's partial rotation against the pairs written out
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    pos = jnp.arange(3, 8)[None]
    freq, factor = reference.yarn_frequencies(8, FULL["rope_theta"], FULL)
    ang = np.asarray(pos[0], np.float64)[:, None] * np.asarray(freq)
    want = np.array(x[0], np.float64)
    x1, x2 = want[..., :4].copy(), want[..., 4:8].copy()
    cos, sin = (np.cos(ang) * factor)[:, None], (np.sin(ang) * factor)[:, None]
    want[..., :4], want[..., 4:8] = x1 * cos - x2 * sin, x1 * sin + x2 * cos
    got = t._rotary_freq(x[..., :8], pos, t.yarn_inv_freq(
        8, FULL["rope_theta"], YARN), factor)
    _close(jnp.concatenate([got, x[..., 8:]], -1)[0], want, 1e-6)


def _attention_alone(kind, gate, params=None):
    """(a layer's `Attention` module alone on a seeded input, its parameters:
    drawn where `params` is None)."""
    module = Attention(_cfg(attention_gate=gate), kind=kind,
                       shape=dict(SHAPES)[kind])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    if params is None:
        params = jax.tree_util.tree_map(
            lambda t: 3.0 * t,
            module.init(jax.random.PRNGKey(8), x, pos)["params"])
    return module.apply({"params": params}, x, pos), params, x


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_gate_is_one_sigmoid_a_head_on_the_heads_output(kind):
    """o_h <- sigmoid(x W_g)_h o_h before `out`: with the gate's matrix at
    zero every head is halved; a head whose rows of `out` are zero makes its
    gate's column irrelevant, and no other's; the gate reads the module's
    input, which is the branch's NORMED state."""
    H = HEADS[kind]
    plain, params, x = _attention_alone(kind, None)
    assert "gate" not in params

    def run(kernel, out_rows=None):
        """The gated module on the ungated one's draws and this gate."""
        p = dict(params, gate={"kernel": kernel})
        if out_rows is not None:
            p["out"] = {"kernel": params["out"]["kernel"].at[out_rows].set(
                0.0)}
        return _attention_alone(kind, "head", p)[0]

    _close(run(jnp.zeros((HIDDEN, H))), 0.5 * plain, 1e-6)
    kernel = jax.random.normal(jax.random.PRNGKey(9), (HIDDEN, H))
    moved = kernel.at[:, 2].add(1.0)
    assert float(jnp.max(jnp.abs(run(kernel) - run(moved)))) > 1e-3
    _close(run(kernel, out_rows=2), run(moved, out_rows=2), 0)
    assert float(jnp.max(jnp.abs(run(kernel, out_rows=3)
                                 - run(moved, out_rows=3)))) > 1e-3
    # against the reference's branch on the same input
    branch, gate = reference.attention_branch(
        x[0], dict(params, gate={"kernel": kernel}), kind, _arch(_cfg()))
    _close(run(kernel)[0], branch, 2e-5)
    _close(gate, jax.nn.sigmoid(x[0] @ kernel), 1e-6)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_gate_inside_the_kernels_is_the_dense_branchs_product(
        kind, interpreted, monkeypatch):
    """`Attention` under `attention="flash"` hands the kernels its gate (PR
    63: no product of its own) and under "dense" multiplies: the same output
    and the same gradients of the gate's, the queries' and the output
    projection's matrices, at 128 positions and a window of 40, the kernels
    themselves in the interpreter (group 3 causal, group 4 under the band)."""
    length = 128
    x = jax.random.normal(jax.random.PRNGKey(7), (1, length, HIDDEN))
    w = jax.random.normal(jax.random.PRNGKey(10), (1, length, HIDDEN))
    pos = jnp.arange(length)[None]

    def module(attention):
        return Attention(_cfg(attention, length=length, window=40),
                         kind=kind, shape=dict(SHAPES)[kind])

    params = jax.tree_util.tree_map(
        lambda t: 3.0 * t,
        module("dense").init(jax.random.PRNGKey(8), x, pos)["params"])

    def run(attention):
        return jax.value_and_grad(lambda p: jnp.sum(
            module(attention).apply({"params": p}, x, pos) * w))(params)

    gates, real = [], fa._flash_gated
    monkeypatch.setattr(fa, "_flash_gated", lambda *a: gates.append(
        a[3].shape) or real(*a))
    got, got_grads = run("flash")
    assert gates == [(1, HEADS[kind], length)]  # the gate went in, [B, H, L]
    want, want_grads = run("dense")
    _close(got, want, TOL)
    _close(module("flash").apply({"params": params}, x, pos),
           module("dense").apply({"params": params}, x, pos), TOL)
    for name in ("gate", "query", "out"):
        assert np.max(np.abs(want_grads[name]["kernel"])) > 0
        _close(got_grads[name]["kernel"], want_grads[name]["kernel"],
               TOL_GRAD)


# --- (c) the shares of an 8-way group add up to the uncut layer --------------

def test_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Laguna's cut at a small size: 16 experts in 8 shares of 2 (the cell:
    256 in 8 of 32), sigmoid over all 16, top-3 renormalised over the chosen
    whoever holds them, times 2.5; the shared expert, which every rank
    computes alike, counted ONCE."""
    E, D, F, T, k, share = 16, 32, 24, 64, 3, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_gate, w_up = (0.3 * jax.random.normal(key, (E, D, F))
                    for key in ks[2:4])
    w_down = 0.3 * jax.random.normal(ks[4], (E, F, D))
    shared = {"shared_%s" % n: {"kernel": 0.3 * jax.random.normal(key, s)}
              for n, key, s in (("gate", ks[5], (D, F)), ("up", ks[6], (D, F)),
                                ("down", ks[7], (F, D)))}
    arch = {"top_k": k, "route_scale": SCALE}
    moe = dict(shared, router=router, w_gate=w_gate, w_up=w_up,
               w_down=w_down)
    uncut = reference.routed_ffn(x, moe, arch, (0, E))[0]
    once = reference.gated(x, *(shared["shared_%s" % n]["kernel"]
                                for n in ("gate", "up", "down")))
    whole, _ = expert.moe_ffn(x, router, w_up, w_down, None, top_k=k,
                              w_gate=w_gate, scoring="sigmoid",
                              bias=jnp.zeros((E,)), scale=SCALE)
    _close(whole + once, uncut, 2e-6)
    total, total_ref, held = once, once, 0
    for r in range(E // share):
        own = slice(share * r, share * (r + 1))
        y, s = expert.moe_ffn(x, router, w_up[own], w_down[own], None,
                              top_k=k, w_gate=w_gate[own], scoring="sigmoid",
                              bias=jnp.zeros((E,)), scale=SCALE,
                              held=(share * r, share))
        y_ref = reference.routed_ffn(  # a rank's tree holds its own alone
            x, dict(moe, w_gate=w_gate[own], w_up=w_up[own],
                    w_down=w_down[own]), arch, (share * r, share),
            shared=False)[0]
        _close(y, y_ref, 2e-6)
        total, total_ref = total + y, total_ref + y_ref
        held += int(s["held"])
        assert int(s["dropped"]) == 0
    _close(total, uncut, 2e-6)
    _close(total_ref, uncut, 2e-6)
    assert held == k * T


# --- (d) every reference of another model is refused -------------------------

@pytest.mark.parametrize("name", list(reference.VARIANTS))
def test_the_comparison_refuses_a_reference_of_another_model(name):
    """Each variant is far from the reference the system agrees with, where
    it changes the stack: an attention variant in the attention branch of
    the first layer of the kind it changes (and nothing before it moves), a
    routing variant in the first routed layer's router's gradient."""
    variant = reference.VARIANTS[name]
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=2)
    arch = _arch(cfg)
    ref = reference.forward(params, tokens[0], arch)
    other = reference.forward(params, tokens[0], arch, variant)
    inter = _captured(model, params, tokens)
    if reference.CHANGES[variant] == "routing":
        ours = jax.grad(lambda p: _system_loss(model, p, tokens))(params)
        pick = lambda g: g["block_1"]["moe_mlp"]["router"]  # noqa: E731
        theirs = pick(reference.gradient(params, tokens[0], arch, variant))
        _close(pick(ours), pick(reference.gradient(params, tokens[0], arch)),
               TOL_GRAD)
        far = jnp.linalg.norm(pick(ours) - theirs) / jnp.linalg.norm(theirs)
        assert float(far) > 0.2
        assert jnp.array_equal(other["states"][0], ref["states"][0])
        return
    layer = KINDS.index(reference.CHANGES[variant])
    ours = jnp.stack([inter["block_%d" % i]["attn"]["__call__"][0][0]
                      for i in range(cfg.num_layers)])
    _close(ours, ref["attn"], TOL_GRAD)
    # from the first layer it changes on (the swapped bases turn a window
    # layer's sixteen channels further than a full layer's eight)
    far = max(float(jnp.max(jnp.abs(ours[i] - other["attn"][i]))
                    / jnp.max(jnp.abs(ref["attn"][i])))
              for i in range(layer, cfg.num_layers))
    assert far > 0.05
    before = slice(0, layer)  # and nothing before it moves
    assert jnp.array_equal(other["states"][before], ref["states"][before])


def test_bf16_where_the_configuration_states_f32_is_refused():
    """The same stack computing in bfloat16 breaks the tolerance the float32
    system is held to, by orders of magnitude."""
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=2)
    ref = reference.forward(params, tokens[0], _arch(cfg))
    low = models.Transformer(_cfg(dtype=jnp.bfloat16))
    _close(model.apply({"params": params}, tokens)[0], ref["logits"],
           TOL_GRAD)
    far = jnp.max(jnp.abs(low.apply({"params": params}, tokens)[0]
                          - ref["logits"]))
    assert float(far) > 100 * TOL_GRAD * float(
        jnp.max(jnp.abs(ref["logits"])))


# --- (e) the cell's two flash calls ------------------------------------------

@pytest.mark.parametrize("kind,heads,rule", [
    ("full", 48, None), ("window", 64, BandMask(512))])
def test_flash_plan_names_kernels_at_the_cells_calls(kind, heads, rule):
    """1 x 48 on 8 x 8192 x 128 causal (group 6) and 1 x 64 on 8 x 8192 x 128
    under a band of 512 (group 8): Pallas kernels in both directions, the
    backward one kernel held by the q block; under the band the plan's tile
    counts are the dense mask's."""
    L, D, G = 8192, 128, 8
    group = heads // G
    plans = {}
    for backward in (False, True):
        plans.update(profile.flash_plan(
            1, heads, L, D, group, jnp.bfloat16, backward,
            **({} if rule is None else {"mask": rule})))
    assert sorted(plans) == [profile.FLASH_BWD, profile.FLASH_FWD]
    for p in plans.values():
        assert (p.path, p.held) == ("resident", "q")
        assert p.block_q % group == 0 and L % (p.block_q // group) == 0
        assert p.vmem_bytes <= p.vmem_limit_bytes
    if rule is None:
        return
    for p in plans.values():
        bqp = p.block_q // group
        some, every = [], []
        for lo in range(0, L, bqp):
            seen = rule.visible(np.arange(lo, lo + bqp)[:, None],
                                np.arange(L)[None, :], np).reshape(
                                    bqp, L // p.block_k, p.block_k)
            some.append(seen.any(axis=(0, 2)))
            every.append(seen.all(axis=(0, 2)))
        some, every = np.stack(some), np.stack(every)
        assert [p.tiles_visited, p.tiles_masked, p.tiles_skipped] == [
            int(G * n) for n in (some.sum(), (some & ~every).sum(),
                                 (~some).sum())]
        # a band of one k block: every tile a window layer visits is cut
        assert p.tiles_masked == p.tiles_visited


# --- (f) an older stack is the program it was --------------------------------

def _older_preset(**over):
    """`tests/test_mellum.py`'s stack: one head count, whole-head rotations
    of one base, YaRN on the full layer, no gate."""
    base = dict(
        vocab_size=96, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
        embed_dim=64, mlp_dim=96, moe_dim=24, max_seq_len=32,
        attention="dense", rope_base=500000.0, qk_norm="head",
        attention_types=("window", "window", "window", "full"),
        attention_window=8, rope_yarn=Yarn(16.0, 32.0, 1.0, 8), moe_experts=8,
        moe_every=1, moe_top_k=3, moe_capacity_factor=None, moe_gated=True,
        moe_held=(2, 4), dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _jaxpr_text(cfg):
    model = models.Transformer(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(p):
        hid = model.apply({"params": p}, tokens, return_hidden=True)
        return chunked_softmax_cross_entropy(
            hid, p["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
            chunk=16)

    return str(jax.make_jaxpr(jax.grad(loss))(params))


# sha256 of `_jaxpr_text(_older_preset())` on the commit BEFORE the shapes by
# kind and the gate (0cece80, PR 61; jax 0.9.0).
OLDER_PRESET_JAXPR = \
    "0a936a2ca54ed5f19b98e2e5e82bb79fa2804b47676095a3a1331175774be92e"


def test_a_stack_without_shapes_and_gate_traces_to_the_jaxpr_it_had():
    text = _jaxpr_text(_older_preset())
    assert hashlib.sha256(text.encode()).hexdigest() == OLDER_PRESET_JAXPR


def test_shapes_that_spell_the_stacks_own_fields_change_nothing():
    """A pair that says what the configuration's fields say already (the
    stack's heads, its base, the whole head, YaRN on the full layers) is the
    same program, and so is the fallback."""
    cfg = _older_preset()
    spelled = _older_preset(attention_shapes=(
        ("full", AttentionShape(4, 500000.0, 16, cfg.rope_yarn)),
        ("window", AttentionShape(4, 500000.0, 16))))
    assert _jaxpr_text(spelled) == _jaxpr_text(cfg)


# --- (g) what is not built is refused by name --------------------------------

REFUSED = {
    "tp_axis": (dict(tp_axis="tp", moe_experts=None, moe_held=None,
                     moe_scoring="softmax", moe_shared_dim=None, moe_dim=None,
                     first_k_dense=0, mlp_gated=False), "attention_shapes"),
    "sp_axis": (dict(sp_axis="sp", moe_experts=None, moe_held=None,
                     moe_scoring="softmax", moe_shared_dim=None, moe_dim=None,
                     first_k_dense=0), "attention_shapes"),
    "num_passes": (dict(num_passes=2, moe_experts=None, moe_held=None,
                        moe_scoring="softmax", moe_shared_dim=None,
                        moe_dim=None, first_k_dense=0), "attention_shapes"),
    "tp_axis beside the gate alone": (
        dict(tp_axis="tp", attention_types=None, attention_shapes=None,
             attention_window=None, moe_experts=None, moe_held=None,
             moe_scoring="softmax", moe_shared_dim=None, moe_dim=None,
             first_k_dense=0, mlp_gated=False), "attention_gate"),
    "kv_lora_rank": (dict(kv_lora_rank=16, attention_gate=None,
                          attention_types=("kda", "full") * 2 + ("kda",)),
                     "attention_shapes"),
    "a gate on latent attention": (
        dict(kv_lora_rank=16, attention_types=None, attention_shapes=None,
             attention_window=None), "attention_gate"),
    "a gate beside kda layers": (
        dict(attention_types=("kda",) + KINDS[1:],
             attention_shapes=SHAPES[1:]), "attention_gate"),
    "a kind the stack has not": (
        dict(attention_types=("window",) * 5), "attention_shapes"),
    "no kinds at all": (dict(attention_types=None, attention_window=None),
                        "attention_shapes"),
    "a kind twice": (dict(attention_shapes=SHAPES + SHAPES[:1]),
                     "attention_shapes"),
    "a kind that is no attention": (
        dict(attention_shapes=(("kda", AttentionShape(8)),)),
        "attention_shapes"),
    "heads off the kv heads": (
        dict(attention_shapes=(("full", AttentionShape(7)),)),
        "attention_shapes"),
    "heads without head_dim": (dict(head_dim=None), "attention_shapes"),
    "an odd rotary_dim": (
        dict(attention_shapes=(("full", AttentionShape(rotary_dim=7)),)),
        "rotary_dim"),
    "a rotary_dim past the head": (
        dict(attention_shapes=(("full", AttentionShape(rotary_dim=32)),)),
        "rotary_dim"),
    "a gate of another form": (dict(attention_gate="element"),
                               "attention_gate")}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    over, named = REFUSED[case]
    with pytest.raises(ValueError) as err:
        _cfg(**over)
    assert named in str(err.value)


# --- (h) the scopes ----------------------------------------------------------

def test_the_program_names_the_gate_and_an_older_stack_does_not():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    assert profile.ATTN_GATE == "hvd_attn_gate"
    for i, kind in enumerate(KINDS):
        assert "block_%d/%s/attn/%s/gate" % (
            i, profile.ATTN_KINDS[kind], profile.ATTN_GATE) in text
    # kept out of what the readers of older cells walk
    assert profile.ATTN_GATE not in profile.ATTN_PARTS
    assert profile.ATTN_GATE not in profile.ATTN_KINDS.values()
    assert profile.ATTN_GATE not in profile.MODEL_SCOPES
    older = _older_preset()
    model = models.Transformer(older)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    assert profile.ATTN_GATE not in text
