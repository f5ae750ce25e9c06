"""LFM2-MoE's stack on the normal train path: double-gated short convolutions
as mixers of their own (`attention_types` "conv", `GatedShortConv`) beside
grouped-query attention with a norm a head, two leading dense layers
(`first_k_dense`), sigmoid-routed experts of which a device holds a part and
no shared one, the head tied to the embedding (`tie_embeddings`): the system
against the plain reference `benchmark/references/lfm2.py` at small sizes,
values and gradients."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import lfm2 as reference  # noqa: E402
from horovod_tpu import models, profile  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    ATTENTION_KINDS, Attention, GatedShortConv)
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy)
from horovod_tpu.parallel import expert  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

VOCAB, HIDDEN, LENGTH, HEAD_DIM, HEADS, KV_HEADS = 96, 64, 32, 16, 4, 2
EXPERTS, HELD, TOP_K, THETA, EPS = 8, (2, 4), 3, 1e6, 1e-5
# the published pattern's start: two dense conv layers, then attention and
# convolutions routed
KINDS = ("conv", "conv", "full", "conv", "conv", "full")
DENSE = 2


def _cfg(attention="dense", length=LENGTH, **over):
    base = dict(
        vocab_size=VOCAB, num_layers=len(KINDS), num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, embed_dim=HIDDEN,
        mlp_dim=96, mlp_gated=True, moe_dim=24, max_seq_len=length,
        attention=attention, attention_types=KINDS, qk_norm="head",
        rope_base=THETA, norm_eps=EPS, conv_taps=3, tie_embeddings=True,
        moe_experts=EXPERTS, moe_every=1, first_k_dense=DENSE,
        moe_top_k=TOP_K, moe_capacity_factor=None, moe_gated=True,
        moe_renormalize=True, moe_scoring="sigmoid", moe_route_scale=1.0,
        moe_held=HELD, dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"kinds": cfg.attention_types, "dense": cfg.first_k_dense,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_base,
            "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
            "held": held}


def _seeded(cfg, seed=0, length=LENGTH, batch=1):
    k_p, k_t, k_s = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (batch, length), 0, VOCAB, jnp.int32)
    model = models.Transformer(cfg)
    params = model.init(k_p, tokens)["params"]
    # norm scales away from 1, so that a scale that is left out shows; the
    # mixers' and the routed layer's matrices large, so that the gates and
    # attention are sharp and the router's scores away from a tie
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
        if "moe_mlp" in name:
            return 4.0 * x
        return x

    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [drawn(path, x, k) for (path, x), k in zip(flat, keys)])
    return model, params, tokens


def _system_loss(model, params, tokens):
    hid = model.apply({"params": params}, tokens, return_hidden=True)
    return chunked_softmax_cross_entropy(
        hid, params["embed"]["embedding"].T, jnp.roll(tokens, -1, axis=1),
        chunk=16)


# f32 against f32 through six layers: rounding grows a layer at a time; the
# routing weights differ by the renormalisation's epsilon (the model's 1e-6
# in the reference, 1e-20 in `parallel.expert.route`: 5e-7 of a weight, a
# listed departure), which these tolerances cover; a reference of another
# model is off by 5% and more.
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
            mdl, (models.transformer.Block, Attention, GatedShortConv))
        and name == "__call__")
    return state["intermediates"]


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels themselves, in interpret mode, under
    `ops.flash_attention` on the CPU."""
    real = fa._flash
    monkeypatch.setattr(
        fa, "_flash", lambda q, k, v, scale, causal, interpret, rule=None:
        real(q, k, v, scale, causal, True, rule))


# --- (a) the model against the reference ------------------------------------

@pytest.mark.parametrize("block_remat", [0, 4])
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
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(ref_grads)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        if "select_bias" not in jax.tree_util.keystr(path):
            assert np.max(np.abs(r)) > 0, path  # every parameter is reached
        _close(g, r, TOL_GRAD)


def test_a_batch_of_two_is_two_sequences_of_the_reference():
    """Two sequences a step (the cell's batch): the loss is the mean of the
    reference's two, the gradient the mean of its two; no tap and no key
    reaches across the batch."""
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=5, batch=2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)
    refs = [jax.jit(jax.value_and_grad(lambda p, seq=seq: reference.forward(
        p, seq, _arch(cfg))["loss"]))(params) for seq in tokens]
    _close(loss, (refs[0][0] + refs[1][0]) / 2, TOL)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, refs[0][1],
                                  refs[1][1])
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(mean)):
        _close(g, r, TOL_GRAD)


def test_the_kernels_under_the_model_agree_with_the_reference(interpreted):
    """128 positions, the flash kernels themselves (interpret mode) at group
    2 and head width 16 under the attention layers, blocks recomputed."""
    cfg = _cfg("flash", length=128, block_remat=3)
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
        _close(block["attn"]["__call__"][0][0], ref["mixer"][i], TOL_GRAD)
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
    for k in ("states", "mixer", "nll", "loss", "margin"):
        assert jnp.array_equal(same[k], ref[k]), k


# --- (b) the table, the mixer and the tied head ------------------------------

def test_a_conv_layer_is_a_mixer_of_its_own_in_the_table():
    cfg = _cfg(block_remat=2)
    table = cfg.layers()
    assert [layer.branches for layer in table] == [
        ("conv", "mlp"), ("conv", "mlp"), ("attn", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("attn", "moe")]
    assert [layer.kind for layer in table] == [
        None, None, "full", None, None, "full"]
    assert [layer.remat for layer in table] == [True, True] + [False] * 4
    assert "conv" in ATTENTION_KINDS
    model, params, _ = _seeded(cfg)
    conv = params["block_0"]["attn"]
    assert {k: jax.tree_util.tree_leaves(v)[0].shape
            for k, v in conv.items()} == {
        "in_proj": (HIDDEN, 3 * HIDDEN), "conv_kernel": (3, HIDDEN),
        "out_proj": (HIDDEN, HIDDEN)}
    assert set(params["block_2"]["attn"]) == {
        "query", "key", "value", "out", "q_norm", "k_norm"}
    assert "mlp_gate" in params["block_1"] and "moe_mlp" in params["block_2"]
    assert "shared_gate" not in params["block_2"]["moe_mlp"]


def test_the_mixer_reads_no_position_and_is_causal():
    """The stack of conv layers alone: moving every position by a constant
    changes nothing, and changing token t moves no logit before t."""
    cfg = _cfg(attention_types=("conv",) * 3, num_layers=3, first_k_dense=3,
               moe_experts=None, moe_held=None, moe_scoring="softmax",
               moe_dim=None, moe_capacity_factor=1.25)
    model, params, tokens = _seeded(cfg, seed=4)
    logits = model.apply({"params": params}, tokens)
    shifted = model.apply({"params": params}, tokens,
                          positions=7 + jnp.arange(LENGTH)[None])
    assert jnp.array_equal(logits, shifted)
    t = 11
    moved = model.apply({"params": params},
                        tokens.at[0, t].set((tokens[0, t] + 1) % VOCAB))
    assert jnp.array_equal(logits[0, :t], moved[0, :t])
    assert not jnp.array_equal(logits[0, t], moved[0, t])


def test_the_whole_stack_is_causal():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=6)
    logits = model.apply({"params": params}, tokens)
    t = 20
    moved = model.apply({"params": params},
                        tokens.at[0, t].set((tokens[0, t] + 1) % VOCAB))
    assert jnp.array_equal(logits[0, :t], moved[0, :t])
    assert not jnp.array_equal(logits[0, t:], moved[0, t:])


def test_a_tied_head_makes_no_lm_head_and_sums_the_two_gradients():
    """The table's gradient is the lookup's plus the head's: taken apart by
    giving the head a copy of the table, the two parts add up to the tied
    gradient; an untied twin has an `lm_head` and the tied stack has none."""
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=7)
    assert "lm_head" not in params
    assert "lm_head" in models.Transformer(_cfg(tie_embeddings=False)).init(
        jax.random.PRNGKey(0), tokens)["params"]

    def apart(p, head):
        hid = model.apply({"params": p}, tokens, return_hidden=True)
        return chunked_softmax_cross_entropy(
            hid, head.T, jnp.roll(tokens, -1, axis=1), chunk=16)

    table = params["embed"]["embedding"]
    lookup, head = jax.grad(apart, argnums=(0, 1))(params, table)
    tied = jax.grad(lambda p: _system_loss(model, p, tokens))(params)
    lookup = lookup["embed"]["embedding"]
    assert float(jnp.max(jnp.abs(lookup))) > 0
    assert float(jnp.max(jnp.abs(head))) > 0
    _close(tied["embed"]["embedding"], lookup + head, 1e-6)
    # the logits are the normed state times the table transposed
    hid = model.apply({"params": params}, tokens, return_hidden=True)
    _close(model.apply({"params": params}, tokens), hid @ table.T, 1e-6)


# --- (c) the share test ------------------------------------------------------

def test_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """LFM2's cut at a small size: 32 experts in 4 shares of 8 (the cell's
    own numbers), sigmoid over all 32, top-4 renormalised over the chosen
    whoever holds them, no shared expert: the four ranks' parts (held
    (0, 8), (8, 8), (16, 8), (24, 8)) add up to the uncut reference's
    layer, in the program and in the reference alike."""
    E, D, F, T, k, share = 32, 32, 24, 64, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_gate, w_up = (0.3 * jax.random.normal(key, (E, D, F))
                    for key in ks[2:4])
    w_down = 0.3 * jax.random.normal(ks[4], (E, F, D))
    arch = {"top_k": k, "route_scale": 1.0}
    moe = dict(router=router, select_bias=jnp.zeros((E,)), w_gate=w_gate,
               w_up=w_up, w_down=w_down)
    uncut = reference.routed_ffn(x, moe, arch, (0, E))[0]
    whole, _ = expert.moe_ffn(x, router, w_up, w_down, None, top_k=k,
                              w_gate=w_gate, scoring="sigmoid",
                              bias=jnp.zeros((E,)))
    _close(whole, uncut, 2e-6)
    total, total_ref, total_cut, held = 0.0, 0.0, 0.0, 0
    for first in (0, 8, 16, 24):
        own = slice(first, first + share)
        y, s = expert.moe_ffn(x, router, w_up[own], w_down[own], None,
                              top_k=k, w_gate=w_gate[own], scoring="sigmoid",
                              bias=jnp.zeros((E,)), held=(first, share))
        # a rank's tree holds its own alone; the whole tree is cut to them
        y_ref = reference.routed_ffn(
            x, dict(moe, w_gate=w_gate[own], w_up=w_up[own],
                    w_down=w_down[own]), arch, (first, share))[0]
        y_cut = reference.routed_ffn(x, moe, arch, (first, share))[0]
        _close(y, y_ref, 2e-6)
        assert jnp.array_equal(y_ref, y_cut)
        total, total_ref, total_cut = total + y, total_ref + y_ref, \
            total_cut + y_cut
        held += int(s["held"])
        assert int(s["dropped"]) == 0
    _close(total, uncut, 2e-6)
    _close(total_ref, uncut, 2e-6)
    assert held == k * T


# --- (d) every reference of another model is refused -------------------------

ALL_VARIANTS = dict(reference.VARIANTS,
                    **reference.VARIANTS_AWAY_FROM_UNIT_SCALE)


@pytest.mark.parametrize("name", list(ALL_VARIANTS))
def test_the_comparison_refuses_a_reference_of_another_model(name):
    """Each variant is far from the reference the system agrees with, where
    it changes the stack: a mixer's variant in the mixer branch of the first
    layer of the kind it changes (and nothing before it moves), a routing
    variant in the first routed layer's router's gradient."""
    variant = ALL_VARIANTS[name]
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=2)
    arch = _arch(cfg)
    ref = reference.forward(params, tokens[0], arch)
    other = reference.forward(params, tokens[0], arch, variant)
    inter = _captured(model, params, tokens)
    if reference.CHANGES[variant] == "routing":
        ours = jax.grad(lambda p: _system_loss(model, p, tokens))(params)
        pick = lambda g: g["block_%d" % DENSE]["moe_mlp"]["router"]  # noqa
        theirs = pick(reference.gradient(params, tokens[0], arch, variant))
        _close(pick(ours), pick(reference.gradient(params, tokens[0], arch)),
               TOL_GRAD)
        far = jnp.linalg.norm(pick(ours) - theirs) / jnp.linalg.norm(theirs)
        assert float(far) > 0.2
        assert jnp.array_equal(other["states"][:DENSE], ref["states"][:DENSE])
        return
    layer = KINDS.index(reference.CHANGES[variant])
    ours = jnp.stack([inter["block_%d" % i]["attn"]["__call__"][0][0]
                      for i in range(cfg.num_layers)])
    _close(ours, ref["mixer"], TOL_GRAD)
    far = float(jnp.max(jnp.abs(ours[layer] - other["mixer"][layer]))
                / jnp.max(jnp.abs(ref["mixer"][layer])))
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


# --- (e) the cell's flash call -----------------------------------------------

def test_flash_plan_names_kernels_at_the_cells_call():
    """2 x 32 on 8 x 8192 x 64 causal (group 4, head width 64: a slab a
    head): Pallas kernels in both directions, resident, the backward ONE
    kernel held by the q block at its 24 MiB."""
    import horovod_tpu as hvd

    fwd = hvd.profile.flash_plan(2, 32, 8192, 64, 4, jnp.bfloat16, False)
    bwd = hvd.profile.flash_plan(2, 32, 8192, 64, 4, jnp.bfloat16, True)
    assert list(fwd) == [profile.FLASH_FWD]
    assert list(bwd) == [profile.FLASH_BWD]
    for plan in (fwd[profile.FLASH_FWD], bwd[profile.FLASH_BWD]):
        assert plan.path == "resident" and plan.held == "q"
        assert (plan.block_q, plan.block_k) == (2048, 512)
        assert plan.grid == (16, 16)
    assert bwd[profile.FLASH_BWD].resident_bytes == 24 << 20


# --- (f) what is not built is refused by name --------------------------------

PLAIN = dict(moe_experts=None, moe_held=None, moe_scoring="softmax",
             moe_dim=None, first_k_dense=0, moe_capacity_factor=1.25,
             qk_norm=False)
REFUSED = {
    "tp_axis": (dict(PLAIN, tp_axis="tp", mlp_gated=False),
                "attention_types"),
    "sp_axis": (dict(PLAIN, sp_axis="sp"), "attention_types"),
    "num_passes": (dict(PLAIN, num_passes=2), "attention_types"),
    "hc_mult": (dict(hc_mult=2), "hc_mult"),
    "a tied head under tp_axis": (
        dict(PLAIN, tp_axis="tp", mlp_gated=False, attention_types=None),
        "tie_embeddings"),
    "a tied head in a looped stack": (
        dict(PLAIN, num_passes=2, attention_types=None), "tie_embeddings"),
    "a kind that is none": (
        dict(attention_types=("conv", "ssm") + KINDS[2:]),
        "full, window, kda, conv"),
    "no taps": (dict(conv_taps=0), "conv_taps")}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    over, named = REFUSED[case]
    with pytest.raises(ValueError) as err:
        _cfg(**over)
    assert named in str(err.value)


def test_a_branch_that_is_no_mixer_is_refused_with_the_mixers_named():
    with pytest.raises(ValueError, match="kda, ssm, conv"):
        models.transformer._mixer(_cfg(), models.Layer(("fft", "mlp")),
                                  "fft", None)


# --- (g) the scopes ----------------------------------------------------------

def test_the_program_names_the_mixer_and_its_parts():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    assert profile.SCONV_SCOPES == ("hvd_sconv", "hvd_sconv_proj",
                                    "hvd_sconv_gate")
    for i, kind in enumerate(KINDS):
        if kind == "conv":
            for part in profile.SCONV_SCOPES[1:]:
                assert "block_%d/%s/attn/%s" % (i, profile.SCONV, part) \
                    in text
            # the norm before the mixer lies under the outer name alone
            assert "block_%d/%s/norm1" % (i, profile.SCONV) in text
        else:
            assert "block_%d/%s/attn/%s" % (
                i, profile.ATTN_FULL, profile.ATTN_PROJ) in text
    # kept out of what the readers of older cells walk
    for name in profile.SCONV_SCOPES:
        assert name not in profile.KDA_SCOPES + profile.SSM_SCOPES \
            + profile.ATTN_PARTS + profile.MODEL_SCOPES
        assert name not in profile.ATTN_KINDS.values()
    # and a stack without the kind carries none of the names
    plain = _cfg(attention_types=None, tie_embeddings=False)
    model = models.Transformer(plain)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    text = jax.jit(jax.grad(lambda p: chunked_softmax_cross_entropy(
        model.apply({"params": p}, tokens, return_hidden=True),
        p["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
        chunk=16))).lower(params).as_text(debug_info=True)
    assert profile.SCONV not in text


# --- (h) the in-projection's data gradient, held ----------------------------

HELD_KINDS = ("conv", "conv", "full")


def _loss_and_gradients(block_remat):
    cfg = _cfg(attention_types=HELD_KINDS, num_layers=len(HELD_KINDS),
               block_remat=block_remat)
    model, params, tokens = _seeded(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)


@pytest.mark.parametrize("block_remat", [0, len(HELD_KINDS)])
def test_a_held_cotangent_changes_no_bit(monkeypatch, block_remat):
    """`_hold_cotangent` between a conv layer's norm and its mixer is the
    identity forward and the same arithmetic backward: the loss and every
    gradient leaf in f32, with it and with the identity in its place."""
    held = _loss_and_gradients(block_remat)
    monkeypatch.setattr(models.transformer, "_hold_cotangent", lambda h: h)
    bare = _loss_and_gradients(block_remat)
    flat = jax.tree_util.tree_leaves_with_path(held)
    assert len(flat) > 20
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(bare)):
        # no gradient reaches the selection bias; every other leaf is live
        assert a.dtype == jnp.float32 and (np.any(np.asarray(a) != 0) or
                                           "select_bias" in str(path)), path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kinds,holds", [
    (HELD_KINDS, 2), (("full", "full", "full"), 0), (None, 0)])
def test_a_conv_layers_backward_alone_holds_its_cotangent(kinds, holds):
    """One `optimization_barrier` a conv layer in the gradient's jaxpr,
    under the name the program gives it; a stack with no such layer holds
    none and names nothing: the program it was."""
    cfg = _cfg(attention_types=kinds, num_layers=3,
               tie_embeddings=kinds is not None)
    tokens = jnp.zeros((1, LENGTH), jnp.int32)
    model = models.Transformer(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
    gradient = jax.grad(lambda p: jnp.sum(model.apply(
        {"params": p}, tokens, return_hidden=True).astype(jnp.float32)))
    assert str(jax.make_jaxpr(gradient)(params)).count(
        "optimization_barrier") == holds
    text = jax.jit(gradient).lower(params).as_text(debug_info=True)
    assert profile.SCONV_HOLD not in profile.SCONV_SCOPES
    for i, kind in enumerate(kinds or ()):
        assert ("block_%d/%s/%s/optimization_barrier" % (
            i, profile.SCONV, profile.SCONV_HOLD) in text) \
            == (kind == "conv")
    assert (profile.SCONV_HOLD in text) == bool(holds)
