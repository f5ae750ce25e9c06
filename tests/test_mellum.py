"""Window and full attention layers in one stack on the normal train path:
the causal band as a rule of the flash kernels (`ops.BandMask`),
`TransformerConfig.attention_types` with a rotation a kind (YaRN on the full
layers' plain attention) and softmax-routed experts of which a device holds
a part: the system against the plain reference
`benchmark/references/mellum.py` at small sizes, values and gradients."""

import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import mellum as reference  # noqa: E402
from horovod_tpu import models, profile  # noqa: E402
from horovod_tpu.models.transformer import Yarn  # noqa: E402
from horovod_tpu.ops import BandMask  # noqa: E402
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy)
from horovod_tpu.parallel import expert, router_aux_losses  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

VOCAB, HIDDEN, LENGTH, WINDOW = 96, 64, 32, 8
EXPERTS, HELD, TOP_K, W_BALANCE = 8, (2, 4), 3, 0.05
KINDS = ("window", "window", "window", "full")  # one period
# The published `rope_parameters` of the full layers, at a context the test's
# positions exceed (so that the interpolated frequencies are in play).
YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 8, "beta_fast": 32.0,
        "beta_slow": 1.0}


def _cfg(attention="dense", length=LENGTH, window=WINDOW, **over):
    base = dict(
        vocab_size=VOCAB, num_layers=len(KINDS), num_heads=4, num_kv_heads=2,
        head_dim=16, embed_dim=HIDDEN, mlp_dim=96, moe_dim=24,
        max_seq_len=length, attention=attention,
        rope_base=YARN["rope_theta"], qk_norm="head",
        attention_types=KINDS, attention_window=window,
        rope_yarn=Yarn(YARN["factor"], YARN["beta_fast"], YARN["beta_slow"],
                       YARN["original_max_position_embeddings"]),
        moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K,
        moe_capacity_factor=None, moe_gated=True, moe_held=HELD,
        dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg):
    return {"kinds": cfg.attention_types, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_base, "window": cfg.attention_window,
            "yarn": YARN, "top_k": TOP_K, "held": HELD,
            "balance_weight": W_BALANCE}


def _seeded(cfg, seed=0, length=LENGTH):
    k_p, k_t, k_s = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (1, length), 0, VOCAB, jnp.int32)
    model = models.Transformer(cfg)
    params = model.init(k_p, tokens)["params"]
    # norm scales away from 1, so that a scale that is left out shows; the
    # per-head scales large, so that attention is sharp and what a query
    # sees, and at which angle, decides its output
    flat = jax.tree_util.tree_leaves_with_path(params)
    keys = jax.random.split(k_s, len(flat))
    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), [
            (2.0 if "_norm" in jax.tree_util.keystr(path) else 1.0) * x
            + 0.3 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
            for (path, x), k in zip(flat, keys)])
    return model, params, tokens


def _system_loss(model, params, tokens):
    hid, state = model.apply({"params": params}, tokens, return_hidden=True,
                             mutable=["intermediates"])
    ce = chunked_softmax_cross_entropy(
        hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
        chunk=16)
    return ce + W_BALANCE * router_aux_losses(state["intermediates"])[0]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels themselves, in interpret mode, under
    `ops.flash_attention` on the CPU (which takes the blockwise jnp form
    otherwise)."""
    real = fa._flash
    monkeypatch.setattr(
        fa, "_flash", lambda q, k, v, scale, causal, interpret, rule=None:
        real(q, k, v, scale, causal, True, rule))


# --- (a) the band rule against the dense mask -------------------------------

def _dense_mask(rule, S):
    return np.asarray(rule.visible(np.arange(S)[:, None],
                                   np.arange(S)[None, :], np))


def _dense_attention(q, k, v, scale, mask):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _kernel_case(S, H, G, D=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda heads: (1, heads, S, D)  # noqa: E731
    return (jax.random.normal(ks[0], shape(H)),
            jax.random.normal(ks[1], shape(G)),
            jax.random.normal(ks[2], shape(G)),
            jax.random.normal(ks[3], shape(H)))


def test_the_band_is_the_published_overlay():
    """Itself and the window - 1 keys before it (`kv > q - window` under
    the causal triangle); a window of the length or more is the triangle."""
    mask = _dense_mask(BandMask(3), 6)
    assert mask.astype(int).tolist() == [
        [1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0],
        [0, 1, 1, 1, 0, 0], [0, 0, 1, 1, 1, 0], [0, 0, 0, 1, 1, 1]]
    for window in (6, 7, 100):
        assert np.array_equal(_dense_mask(BandMask(window), 6),
                              np.tril(np.ones((6, 6), bool)))


def _budget(path, H, G, S, D, bq, bk, rule, monkeypatch):
    """The VMEM budget (and order of forms) that makes the backward under
    `rule` take `path`, as `tests/test_sdar.py::_budget`."""
    plan = lambda budget: fa.flash_plan(  # noqa: E731
        1, H, S, D, H // G, jnp.float32, True, bq, bk, budget, mask=rule)
    budget = fa.RESIDENT_VMEM_BUDGET
    if path == "one kernel":
        return budget
    budget = plan(budget)[profile.FLASH_BWD].resident_bytes - 1
    if path == "q-held":
        return budget
    monkeypatch.setattr(fa, "_BWD_HELD", ("k",))
    if path == "gridded dK/dV":
        budget = plan(budget)[profile.FLASH_DKV].resident_bytes - 1
    return budget


# (window, heads, kv heads, rows of a q block, k block) at 256 positions: a
# window smaller than a tile, equal to one, larger than one and not a
# multiple of it, and as long as the sequence (the causal triangle); a head
# group of 1, 2 and 8.
BAND_CASES = [(8, 4, 2, 64, 128), (128, 2, 2, 128, 128), (200, 8, 1, 256, 128),
              (256, 4, 2, 128, 64)]


def _kernels_against_the_dense_mask(rule, S, H, G, bq, bk, path, monkeypatch,
                                    seed=0):
    """The forward and the backward in the form `path` names, at the given
    blocks, against the dense masked softmax; the backward's plans."""
    D = 64
    q, k, v, w = _kernel_case(S, H, G, D, seed)
    mask = jnp.asarray(_dense_mask(rule, S))
    want, vjp = jax.vjp(lambda *a: _dense_attention(*a, D ** -0.5, mask),
                        q, k, v)
    budget = _budget(path, H, G, S, D, bq, bk, rule, monkeypatch)
    plans = fa.flash_plan(1, H, S, D, H // G, q.dtype, True, bq, bk, budget,
                          mask=rule)
    assert {n: (p.path, p.held) for n, p in plans.items()} == {
        "one kernel": {profile.FLASH_BWD: ("resident", "k")},
        "two resident": {profile.FLASH_DQ: ("resident", "q"),
                         profile.FLASH_DKV: ("resident", "k")},
        "q-held": {profile.FLASH_BWD: ("resident", "q")},
        "gridded dK/dV": {profile.FLASH_DQ: ("resident", "q"),
                          profile.FLASH_DKV: ("gridded", "k")}}[path]
    kw = dict(block_q=bq, block_k=bk, vmem_budget=budget, rule=rule)
    out, lse = fa._pallas_forward_lse(q, k, v, D ** -0.5, False, True, **kw)
    _close(out, want, 2e-6)
    got = fa._pallas_backward(q, k, v, out, lse, w, D ** -0.5, False, True,
                              **kw)
    for g, r in zip(got, vjp(w)):
        _close(g, r, 2e-6)
    return plans


@pytest.mark.parametrize("path", ["one kernel", "two resident", "q-held",
                                  "gridded dK/dV"])
@pytest.mark.parametrize("window,H,G,bq,bk", BAND_CASES)
def test_banded_kernels_agree_with_a_dense_masked_softmax(window, H, G, bq,
                                                          bk, path,
                                                          monkeypatch):
    _kernels_against_the_dense_mask(BandMask(window), 256, H, G, bq, bk, path,
                                    monkeypatch)


# (positions, window, heads, kv heads, rows of a q block, k block, cut_k,
# cut k blocks a kv head with one sub-tile in sight) with `_CUT_K` set to
# cut_k: a window smaller than a sub-tile (a q tile at a k block's start
# sees one sub-tile of it and one of the block before); a window of several
# sub-tiles; a head group of 8 on q tiles narrower than a sub-tile; the
# triangle with q tiles as wide as the k block (every sub-tile of a cut k
# block in sight: the walk by k blocks); the cells' steps, (128, 512, 128).
CUT_CASES = [(512, 8, 4, 2, 64, 128, 32, 7), (512, 200, 2, 2, 32, 128, 32, 6),
             (512, 300, 8, 1, 256, 256, 64, 4),
             (512, 512, 2, 1, 128, 128, 32, 0),
             (1024, 300, 1, 1, 128, 512, 128, 3)]


@pytest.mark.parametrize("path", ["two resident", "q-held"])
@pytest.mark.parametrize("S,window,H,G,bq,bk,cut_k,lone", CUT_CASES)
def test_banded_kernels_take_a_lone_sub_tile_alone(S, window, H, G, bq, bk,
                                                   cut_k, lone, path,
                                                   monkeypatch):
    """The kernels held by the q block against the dense masked softmax: the
    forward and dQ by its own kernel under `_walk_runs_merged`, the
    one-kernel backward under `_walk_cut_runs`, where a cut k block with one
    sub-tile in sight (`lone`: how many the call has a kv head) is computed
    as that sub-tile."""
    monkeypatch.setattr(fa, "_CUT_K", cut_k)
    plans = _kernels_against_the_dense_mask(BandMask(window), S, H, G, bq, bk,
                                            path, monkeypatch, seed=7)
    if path == "two resident":  # dQ carries its sum: k blocks alone
        assert plans[profile.FLASH_DQ].cut_k == bk
    else:
        cut, ratio = plans[profile.FLASH_BWD], bk // cut_k
        assert (cut.held, cut.cut_k) == ("q", cut_k)
        assert G * lone * (ratio - 1) == (
            ratio * cut.tiles_visited - cut.subtiles_visited) == (
            ratio * cut.tiles_masked - cut.subtiles_masked)


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("S,window,group,bq,bk", [
    (512, 8, 1, 128, 128), (512, 128, 2, 128, 128), (512, 200, 4, 256, 128),
    (512, 600, 1, 64, 256), (1024, 300, 8, 256, 128),
    (8192, 1024, 8, None, None)])
def test_flash_plan_counts_the_tiles_the_band_has(S, window, group, bq, bk,
                                                  backward, monkeypatch):
    rule = BandMask(window)
    mask = _dense_mask(rule, S)
    heads = 2
    for held in (("k", "q"), ("q",), ()):  # every form that counts
        monkeypatch.setattr(fa, "_BWD_HELD", held)
        plans = fa.flash_plan(1, heads * group, S, 128, group, jnp.bfloat16,
                              backward, block_q=bq, block_k=bk, mask=rule)
        assert plans
        for name, p in plans.items():
            bqp = p.block_q // group
            tiles = mask.reshape(S // bqp, bqp, S // p.block_k, p.block_k)
            some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
            assert p.tiles_visited == heads * some.sum(), name
            assert p.tiles_skipped == heads * (~some).sum(), name
            assert p.tiles_masked == heads * (some & ~every).sum(), name
            assert p.tiles_masked > 0


def test_the_cells_shape_runs_kernels_in_both_directions():
    """1 x 32 heads on 4, 8192 positions, D 128, window 1024: the forward
    resident on k + v and the backward ONE kernel held by the q block, as
    the block-diffusion cell's call; the full layers' causal call of the
    same shape takes the same forms."""
    band = {n: p for b in (False, True) for n, p in fa.flash_plan(
        1, 32, 8192, 128, 8, jnp.bfloat16, b, mask=BandMask(1024)).items()}
    full = {n: p for b in (False, True) for n, p in fa.flash_plan(
        1, 32, 8192, 128, 8, jnp.bfloat16, b).items()}
    assert sorted(band) == sorted(full) == [profile.FLASH_BWD,
                                            profile.FLASH_FWD]
    for name, p in band.items():
        assert (p.path, p.held) == ("resident", "q") == (
            full[name].path, full[name].held)
        assert p.tiles_visited + p.tiles_skipped == 4 * (
            8192 * 8 // p.block_q) * (8192 // p.block_k)
        # a window layer visits well under a third of a causal layer's
        # tiles: (window + a q block + a k block) / (L / 2) at most
        bqp = p.block_q // 8
        causal = 4 * sum(-(-(i + bqp) // p.block_k)
                         for i in range(0, 8192, bqp))
        assert p.tiles_visited * 8192 // 2 <= causal * (
            1024 + bqp + p.block_k)
    assert band[profile.FLASH_BWD].resident_bytes == 24 * 2 ** 20


@pytest.mark.parametrize("interpret", [True, None])
def test_banded_custom_vjp(interpret):
    S, D = 128, 64
    rule = BandMask(40)
    q, k, v, w = _kernel_case(S, 4, 2, D, seed=3)
    mask = jnp.asarray(_dense_mask(rule, S))
    want, vjp = jax.vjp(lambda *a: _dense_attention(*a, D ** -0.5, mask),
                        q, k, v)
    out, got = jax.vjp(lambda *a: fa._flash(*a, D ** -0.5, False, interpret,
                                            rule), q, k, v)
    _close(out, want, 2e-6)
    for g, r in zip(got(w), vjp(w)):
        _close(g, r, 2e-6)


def test_flash_attention_takes_the_band_and_refuses_what_it_cannot():
    q, k, v, _ = _kernel_case(128, 4, 2, seed=4)
    to_blhd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    for window in (1, 40, 128, 4096):
        rule = BandMask(window)
        got = fa.flash_attention(to_blhd(q), to_blhd(k), to_blhd(v),
                                 mask=rule)
        _close(to_blhd(got), _dense_attention(
            q, k, v, 64 ** -0.5, jnp.asarray(_dense_mask(rule, 128))), 2e-6)
    with pytest.raises(ValueError, match="sees itself"):
        fa.flash_attention(to_blhd(q), to_blhd(k), to_blhd(v),
                           mask=BandMask(0))
    with pytest.raises(ValueError, match="one score product"):
        fa.flash_plan(1, 4, 256, 64, 2, shared_dim=64, mask=BandMask(8))
    # any blocks that tile the sequence will do: the band has no seam
    assert fa.flash_plan(1, 4, 256, 64, 2, block_q=64, block_k=256,
                         mask=BandMask(8))
    # no resident forward, no kernel: the call is the blockwise jnp form
    assert fa.flash_plan(1, 4, 256, 64, 2, vmem_budget=0,
                         mask=BandMask(8)) == {}


# --- (b) the model against the reference ------------------------------------

@pytest.mark.parametrize("seed", [0, 2])
@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_loss_and_gradients_agree_with_the_reference(attention, seed):
    cfg = _cfg(attention)
    model, params, tokens = _seeded(cfg, seed)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.forward(p, tokens[0], _arch(cfg))["loss"]))(
            params)
    _close(loss, ref_loss, 2e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        assert np.max(np.abs(r)) > 0, path  # every parameter is reached
        _close(g, r, 2e-5)


def test_the_kernels_under_the_model_agree_with_the_reference(interpreted):
    """128 positions, a window of 40, the flash kernels themselves
    (interpret mode) under both kinds of layer: the band-ruled pair in the
    window layers, the causal pair in the full one."""
    cfg = _cfg("flash", length=128, window=40)
    model, params, tokens = _seeded(cfg, seed=1, length=128)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.forward(p, tokens[0], _arch(cfg))["loss"]))(
            params)
    _close(loss, ref_loss, 2e-6)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        _close(g, r, 2e-5)


@pytest.mark.parametrize("seed", [1, 3])
def test_states_branches_and_routing_agree_with_the_reference(seed):
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed)
    _, state = model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"],
        capture_intermediates=lambda mdl, name: isinstance(
            mdl, (models.transformer.Block, models.transformer.Attention))
        and name == "__call__")
    ref = reference.forward(params, tokens[0], _arch(cfg))
    inter = state["intermediates"]
    for i in range(cfg.num_layers):
        block = inter["block_%d" % i]
        _close(block["__call__"][0][0], ref["states"][i], 2e-5)
        _close(block["attn"]["__call__"][0][0], ref["attn"][i], 2e-5)
    routing = expert.routing_stats(inter)
    chosen = jnp.any(jax.nn.one_hot(routing["chosen"], EXPERTS,
                                    dtype=jnp.bool_), axis=-2)
    assert jnp.array_equal(chosen, ref["chosen"])
    assert float(jnp.max(jnp.abs(ref["margin"]))) == 0.0
    held = routing["assignments"][:, HELD[0]:HELD[0] + HELD[1]].sum(axis=1)
    assert jnp.array_equal(held, ref["held_rows"])
    # computed with the sets it would choose itself, nothing moves
    same = reference.forward(params, tokens[0], _arch(cfg),
                             follow=ref["chosen"])
    for k in ("states", "attn", "nll", "loss", "margin"):
        assert jnp.array_equal(same[k], ref[k]), k


# --- (c) the shares of a 4-way group add up to the uncut layer --------------

def test_four_shares_of_a_softmax_top_k_layer_add_up_to_the_uncut_layer():
    """Mellum2's cut at a small size: 8 experts in 4 shares of 2 (the cell:
    64 in 4 of 16), softmax over all 8, top-3 renormalised over the chosen
    whoever holds them; no shared expert, so nothing is counted twice."""
    E, D, F, T, k, share = 8, 32, 24, 64, 3, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_gate, w_up = (0.3 * jax.random.normal(key, (E, D, F))
                    for key in ks[2:4])
    w_down = 0.3 * jax.random.normal(ks[4], (E, F, D))
    whole, stats = expert.moe_ffn(x, router, w_up, w_down, None, top_k=k,
                                  w_gate=w_gate)
    moe = {"router": router, "w_gate": w_gate, "w_up": w_up,
           "w_down": w_down}
    uncut = reference.routed_ffn(x, moe, k, (0, E))[0]
    _close(whole, uncut, 2e-6)
    total, total_ref, held = 0.0, 0.0, 0
    for r in range(E // share):
        own = slice(share * r, share * (r + 1))
        y, s = expert.moe_ffn(x, router, w_up[own], w_down[own], None,
                              top_k=k, w_gate=w_gate[own],
                              held=(share * r, share))
        y_ref = reference.routed_ffn(  # a rank's tree holds its own alone
            x, dict(moe, w_gate=w_gate[own], w_up=w_up[own],
                    w_down=w_down[own]), k, (share * r, share))[0]
        _close(y, y_ref, 2e-6)
        total, total_ref = total + y, total_ref + y_ref
        held += int(s["held"])
        # the balancing term is over all 8 outputs on every rank
        _close(s["load_balance_loss"], stats["load_balance_loss"], 1e-6)
    _close(total, uncut, 2e-6)
    _close(total_ref, uncut, 2e-6)
    assert held == k * T


# --- (d) the window and the rotation matter at this size --------------------

@pytest.mark.parametrize("variant", [
    reference.ALL_FULL, reference.PLAIN_ROTATION, reference.ALL_WINDOW,
    reference.NO_FACTOR])
def test_the_comparison_tells_the_mechanism(variant):
    """A reference of another stack (every layer full; the full layers on
    the plain frequencies; the full layer under the window; YaRN's factor
    left off cos and sin) is far from the one the system agrees with: in
    the loss, by more than ten times the tests' tolerance, and in the
    attention branch of the first layer it changes."""
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=2)
    arch = _arch(cfg)
    loss = float(_system_loss(model, params, tokens))
    ref = reference.forward(params, tokens[0], arch)
    other = reference.forward(params, tokens[0], arch, variant)
    _close(loss, ref["loss"], 2e-6)
    assert abs(loss - float(other["loss"])) > 10 * 2e-6 * max(1.0, loss)
    layer = 0 if variant == reference.ALL_FULL else KINDS.index("full")
    far = jnp.max(jnp.abs(other["attn"][layer] - ref["attn"][layer]))
    assert float(far) > 0.05 * float(jnp.max(jnp.abs(ref["attn"][layer])))
    before = slice(0, layer)  # and nothing before it moves
    assert jnp.array_equal(other["states"][before], ref["states"][before])


def test_the_rotations_are_the_published_ones():
    """The program's YaRN (`yarn_inv_freq`, `yarn_mscale`: latent
    attention's since PR 37) gives the config's `attention_factor` and the
    reference's table at the published sizes; the window layers' table is
    the plain one."""
    published = {"factor": 16, "original_max_position_embeddings": 8192,
                 "beta_fast": 32, "beta_slow": 1,
                 "attention_factor": 1.2772588722239782}
    yarn = Yarn(16, 32, 1, 8192, mscale=1, mscale_all_dim=0)
    t = models.transformer
    m = t.yarn_mscale(yarn.factor, yarn.mscale) \
        / t.yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    assert abs(m - published["attention_factor"]) < 1e-12
    assert abs(0.1 * math.log(16) + 1 - published["attention_factor"]) < 1e-12
    want, factor = reference.yarn_frequencies(128, 500000, published)
    assert factor == published["attention_factor"]
    got = t.yarn_inv_freq(128, 500000, yarn)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    plain = reference.plain_frequencies(128, 500000)
    assert got[0] == plain[0] and got[-1] == plain[-1] / 16
    assert any(p / 16 < g < p for g, p in zip(got, plain))  # the blend


# --- (e) what is not built is refused by name -------------------------------

REFUSED = {
    "attention='ring'": dict(attention="ring", sp_axis="sp"),
    "attention='ulysses'": dict(attention="ulysses", sp_axis="sp"),
    "attention_mask": dict(attention_mask=BandMask(8)),
    "kv_lora_rank": dict(kv_lora_rank=16, q_lora_rank=16, qk_norm=False),
    "layer_types": dict(layer_types=("attn",) * 4),
    "hc_mult": dict(hc_mult=2),
    "mtp_depth": dict(mtp_depth=1),
    "tp_axis": dict(tp_axis="tp", moe_experts=None, moe_held=None,
                    qk_norm=False),
    "num_layers": dict(num_layers=3),
    "each of full, window": dict(attention_types=("full", "local") * 2),
    "attention_window": dict(attention_window=None)}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    with pytest.raises(ValueError) as err:
        _cfg(**REFUSED[case])
    assert "attention_types" in str(err.value)
    assert case.replace("'", "").split("=")[0] in str(err.value).replace(
        "'", "")


def test_rope_yarn_on_plain_attention_needs_the_kinds():
    with pytest.raises(ValueError, match="attention_types"):
        _cfg(attention_types=None, attention_window=None)


def test_the_program_names_each_kind_and_the_older_stacks_none():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    assert (profile.ATTN_WINDOW, profile.ATTN_FULL) == (
        "hvd_attn_window", "hvd_attn_full")
    for i, kind in enumerate(KINDS):
        assert "block_%d/%s/attn" % (i, profile.ATTN_KINDS[kind]) in text
        other = profile.ATTN_KINDS["full" if kind == "window" else "window"]
        assert "block_%d/%s" % (i, other) not in text
    plain = _cfg(attention_types=None, attention_window=None, rope_yarn=None)
    model, params, tokens = _seeded(plain)
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    for scope in profile.ATTN_KINDS.values():
        # (the parts inside `attn`, `profile.ATTN_PARTS`, are every stack's)
        assert scope not in text
        assert scope not in profile.MODEL_SCOPES
