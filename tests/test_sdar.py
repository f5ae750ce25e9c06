"""Block-diffusion training on the normal train path: a mask by rule in the
flash kernels (`ops.BlockDiffusionMask`), `TransformerConfig.attention_mask`
and per-head QK-norm, `models.block_diffusion_batch` with the 1/t-weighted
loss, and softmax-routed experts of which a device holds a part: the system
against the plain reference `benchmark/references/sdar.py` at small sizes,
values and gradients."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import sdar as reference  # noqa: E402
from horovod_tpu import models, profile  # noqa: E402
from horovod_tpu.ops import BlockDiffusionMask  # noqa: E402
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy)
from horovod_tpu.parallel import expert, router_aux_losses  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

VOCAB, HIDDEN, LENGTH, EXPERTS, HELD, TOP_K = 96, 64, 64, 16, (4, 6), 4
T_MIN, W_BALANCE = 1e-3, 0.05


def _cfg(block, attention="dense", **over):
    base = dict(
        vocab_size=VOCAB, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, embed_dim=HIDDEN, mlp_dim=96, moe_dim=24,
        max_seq_len=2 * LENGTH, attention=attention, rope_base=1e6,
        qk_norm="head", attention_mask=BlockDiffusionMask(LENGTH, block),
        moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K,
        moe_capacity_factor=None, moe_gated=True, moe_held=HELD,
        dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, block):
    return {"num_layers": cfg.num_layers, "eps": cfg.norm_eps,
            "rope_base": cfg.rope_base, "top_k": TOP_K, "held": HELD,
            "block": block, "mask_id": VOCAB - 1, "t_min": T_MIN,
            "balance_weight": W_BALANCE}


def _seeded(cfg, seed=0):
    k_p, k_t, k_n, k_s = jax.random.split(jax.random.PRNGKey(seed), 4)
    tokens = jax.random.randint(k_t, (1, LENGTH), 0, VOCAB - 1, jnp.int32)
    model = models.Transformer(cfg)
    params = model.init(k_p, jnp.zeros((1, 2 * LENGTH), jnp.int32))["params"]
    # norm scales away from 1, so that a scale that is left out shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(k_s, len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])
    return model, params, tokens, jax.random.split(k_n, 1)


def _system_loss(model, params, tokens, keys, block):
    bd = models.block_diffusion_batch(keys, tokens, block, VOCAB - 1, T_MIN)
    hid, state = model.apply({"params": params}, bd["ids"], bd["positions"],
                             return_hidden=True, mutable=["intermediates"])
    ce = chunked_softmax_cross_entropy(
        models.block_diffusion_noisy_half(hid), params["lm_head"]["kernel"],
        bd["targets"], chunk=16, weights=bd["weights"])
    return ce + W_BALANCE * router_aux_losses(state["intermediates"])[0]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


# --- (a) the model, the batch and the weighted loss against the reference --

@pytest.mark.parametrize("seed", [0, 2])
@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("block", [4, 16])
def test_loss_and_gradients_agree_with_the_reference(block, attention, seed):
    cfg = _cfg(block, attention)
    model, params, tokens, keys = _seeded(cfg, seed)
    loss, grads = jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens, keys, block))(params)
    arch = _arch(cfg, block)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.forward(p, tokens[0], keys[0], arch)["loss"])(
            params)
    _close(loss, ref_loss, 2e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        assert np.max(np.abs(r)) > 0, path  # every parameter is reached
        _close(g, r, 2e-5)


@pytest.mark.parametrize("seed", [1, 3])
@pytest.mark.parametrize("block", [4, 16])
def test_states_routing_and_rows_agree_with_the_reference(block, seed):
    cfg = _cfg(block)
    model, params, tokens, keys = _seeded(cfg, seed)
    bd = models.block_diffusion_batch(keys, tokens, block, VOCAB - 1, T_MIN)
    hid, state = model.apply(
        {"params": params}, bd["ids"], bd["positions"], return_hidden=True,
        mutable=["intermediates"],
        capture_intermediates=lambda mdl, name: isinstance(
            mdl, models.transformer.Block) and name == "__call__")
    ref = reference.forward(params, tokens[0], keys[0], _arch(cfg, block))
    inter = state["intermediates"]
    for i in range(cfg.num_layers):
        _close(inter["block_%d" % i]["__call__"][0][0], ref["states"][i],
               2e-6)
    chosen = jnp.any(jax.nn.one_hot(
        expert.routing_stats(inter)["chosen"], EXPERTS, dtype=jnp.bool_),
        axis=-2)
    assert jnp.array_equal(chosen, ref["chosen"])
    # the same noise from the same key, by the reference's own code
    assert jnp.array_equal(bd["weights"][0] > 0, ref["masked"])
    _close(bd["t"][0], ref["t"], 1e-7)
    nll = jax.grad(lambda w: chunked_softmax_cross_entropy(
        models.block_diffusion_noisy_half(hid), params["lm_head"]["kernel"],
        bd["targets"], chunk=16, weights=w))(jnp.zeros((1, LENGTH)))
    _close(nll[0], ref["nll"], 2e-6)


@pytest.mark.parametrize("swap", ["the system's own sets", "one near tie",
                                  "the least likely expert"])
def test_the_reference_follows_given_sets_and_says_how_near_a_tie(swap):
    """`reference.forward(follow=)`: with the sets it would choose itself
    nothing moves and every margin is 0; with the 8th and 9th most likely
    experts of one position swapped the margin is that pair's relative
    distance and only that position's state moves; with the LEAST likely
    expert in the 8th one's place the margin is near 1."""
    cfg = _cfg(4)
    model, params, tokens, keys = _seeded(cfg, seed=4)
    arch = _arch(cfg, 4)
    ref = reference.forward(params, tokens[0], keys[0], arch)
    assert float(jnp.max(jnp.abs(ref["margin"]))) == 0.0
    follow = ref["chosen"]
    if swap == "the system's own sets":
        same = reference.forward(params, tokens[0], keys[0], arch,
                                 follow=follow)
        for k in ("states", "nll", "loss", "margin", "chosen"):
            assert jnp.array_equal(same[k], ref[k]), k
        return
    # Layer 0, position 5. The layer's probabilities are not handed out:
    # the pairs are tried and their margins read.
    out = [int(e) for e in jnp.where(~follow[0, 5])[0]]
    inside = [int(e) for e in jnp.where(follow[0, 5])[0]]
    margins = {}
    for drop in inside:
        for take in (out if swap == "one near tie" else out[:8]):
            f = follow.at[0, 5, drop].set(False).at[0, 5, take].set(True)
            margins[drop, take] = float(reference.forward(
                params, tokens[0], keys[0], arch, follow=f)["margin"][0, 5])
        if swap != "one near tie":
            break
    assert all(m > 0 for m in margins.values())
    if swap == "one near tie":
        # the nearest tie: dropping the 8th for the 9th; nobody else moves
        (drop, take), least = min(margins.items(), key=lambda kv: kv[1])
        assert least < 0.5
        f = follow.at[0, 5, drop].set(False).at[0, 5, take].set(True)
        moved = reference.forward(params, tokens[0], keys[0], arch, follow=f)
        assert jnp.array_equal(moved["chosen"][0], ref["chosen"][0])
        rows = jnp.any(moved["states"][0] != ref["states"][0], axis=-1)
        assert jnp.array_equal(jnp.where(rows)[0], jnp.array([5]))
        assert float(jnp.sum(moved["margin"][0] > 0)) == 1
    else:
        # any expert outside in the place of one inside: a margin that
        # grows with the distance; the least likely of them above 0.3
        assert max(margins.values()) > 0.3


@pytest.mark.parametrize("wrong", [1, 2, "unit_weights"])
def test_the_comparison_tells_the_mechanism(wrong):
    """A reference of another model (the causal mask; the clean half left
    out; weights 1 for 1 / t) is far from the one the system agrees with
    (the tests above): in the last block's state, or in the loss."""
    cfg = _cfg(4)
    model, params, tokens, keys = _seeded(cfg, seed=2)
    arch = _arch(cfg, 4)
    ref = reference.forward(params, tokens[0], keys[0], arch)
    if wrong == "unit_weights":
        other = ref["ce_unit_weights"] + W_BALANCE * ref["balance"]
        assert abs(float(ref["loss"] - other)) > 0.1 * float(other)
        return
    other = reference.forward(params, tokens[0], keys[0], arch,
                              wrong)["states"][-1]
    noisy = slice(0, LENGTH)  # the clean half sees no noisy key either way
    far = jnp.max(jnp.abs(other[noisy] - ref["states"][-1][noisy]))
    assert float(far) > 0.05 * float(jnp.max(jnp.abs(other)))


# --- (b) the mask-ruled kernels against a dense masked softmax -------------

def _dense_mask(rule):
    S = 2 * rule.length
    return np.asarray(rule.visible(np.arange(S)[:, None],
                                   np.arange(S)[None, :], np))


def _dense_attention(q, k, v, scale, mask):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _kernel_case(length, H, G, D=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    S = 2 * length
    shape = lambda heads: (1, heads, S, D)  # noqa: E731
    return (jax.random.normal(ks[0], shape(H)),
            jax.random.normal(ks[1], shape(G)),
            jax.random.normal(ks[2], shape(G)),
            jax.random.normal(ks[3], shape(H)))


def _budget(path, H, G, S, D, bq, bk, rule, monkeypatch):
    """The VMEM budget that forces `path` of the backward under `rule`: one
    byte short of what the form before it holds, in the order `flash_plan`
    tries them (the one kernel held by the k block, then by the q block;
    dQ beside dK/dV held by the k block; gridded). The two kernels hold
    less than the one held by the q block only with one head a kv head, so
    they are reached with that form out of the order."""
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


# (block, heads, kv heads, rows of a q block, k block): at 2 x 256 positions
# a q block of 32 or 64 positions under k blocks of 128 meets tiles the rule
# empties, fills and cuts.
KERNEL_CASES = [(4, 4, 2, 64, 128), (16, 4, 1, 128, 128), (16, 2, 2, 64, 128)]


def _kernels_against_the_dense_mask(rule, H, G, bq, bk, path, monkeypatch,
                                    seed=0):
    """The forward and the backward in the form `path` names, at the given
    blocks, against the dense masked softmax; the backward's plans."""
    length, D = rule.length, 64
    q, k, v, w = _kernel_case(length, H, G, D, seed)
    mask = jnp.asarray(_dense_mask(rule))
    want, vjp = jax.vjp(lambda *a: _dense_attention(*a, D ** -0.5, mask),
                        q, k, v)
    budget = _budget(path, H, G, 2 * length, D, bq, bk, rule, monkeypatch)
    plans = fa.flash_plan(1, H, 2 * length, D, H // G, q.dtype, True, bq, bk,
                          budget, mask=rule)
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


@pytest.mark.parametrize("path", ["one kernel", "two resident",
                                  "q-held", "gridded dK/dV"])
@pytest.mark.parametrize("block,H,G,bq,bk", KERNEL_CASES)
def test_ruled_kernels_agree_with_a_dense_masked_softmax(block, H, G, bq, bk,
                                                         path, monkeypatch):
    _kernels_against_the_dense_mask(BlockDiffusionMask(256, block), H, G, bq,
                                    bk, path, monkeypatch)


# (length, block, heads, kv heads, rows of a q block, k block, cut_k, cut k
# blocks a kv head with one sub-tile in sight) with `_CUT_K` set to cut_k: a
# noisy q tile's own blocks lie in one sub-tile, and the clean k block that
# holds its own block's copy begins with it a time in four; a head group of
# 4; diffusion blocks as wide as a sub-tile; the cell's steps, (128, 512,
# 128).
CUT_CASES = [(256, 4, 4, 2, 64, 128, 32, 12),
             (256, 16, 4, 1, 128, 128, 32, 12),
             (256, 64, 2, 2, 32, 256, 64, 12),
             (512, 4, 1, 1, 128, 512, 128, 6)]


@pytest.mark.parametrize("path", ["two resident", "q-held"])
@pytest.mark.parametrize("length,block,H,G,bq,bk,cut_k,lone", CUT_CASES)
def test_ruled_kernels_take_a_lone_sub_tile_alone(length, block, H, G, bq,
                                                  bk, cut_k, lone, path,
                                                  monkeypatch):
    """The kernels held by the q block against the dense masked softmax: the
    forward and dQ by its own kernel under `_walk_runs_merged`, the
    one-kernel backward under `_walk_cut_runs`, where a cut k block with one
    sub-tile in sight (`lone`: how many the call has a kv head) is computed
    as that sub-tile."""
    monkeypatch.setattr(fa, "_CUT_K", cut_k)
    plans = _kernels_against_the_dense_mask(
        BlockDiffusionMask(length, block), H, G, bq, bk, path, monkeypatch,
        seed=7)
    if path == "two resident":  # dQ carries its sum: k blocks alone
        assert plans[profile.FLASH_DQ].cut_k == bk
    else:
        cut, ratio = plans[profile.FLASH_BWD], bk // cut_k
        assert (cut.held, cut.cut_k) == ("q", cut_k)
        assert G * lone * (ratio - 1) == (
            ratio * cut.tiles_visited - cut.subtiles_visited) == (
            ratio * cut.tiles_masked - cut.subtiles_masked)


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("length,block,group,bq,bk", [
    (256, 4, 2, 64, 128), (256, 16, 4, 128, 128), (256, 16, 1, 8, 128),
    (512, 4, 1, 128, 256), (4096, 4, 8, None, None)])
def test_flash_plan_counts_the_tiles_the_dense_mask_has(length, block, group,
                                                        bq, bk, backward):
    rule = BlockDiffusionMask(length, block)
    S, H = 2 * length, 2 * group
    plans = fa.flash_plan(1, H, S, 128, group, jnp.bfloat16, backward,
                          block_q=bq, block_k=bk, mask=rule)
    assert plans
    mask = _dense_mask(rule)
    for name, p in plans.items():
        bqp = p.block_q // group
        tiles = mask.reshape(S // bqp, bqp, S // p.block_k, p.block_k)
        some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        heads = H // group
        assert p.tiles_visited == heads * some.sum(), name
        assert p.tiles_skipped == heads * (~some).sum(), name
        # what needs the mask pass is masked; a full tile may be too only
        # where a diffusion block is as wide as a tile (none here)
        assert p.tiles_masked == heads * (some & ~every).sum(), name
        assert 0 < p.tiles_skipped and 0 < p.tiles_masked < p.tiles_visited


def test_the_cells_shape_runs_kernels_in_both_directions():
    """1 x 32 heads on 4, 8192 positions, D 128: the forward resident on k +
    v and the backward ONE kernel, both held by the q block (q + dO of a kv
    head's 8 query heads do not fit; the backward holds k, v, dk, dv and two
    accumulators: 24 MiB), 5/16 of the tiles visited; the two kernels with
    dK/dV gridded, one byte under that, would walk the same tiles."""
    rule = BlockDiffusionMask(4096, 4)
    plans = {n: p for b in (False, True) for n, p in fa.flash_plan(
        1, 32, 8192, 128, 8, jnp.bfloat16, b, mask=rule).items()}
    assert sorted(plans) == [profile.FLASH_BWD, profile.FLASH_FWD]
    assert plans[profile.FLASH_BWD].resident_bytes == 24 * 2 ** 20
    two = fa.flash_plan(1, 32, 8192, 128, 8, jnp.bfloat16, True,
                        vmem_budget=24 * 2 ** 20 - 1, mask=rule)
    assert sorted(two) == [profile.FLASH_DKV, profile.FLASH_DQ]
    gridded = two[profile.FLASH_DKV]
    assert (gridded.path, gridded.held, gridded.grid) == (
        "gridded", "k", (4, 16, 64))
    for p in list(plans.values()) + list(two.values()):
        if p is not gridded:
            assert (p.path, p.held, p.grid, p.grid_steps) == (
                "resident", "q", (4, 64), 256)
        assert (p.block_q, p.block_k) == (1024, 512)
        assert (p.tiles_visited, p.tiles_masked, p.tiles_skipped) == (
            1280, 384, 2816)


@pytest.mark.parametrize("interpret", [True, None])
def test_ruled_custom_vjp(interpret):
    length, D = 128, 64
    rule = BlockDiffusionMask(length, 4)
    q, k, v, w = _kernel_case(length, 4, 2, D, seed=3)
    mask = jnp.asarray(_dense_mask(rule))
    want, vjp = jax.vjp(lambda *a: _dense_attention(*a, D ** -0.5, mask),
                        q, k, v)
    out, got = jax.vjp(lambda *a: fa._flash(*a, D ** -0.5, False, interpret,
                                            rule), q, k, v)
    _close(out, want, 2e-6)
    for g, r in zip(got(w), vjp(w)):
        _close(g, r, 2e-6)


def test_ruled_calls_of_one_shape_share_one_lowering():
    """Three layers' worth of ruled calls, forward and backward: the
    lowered module holds each kernel's function ONCE (the calls are jitted;
    a `pl.pallas_call` costs a step's lowering a quarter of a second each
    time it is lowered), called three times."""
    length, D = 128, 64
    rule = BlockDiffusionMask(length, 4)
    q, k, v, _ = _kernel_case(length, 4, 2, D, seed=5)

    def three(q, k, v):
        for _ in range(3):
            q = fa._flash(q, k, v, D ** -0.5, False, True, rule)
        return jnp.sum(q.astype(jnp.float32))

    text = jax.jit(jax.grad(three, argnums=(0, 1, 2))).lower(
        q, k, v).as_text()
    for name in ("_ruled_hvd_flash_fwd", "_ruled_hvd_flash_bwd"):
        assert text.count("func.func private @%s" % name) == 1, name
        assert text.count("call @%s" % name) == 3, name


def test_flash_attention_takes_the_rule_and_refuses_what_it_cannot():
    rule = BlockDiffusionMask(128, 4)
    q, k, v, _ = _kernel_case(128, 4, 2, seed=4)
    to_blhd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    got = fa.flash_attention(to_blhd(q), to_blhd(k), to_blhd(v), mask=rule)
    _close(to_blhd(got), _dense_attention(
        q, k, v, 64 ** -0.5, jnp.asarray(_dense_mask(rule))), 2e-6)
    with pytest.raises(ValueError, match="one score product"):
        fa.flash_plan(1, 4, 256, 64, 2, shared_dim=64, mask=rule)
    with pytest.raises(ValueError, match="2 x length"):
        fa.flash_attention(to_blhd(q), to_blhd(k), to_blhd(v),
                           mask=BlockDiffusionMask(64, 4))
    with pytest.raises(ValueError, match="straddle"):
        fa.flash_plan(1, 4, 256, 64, 2, block_q=64, block_k=256, mask=rule)
    # no resident forward, no kernel: the call is the blockwise jnp form
    assert fa.flash_plan(1, 4, 256, 64, 2, vmem_budget=0, mask=rule) == {}


# --- (d) the shares of an 8-way group add up to the uncut layer ------------

def test_eight_shares_of_a_softmax_top8_layer_add_up_to_the_uncut_layer():
    E, D, F, T, k = 128, 32, 24, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_gate, w_up = (0.3 * jax.random.normal(key, (E, D, F))
                    for key in ks[2:4])
    w_down = 0.3 * jax.random.normal(ks[4], (E, F, D))
    whole, stats = expert.moe_ffn(x, router, w_up, w_down, None, top_k=k,
                                  w_gate=w_gate)
    total, held = 0.0, 0
    for r in range(8):
        own = slice(16 * r, 16 * r + 16)
        y, s = expert.moe_ffn(x, router, w_up[own], w_down[own], None,
                              top_k=k, w_gate=w_gate[own], held=(16 * r, 16))
        total, held = total + y, held + int(s["held"])
        # the balancing term is over all 128 outputs on every rank
        _close(s["load_balance_loss"], stats["load_balance_loss"], 1e-6)
        assert s["assignments"].shape == (E,)
    _close(total, whole, 2e-6)
    assert held == k * T


# --- (e) the norm over each head ------------------------------------------

def test_per_head_qk_norm_against_its_three_lines():
    cfg = _cfg(4, num_layers=1, attention_mask=None)
    model, params, tokens, _ = _seeded(cfg, seed=6)
    attn = params["block_0"]["attn"]
    assert attn["q_norm"]["scale"].shape == (16,) == \
        attn["k_norm"]["scale"].shape
    x = jax.random.normal(jax.random.PRNGKey(7), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    got = models.transformer.Attention(cfg).apply({"params": attn}, x, pos)

    def normed(t, scale):  # the three lines
        ms = jnp.mean(t * t, axis=-1, keepdims=True)
        return t * jax.lax.rsqrt(ms + cfg.norm_eps) * scale

    q = normed(jnp.einsum("bld,dhk->blhk", x, attn["query"]["kernel"]),
               attn["q_norm"]["scale"])
    k = normed(jnp.einsum("bld,dhk->blhk", x, attn["key"]["kernel"]),
               attn["k_norm"]["scale"])
    v = jnp.einsum("bld,dhk->blhk", x, attn["value"]["kernel"])
    q = models.transformer._rotary(q, pos, cfg.rope_base)
    k = jnp.repeat(models.transformer._rotary(k, pos, cfg.rope_base), 2, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((LENGTH, LENGTH), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                   jnp.repeat(v, 2, 2))
    _close(got, jnp.einsum("bqhd,hdc->bqc", o, attn["out"]["kernel"]), 2e-6)
    # and it is not the whole-projection norm
    other = models.transformer.Attention(_cfg(
        4, num_layers=1, attention_mask=None, qk_norm=False)).apply(
            {"params": {n: attn[n] for n in ("query", "key", "value",
                                             "out")}}, x, pos)
    assert float(jnp.max(jnp.abs(got - other))) > 1e-3


# --- (f) the noise's counters ----------------------------------------------

@pytest.mark.parametrize("block", [4, 16])
@pytest.mark.parametrize("keyed", ["one key", "a key a sequence"])
def test_block_diffusion_batch_and_stats(block, keyed):
    B, L, mask_id = 3, 128, VOCAB - 1
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, L), 0, mask_id)
    key = jax.random.PRNGKey(9)
    keys = key if keyed == "one key" else jax.random.split(key, B)
    bd = models.block_diffusion_batch(keys, tokens, block, mask_id, 0.25)
    stats = jax.device_get(models.block_diffusion_stats(bd, 0.25))
    assert np.all(stats["masked"] + stats["kept"] == L)
    assert stats["t_outside"] == 0 and 0.25 <= stats["t_lowest"] \
        and stats["t_highest"] <= 1.0
    masked = np.asarray(bd["weights"] > 0)
    assert np.all(stats["masked"] == masked.sum(axis=1))
    assert np.all(np.asarray(bd["ids"][:, :L])[masked] == mask_id)
    assert np.all(np.asarray(bd["ids"][:, :L])[~masked]
                  == np.asarray(tokens)[~masked])
    assert jnp.array_equal(bd["ids"][:, L:], tokens)
    assert jnp.array_equal(bd["positions"][:, :L], bd["positions"][:, L:])
    t_row = np.repeat(np.asarray(bd["t"]), block, axis=1)
    np.testing.assert_allclose(np.asarray(bd["weights"])[masked],
                               1.0 / (t_row[masked] * B * L), rtol=1e-6)
    assert stats["empty_blocks"] == (
        masked.reshape(B, L // block, block).sum(-1) == 0).sum()
    with pytest.raises(ValueError, match="must divide"):
        models.block_diffusion_batch(key, tokens, 48, mask_id)


# --- what is not built is refused by name ----------------------------------

REFUSED = {
    "attention_mask beside tp_axis": dict(
        attention_mask=BlockDiffusionMask(64, 4), tp_axis="tp"),
    "attention_mask beside sp_axis": dict(
        attention_mask=BlockDiffusionMask(64, 4), attention="ring",
        sp_axis="sp"),
    "qk_norm='head' beside tp_axis": dict(qk_norm="head", tp_axis="tp"),
    "qk_norm='head' beside sp_axis": dict(qk_norm="head", attention="ring",
                                          sp_axis="sp"),
    "attention_mask beside latent attention": dict(
        attention_mask=BlockDiffusionMask(64, 4), kv_lora_rank=16,
        q_lora_rank=16),
    "another qk_norm": dict(qk_norm="heads")}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    name = case.split(" beside ")[0] if " beside " in case else "qk_norm"
    with pytest.raises(ValueError, match=name.replace("'", ".")):
        models.TransformerConfig(**REFUSED[case])


def test_the_program_names_the_new_part():
    cfg = _cfg(4)
    model, params, tokens, keys = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _system_loss(
        model, p, tokens, keys, 4))).lower(params).as_text(debug_info=True)
    assert profile.BD == "hvd_bd" and profile.BD in text
    assert profile.BD not in profile.MODEL_SCOPES
