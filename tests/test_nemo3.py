"""A layer pattern of single-mixer layers (`layer_types`): the Mamba-2 mixer
with its chunked scan, attention without rotary, LatentMoE (sigmoid-routed
relu2 experts in a latent, a part of them held, beside an ungated shared
expert): the system against the plain reference
`benchmark/references/nemo3.py` at small sizes, values and gradients; the
chunked scan against the sequential one; the shares of a deployment's ranks
against the uncut layer; and what the new fields refuse."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import nemo3 as reference  # noqa: E402
from horovod_tpu import models, parallel, profile  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops.ssd import ssd_scan  # noqa: E402
from horovod_tpu.parallel import expert  # noqa: E402

VOCAB, HIDDEN, LENGTH = 256, 64, 96
HEADS, KV_HEADS, HEAD_DIM = 4, 2, 16
SSM = dict(ssm_heads=8, ssm_head_dim=16, ssm_groups=2, ssm_state=16,
           ssm_conv=4, ssm_chunk=32)
EXPERTS, HELD, TOP_K = 16, (2, 4), 3
PATTERN = ("ssm", "moe", "attn", "moe", "ssm")


def _cfg(**over):
    base = dict(
        vocab_size=VOCAB, num_layers=len(PATTERN), num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, embed_dim=HIDDEN,
        mlp_dim=96, max_seq_len=LENGTH, attention="dense", norm_eps=1e-5,
        rotary=False, layer_types=PATTERN, moe_experts=EXPERTS,
        moe_top_k=TOP_K, moe_dim=48, moe_capacity_factor=None,
        moe_gated=False, moe_scoring="sigmoid", moe_route_scale=5.0,
        moe_shared_dim=80, moe_shared_gated=False, moe_act="relu2",
        moe_latent_dim=32, moe_held=HELD, dtype=jnp.float32, **SSM)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"pattern": cfg.layer_types, "eps": cfg.norm_eps,
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_groups": cfg.ssm_groups, "ssm_state": cfg.ssm_state,
            "top_k": cfg.moe_top_k, "norm_topk_prob": cfg.moe_renormalize,
            "route_scale": cfg.moe_route_scale, "held": held}


def _seeded(cfg, seed=0):
    """(model, parameters with every vector moved off its initial value —
    norm scales, D, the selection bias —, tokens [1, LENGTH])."""
    model = models.Transformer(cfg)
    k_p, k_t, k_n = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (1, LENGTH), 0, VOCAB, jnp.int32)
    params = model.init(k_p, tokens)["params"]
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(k_n, len(flat))
    out = []
    for key, (path, x) in zip(keys, flat):
        name = getattr(path[-1], "key", "")
        if name == "select_bias":
            x = 0.05 * jax.random.normal(key, x.shape)
        elif name in ("scale", "norm", "D"):
            x = x + 0.2 * jax.random.normal(key, x.shape)
        out.append(x)
    return model, jax.tree_util.tree_unflatten(tree, out), tokens


def _system_loss(model, params, tokens):
    logits = model.apply({"params": params}, tokens)
    nll = -jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1),
        jnp.roll(tokens, -1, 1)[..., None], -1)[..., 0]
    return jnp.mean(nll)


def _close(a, b, tol, what=""):
    err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
    assert err <= tol, "%s: rel err %.3e > %.0e" % (what, err, tol)


# --------------------------------------------------------------------------
# The program against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_states_and_loss_agree_with_the_reference(attention):
    cfg = _cfg(attention=attention)
    model, params, tokens = _seeded(cfg)
    logits, state = model.apply(
        {"params": params}, tokens, mutable=["intermediates"],
        capture_intermediates=lambda mdl, name: isinstance(
            mdl, transformer.Block) and name == "__call__")
    inter = state["intermediates"]
    ref = reference.forward(params, tokens[0], _arch(cfg))
    for i in range(cfg.num_layers):
        _close(inter["block_%d" % i]["__call__"][0][0], ref["states"][i],
               2e-5, "layer %d (%s)" % (i, PATTERN[i]))
    np.testing.assert_allclose(_system_loss(model, params, tokens),
                               ref["loss"], rtol=2e-6)
    stats = parallel.routing_stats(inter)
    chosen = jnp.any(jax.nn.one_hot(stats["chosen"], EXPERTS,
                                    dtype=jnp.bool_), axis=-2)
    assert bool(jnp.all(chosen == ref["chosen"]))
    first, count = HELD
    np.testing.assert_array_equal(
        jnp.sum(stats["assignments"][:, first:first + count], axis=1),
        ref["held_rows"])
    # the reference looks at every SCAN_BLOCK-th state, the program at the
    # chunk borders: the same tokens here
    assert reference.SCAN_BLOCK % cfg.ssm_chunk == 0 or \
        cfg.ssm_chunk % reference.SCAN_BLOCK == 0
    top = float(models.ssd_stats(inter))
    assert 0.5 * float(ref["state_max"]) <= top <= float(ref["state_max"]) \
        * 1.0001


def test_every_gradient_agrees_with_the_reference():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg, seed=1)
    mine = jax.grad(lambda p: _system_loss(model, p, tokens))(params)
    theirs = jax.grad(lambda p: reference.loss(p, tokens[0], _arch(cfg)))(
        params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree_util.tree_leaves(theirs)):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:  # no gradient reaches it, on either side
            assert not bool(jnp.any(a)) and not bool(jnp.any(b))
            continue
        _close(a, b, 5e-5, name)


def test_the_comparison_tells_the_shared_expert():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    ref = reference.forward(params, tokens[0], _arch(cfg))
    without = reference.forward(params, tokens[0], _arch(cfg), shared=0.0)
    assert float(jnp.max(jnp.abs(ref["states"] - without["states"]))) > 1e-2


def test_a_train_step_runs_the_pattern_and_its_loss_falls():
    import optax
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    mesh = parallel.data_parallel_mesh(devices=jax.devices()[:1])
    step = parallel.make_train_step(
        lambda p, b: _system_loss(model, p, b["x"]), optax.adam(1e-2), mesh)
    opt = optax.adam(1e-2)
    state = step.place(params, opt.init(params), {"x": tokens})
    p, o, batch = state
    losses = []
    for _ in range(4):
        p, o, loss = step(p, o, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------------
# The chunked scan against the sequential one
# --------------------------------------------------------------------------

def _scan_inputs(seed, L=96, H=4, P=8, G=2, N=16, slow=True):
    """Inputs of a scan over three chunks of 32. `slow`: decays near 1
    (dt a in [-0.02, -0.002] a token), so that what the first chunk wrote
    is still half there at the end: a state survives both chunk borders."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1, L, H, P))
    b = jax.random.normal(ks[1], (1, L, G, N))
    c = jax.random.normal(ks[2], (1, L, G, N))
    lo, hi = (0.002, 0.02) if slow else (0.05, 1.0)
    dt = jax.random.uniform(ks[3], (1, L, H), jnp.float32, lo, hi)
    a = -jax.random.uniform(ks[4], (H,), jnp.float32, 0.5, 1.0)
    return x, dt, a, b, c


@pytest.mark.parametrize("slow", [True, False])
def test_chunked_scan_is_the_sequential_scan_across_chunk_borders(slow):
    x, dt, a, b, c = _scan_inputs(0, slow=slow)
    y, top = ssd_scan(x, dt, a, b, c, 32)
    y_ref, top_ref = reference.sequential_scan(x[0], dt[0], a, b[0], c[0],
                                               block=32)
    _close(y[0], y_ref, 1e-5, "y")
    np.testing.assert_allclose(top, top_ref, rtol=1e-5)
    if slow:
        # what the first chunk wrote reaches the third: cutting the carry
        # (three scans of one chunk) is far from the scan
        cut = jnp.concatenate([
            ssd_scan(*(t[:, s:s + 32] for t in (x, dt)), a,
                     *(t[:, s:s + 32] for t in (b, c)), 32)[0]
            for s in (0, 32, 64)], axis=1)
        assert float(jnp.max(jnp.abs(cut[0, 64:] - y_ref[64:]))) > \
            0.1 * float(jnp.max(jnp.abs(y_ref[64:])))


def test_chunked_scans_gradients_are_the_sequential_scans():
    x, dt, a, b, c = _scan_inputs(1)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def mine(x, dt, a, b, c):
        return jnp.sum(ssd_scan(x, dt, a, b, c, 32)[0] * w)

    def theirs(x, dt, a, b, c):
        return jnp.sum(reference.sequential_scan(
            x[0], dt[0], a, b[0], c[0])[0] * w[0])

    got = jax.grad(mine, argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    want = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for name, g, r in zip("x dt a b c".split(), got, want):
        _close(g, r, 2e-5, "d" + name)


def test_a_state_carried_in_bf16_is_told_from_the_scan():
    """The chunked scan on bf16 operands (f32 decays, sums and carry) stays
    within a few bf16 roundings of the f32 sequential scan; a sequential
    scan that rounds its STATE to bf16 after every token does not: the
    tolerance that passes the first refuses the second."""
    x, dt, a, b, c = _scan_inputs(2, L=384, slow=True)
    y_ref = reference.sequential_scan(x[0], dt[0], a, b[0], c[0])[0]
    bf = jnp.bfloat16
    y = ssd_scan(x.astype(bf), dt, a, b.astype(bf), c.astype(bf), 32)[0][0]

    def rounded(S, inp):
        x_t, dt_t, b_t, c_t = inp
        bh, ch = jnp.repeat(b_t, 2, axis=0), jnp.repeat(c_t, 2, axis=0)
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        S = S.astype(bf).astype(jnp.float32)
        return S, jnp.sum(S * ch[:, None, :], axis=-1)

    y_low = jax.lax.scan(rounded, jnp.zeros((4, 8, 16)),
                         (x[0], dt[0], b[0], c[0]))[1]
    scale = float(jnp.max(jnp.abs(y_ref)))
    err = float(jnp.max(jnp.abs(y - y_ref))) / scale
    err_low = float(jnp.max(jnp.abs(y_low - y_ref))) / scale
    assert err <= 1e-2 < err_low, (err, err_low)


def test_scan_refuses_a_length_that_is_no_whole_number_of_chunks():
    x, dt, a, b, c = _scan_inputs(0)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_scan(x, dt, a, b, c, 64)


def test_mamba2_starts_at_its_own_values():
    cfg = _cfg()
    _, _, tokens = _seeded(cfg)
    p = models.Transformer(cfg).init(jax.random.PRNGKey(3), tokens)[
        "params"]["block_0"]["ssm"]
    dt = jax.nn.softplus(p["dt_bias"])
    lo, hi, floor = cfg.ssm_dt_init
    assert float(jnp.min(dt)) >= min(lo, floor) * 0.999
    assert float(jnp.max(dt)) <= hi * 1.001
    assert float(jnp.min(p["A_log"])) >= 0.0
    assert float(jnp.max(p["A_log"])) <= float(jnp.log(16.0))
    np.testing.assert_array_equal(p["D"], jnp.ones_like(p["D"]))
    assert p["in_proj"]["kernel"].shape == (
        HIDDEN, 2 * 8 * 16 + 2 * 2 * 16 + 8)
    assert p["conv_kernel"].shape == (4, 8 * 16 + 2 * 2 * 16)


# --------------------------------------------------------------------------
# The shares of a deployment's ranks add up to the uncut layer
# --------------------------------------------------------------------------

def _moe(cfg, held):
    return expert.MoeMlp(
        num_experts=cfg.moe_experts, mlp_dim=cfg.moe_dim,
        capacity_factor=None, top_k=cfg.moe_top_k, gated=False,
        renormalize=True, dtype=jnp.float32, scoring="sigmoid",
        route_scale=cfg.moe_route_scale, held=held,
        shared_dim=cfg.moe_shared_dim, act="relu2", shared_gated=False,
        latent_dim=cfg.moe_latent_dim)


@pytest.mark.parametrize("ranks", [2, 16])
def test_the_expert_shares_add_up_to_the_uncut_layer(ranks):
    """Each rank holds EXPERTS / ranks experts and computes W_2 of its
    experts' part of the latent sum + the shared expert; with the router,
    the latent projections and the shared expert counted once, the ranks'
    parts sum to the layer that holds every expert."""
    cfg = _cfg()
    u = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    whole = _moe(cfg, None)
    params = whole.init(jax.random.PRNGKey(1), u)["params"]
    params = dict(params, select_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (EXPERTS,)))
    uncut = whole.apply({"params": params}, u)
    shared = reference.relu2(u @ params["shared_up"]["kernel"]) \
        @ params["shared_down"]["kernel"]
    count = EXPERTS // ranks
    total = shared
    for r in range(ranks):
        mine = dict(params, w_in=params["w_in"][r * count:(r + 1) * count],
                    w_out=params["w_out"][r * count:(r + 1) * count])
        total = total + _moe(cfg, (r * count, count)).apply(
            {"params": mine}, u) - shared
    _close(total, uncut, 1e-5)
    # and the uncut layer is the reference's with every expert held
    ref = reference.latent_moe(params, u[0], _arch(cfg, (0, EXPERTS)))[0]
    _close(uncut[0], ref, 1e-5)


def _take(x, axis, ranges):
    return jnp.concatenate([jnp.take(x, jnp.arange(lo, hi), axis=axis)
                            for lo, hi in ranges], axis=axis)


def test_the_head_shares_of_a_mamba2_layer_add_up():
    """A rank that holds half the heads and half the groups runs a Mamba-2
    layer of that size (convolution a channel, state a head, B, C and the
    norm a group); the two ranks' out-projections sum to the uncut
    layer's."""
    cfg = _cfg()
    H, P, G, N = 8, 16, 2, 16
    inner, gn = H * P, G * N
    u = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    whole = transformer.Mamba2(cfg)
    p = whole.init(jax.random.PRNGKey(1), u)["params"]
    p = dict(p, norm=p["norm"] + 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), p["norm"].shape))
    uncut = whole.apply({"params": p}, u)
    half = dataclasses.replace(cfg, ssm_heads=H // 2, ssm_groups=G // 2)
    total = 0.0
    for r in range(2):
        ch = (r * inner // 2, (r + 1) * inner // 2)       # z and x
        gr = (r * gn // 2, (r + 1) * gn // 2)             # B and C
        hd = (r * H // 2, (r + 1) * H // 2)
        conv = [(ch[0], ch[1]), (inner + gr[0], inner + gr[1]),
                (inner + gn + gr[0], inner + gn + gr[1])]
        cols = [(ch[0], ch[1])] + [(inner + lo, inner + hi)
                                   for lo, hi in conv] \
            + [(2 * inner + 2 * gn + hd[0], 2 * inner + 2 * gn + hd[1])]
        mine = {
            "in_proj": {"kernel": _take(p["in_proj"]["kernel"], 1, cols)},
            "conv_kernel": _take(p["conv_kernel"], 1, conv),
            "conv_bias": _take(p["conv_bias"], 0, conv),
            "dt_bias": p["dt_bias"][hd[0]:hd[1]],
            "A_log": p["A_log"][hd[0]:hd[1]], "D": p["D"][hd[0]:hd[1]],
            "norm": p["norm"][ch[0]:ch[1]],
            "out_proj": {"kernel": p["out_proj"]["kernel"][ch[0]:ch[1]]}}
        total = total + transformer.Mamba2(half).apply({"params": mine}, u)
    _close(total, uncut, 1e-5)


def test_the_head_shares_of_an_attention_layer_add_up():
    """Half the query heads on half the kv heads, twice, sum to the layer
    (no rotary: nothing but the heads' own projections)."""
    cfg = _cfg()
    u = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    whole = transformer.Attention(cfg)
    p = whole.init(jax.random.PRNGKey(1), u, pos)["params"]
    uncut = whole.apply({"params": p}, u, pos)
    half = dataclasses.replace(cfg, num_heads=HEADS // 2,
                               num_kv_heads=KV_HEADS // 2)
    total = 0.0
    for r in range(2):
        q = slice(r * HEADS // 2, (r + 1) * HEADS // 2)
        kv = slice(r * KV_HEADS // 2, (r + 1) * KV_HEADS // 2)
        mine = {"query": {"kernel": p["query"]["kernel"][:, q]},
                "key": {"kernel": p["key"]["kernel"][:, kv]},
                "value": {"kernel": p["value"]["kernel"][:, kv]},
                "out": {"kernel": p["out"]["kernel"][q]}}
        total = total + transformer.Attention(half).apply(
            {"params": mine}, u, pos)
    _close(total, uncut, 1e-5)
    _close(uncut[0], reference.attention(p, u[0]), 1e-5)


def test_attention_without_rotary_reads_no_position():
    cfg = _cfg()
    u = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    attn = transformer.Attention(cfg)
    p = attn.init(jax.random.PRNGKey(1), u, pos)["params"]
    np.testing.assert_array_equal(attn.apply({"params": p}, u, pos),
                                  attn.apply({"params": p}, u, pos + 7))
    turned = transformer.Attention(dataclasses.replace(cfg, rotary=True))
    assert float(jnp.max(jnp.abs(
        turned.apply({"params": p}, u, pos)
        - attn.apply({"params": p}, u, pos)))) > 1e-3


# --------------------------------------------------------------------------
# The routed layer's new arguments
# --------------------------------------------------------------------------

def test_routing_stats_are_in_the_stacks_order_past_ten_layers():
    """`block_10` sorts before `block_3` by name; the statistics are
    stacked by depth."""
    kinds = ("moe",) * 11
    cfg = _cfg(layer_types=kinds, num_layers=11, moe_held=None)
    model, params, tokens = _seeded(cfg)
    _, state = model.apply({"params": params}, tokens,
                           mutable=["intermediates"])
    inter = state["intermediates"]
    stats = parallel.routing_stats(inter)
    for i in range(11):
        np.testing.assert_array_equal(
            stats["assignments"][i],
            inter["block_%d" % i]["moe_mlp"]["moe_assignments"][0])
    assert not np.array_equal(stats["assignments"][2],
                              stats["assignments"][10])


def test_moe_ffn_routes_on_the_state_and_multiplies_the_rows():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    T, D, R, F, E = 64, 32, 16, 24, 8
    x = jax.random.normal(ks[0], (T, D))
    rows = jax.random.normal(ks[1], (T, R))
    router = jax.random.normal(ks[2], (D, E))
    w_in = jax.random.normal(ks[3], (E, R, F)) * 0.2
    w_out = jax.random.normal(ks[4], (E, F, R)) * 0.2
    y, stats = expert.moe_ffn(x, router, w_in, w_out, capacity_factor=None,
                              act=expert.relu2, top_k=2, scoring="sigmoid",
                              rows=rows)
    assert y.shape == (T, R)
    s = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(s, 2)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    want = sum(w[:, j, None] * jnp.einsum(
        "tf,tfr->tr", reference.relu2(jnp.einsum(
            "tr,trf->tf", rows, w_in[idx[:, j]])), w_out[idx[:, j]])
        for j in range(2))
    _close(y, want, 1e-5)
    np.testing.assert_array_equal(stats["chosen"], idx)


@pytest.mark.parametrize("k, held", [(6, (2, 2)), (6, (0, 5)), (2, (1, 3))])
def test_held_experts_run_on_the_front_of_the_buffer_alone(k, held):
    """Where count * T < k * T (many choices, few experts held) the experts'
    buffer is cut to count * T rows, a static bound (a token picks an expert
    once): values and every gradient are the dense formula's over the held
    experts, whether the cut is taken (6 choices, 2 held) or not."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    T, D, F, E = 64, 32, 24, 16
    first, count = held
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_in = jax.random.normal(ks[2], (count, D, F)) * 0.2
    w_out = jax.random.normal(ks[3], (count, F, D)) * 0.2
    assert (count * T < k * T) == (count < k)

    def mine(x, w_in, w_out):
        y, stats = expert.moe_ffn(
            x, router, w_in, w_out, capacity_factor=None, act=expert.relu2,
            top_k=k, scoring="sigmoid", scale=5.0, held=held)
        return y, stats

    def theirs(x, w_in, w_out):
        s = jax.nn.sigmoid(x @ router)
        _, idx = jax.lax.top_k(s, k)
        chosen = jnp.any(jax.nn.one_hot(idx, E, dtype=jnp.bool_), axis=-2)
        w = jnp.where(chosen, s, 0.0)
        w = 5.0 * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return sum(w[:, first + e, None] * (
            reference.relu2(x @ w_in[e]) @ w_out[e]) for e in range(count))

    y, stats = mine(x, w_in, w_out)
    _close(y, theirs(x, w_in, w_out), 1e-5)
    assert int(stats["held"]) <= count * T
    probe = jax.random.normal(jax.random.PRNGKey(5), (T, D))
    got = jax.grad(lambda *a: jnp.sum(mine(*a)[0] * probe),
                   argnums=(0, 1, 2))(x, w_in, w_out)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * probe),
                    argnums=(0, 1, 2))(x, w_in, w_out)
    for name, g, r in zip(("x", "w_in", "w_out"), got, want):
        _close(g, r, 2e-5, "d" + name)


@pytest.mark.parametrize("gated", [True, False])
def test_the_shared_expert_with_and_without_a_gate(gated):
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    layer = expert.MoeMlp(num_experts=4, mlp_dim=8, capacity_factor=None,
                          dtype=jnp.float32, shared_dim=24, act="relu2",
                          shared_gated=gated)
    p = layer.init(jax.random.PRNGKey(1), u)["params"]
    assert ("shared_gate" in p) == gated
    routed = expert.MoeMlp(num_experts=4, mlp_dim=8, capacity_factor=None,
                           dtype=jnp.float32, act="relu2").apply(
        {"params": {k: p[k] for k in ("router", "w_in", "w_out")}}, u)
    h = u @ p["shared_up"]["kernel"]
    h = reference.relu2(u @ p["shared_gate"]["kernel"]) * h if gated \
        else reference.relu2(h)
    _close(layer.apply({"params": p}, u),
           routed + h @ p["shared_down"]["kernel"], 1e-5)


# --------------------------------------------------------------------------
# What is refused, and what is named
# --------------------------------------------------------------------------

@pytest.mark.parametrize("over, match", [
    (dict(tp_axis="tp", moe_experts=None,
          layer_types=("ssm", "attn", "ssm", "attn", "ssm")),
     "tp_axis cannot be combined with .*layer_types"),
    (dict(sp_axis="sp"), "sp_axis cannot be combined with .*layer_types"),
    (dict(num_passes=2, moe_experts=None,
          layer_types=("ssm", "attn", "ssm", "attn", "ssm")),
     "num_passes cannot be combined with .*layer_types"),
    (dict(hc_mult=4), "layer_types cannot be combined with hc_mult"),
    (dict(mtp_depth=1), "layer_types cannot be combined with mtp_depth"),
    (dict(ep_axis="ep", moe_held=None, moe_capacity_factor=1.25),
     "layer_types cannot be combined with ep_axis"),
    (dict(sandwich_norm=True),
     "layer_types cannot be combined with sandwich_norm"),
    (dict(first_k_dense=1),
     "layer_types cannot be combined with first_k_dense"),
    (dict(num_layers=4), "num_layers=4 kinds"),
    (dict(layer_types=("ssm", "moe", "attn", "moe", "conv")),
     "each of ssm, attn, moe, mlp"),
    (dict(moe_experts=None, moe_held=None), "names a 'moe' layer"),
    (dict(ssm_heads=None), "names an 'ssm' layer"),
    (dict(ssm_groups=3), "ssm_groups=3 must divide ssm_heads=8"),
    (dict(moe_act="gelu"), "moe_act='gelu'"),
    (dict(rotary=False, kv_lora_rank=16, q_lora_rank=24, layer_types=None,
          num_kv_heads=None, head_dim=None),
     "rotary=False cannot be combined with kv_lora_rank"),
])
def test_what_the_pattern_cannot_be_placed_beside_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**over)


@pytest.mark.parametrize("field, over", [
    ("rotary=False", dict(rotary=False)),
    ("moe_latent_dim", dict(moe_latent_dim=32)),
    ("moe_act", dict(moe_act="relu2")),
    ("moe_shared_gated=False", dict(moe_shared_gated=False)),
])
def test_tp_axis_refuses_each_new_field_by_its_name(field, over):
    with pytest.raises(ValueError, match="tp_axis cannot be combined with "
                       + field.replace("=", "=")):
        models.TransformerConfig(tp_axis="tp", **over)


def test_a_dense_feed_forward_is_a_kind_too():
    cfg = _cfg(layer_types=("ssm", "mlp", "attn", "mlp", "ssm"),
               moe_experts=None, moe_held=None)
    model = models.Transformer(cfg)
    tokens = jnp.zeros((1, LENGTH), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert sorted(params["block_1"]) == ["mlp_in", "mlp_out", "norm"]
    assert sorted(params["block_0"]) == ["norm", "ssm"]
    assert sorted(params["block_2"]) == ["attn", "norm"]
    assert model.apply({"params": params}, tokens).shape == (1, LENGTH,
                                                             VOCAB)


def test_the_program_names_the_new_parts():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _system_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    for scope in profile.SSM_SCOPES + (profile.MOE_LATENT,):
        assert scope in text, scope
    # the scan and the convolution lie inside the mixer, the mixer inside a
    # block; the latent projections inside the routed layer
    assert "%s/block_0/%s/ssm/%s" % (profile.BLOCK, profile.SSM,
                                     profile.SSD) in text
    assert "%s/ssm/%s" % (profile.SSM, profile.SSM_CONV) in text
    assert "%s/%s" % (profile.MOE, profile.MOE_LATENT) in text
    assert profile.MOE_LATENT in profile.MOE_SCOPES


def test_without_a_pattern_the_new_fields_change_no_parameter():
    old = models.TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, max_seq_len=LENGTH, attention="dense",
        moe_experts=4, moe_every=1, moe_capacity_factor=None,
        moe_shared_dim=32, moe_scoring="sigmoid", dtype=jnp.float32)
    tokens = jnp.zeros((1, LENGTH), jnp.int32)
    params = models.Transformer(old).init(jax.random.PRNGKey(0),
                                          tokens)["params"]
    assert sorted(params["block_0"]) == ["attn", "moe_mlp", "norm1", "norm2"]
    assert sorted(params["block_0"]["moe_mlp"]) == [
        "router", "select_bias", "shared_down", "shared_gate", "shared_up",
        "w_in", "w_out"]
