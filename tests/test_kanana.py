"""Kanana-2-30B-A3B's stack at toy sizes on the CPU: latent attention with
the queries straight from the state (`q_lora_rank=None`), the flash kernels'
scores of two products in the forms a long sequence takes (the one-kernel
backward held by the q block with the shared key's gradient summed over the
heads in VMEM; dQ beside a gridded dK/dV; every kernel gridded), in Pallas'
interpreter, the plan that chooses among them, a routed layer's eight shares,
and a train step, against `benchmark/references/kanana.py`.
"""

import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.references import kanana as reference  # noqa: E402
from horovod_tpu import models, parallel, profile  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops.losses import chunked_softmax_cross_entropy  # noqa: E402
from horovod_tpu.parallel import expert  # noqa: E402

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

jax.config.update("jax_default_matmul_precision", "highest")

VOCAB, HIDDEN, HEADS, LENGTH = 256, 64, 2, 64
EXPERTS, HELD, TOP_K, SCALE = 16, (4, 4), 3, 2.448


def _cfg(**over):
    base = dict(
        vocab_size=VOCAB, num_layers=3, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, mlp_gated=True, max_seq_len=LENGTH, attention="dense",
        rope_base=1e6, kv_lora_rank=16, q_lora_rank=None, qk_nope_dim=32,
        qk_rope_dim=16, v_head_dim=32, moe_experts=EXPERTS, moe_every=1,
        first_k_dense=1, moe_dim=32, moe_top_k=TOP_K,
        moe_capacity_factor=None, moe_gated=True, moe_renormalize=True,
        moe_scoring="sigmoid", moe_route_scale=SCALE, moe_shared_dim=64,
        moe_held=HELD, dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"num_layers": cfg.num_layers, "first_k_dense": cfg.first_k_dense,
            "eps": cfg.norm_eps, "nope": cfg.qk_nope_dim,
            "rope": cfg.qk_rope_dim, "rope_theta": cfg.rope_base,
            "top_k": cfg.moe_top_k, "norm_topk_prob": cfg.moe_renormalize,
            "route_scale": cfg.moe_route_scale, "held": held}


def _seeded(cfg, seed=0):
    """(model, parameters with every vector moved off its initial value:
    norm scales and the selection bias, tokens [1, LENGTH])."""
    model = models.Transformer(cfg)
    k_p, k_t, k_n = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (1, LENGTH), 0, VOCAB, jnp.int32)
    params = model.init(k_p, tokens)["params"]
    flat, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(k_n, len(flat))
    return model, jax.tree_util.tree_unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for k, x in zip(keys, flat)]), tokens


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


# --------------------------------------------------------------------------
# (a) Latent attention with direct queries
# --------------------------------------------------------------------------

@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_latent_attention_with_direct_queries_agrees_with_the_reference(
        attention):
    cfg = _cfg(attention=attention)
    module = transformer.LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    p = module.init(jax.random.PRNGKey(1), x, pos)["params"]
    p["kv_norm"]["scale"] = p["kv_norm"]["scale"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), p["kv_norm"]["scale"].shape)
    # the queries come from ONE matrix: no q_a, no q_norm, no q_b
    assert sorted(p) == ["kv_a", "kv_b", "kv_norm", "out", "q"]
    assert p["q"]["kernel"].shape == (HIDDEN, HEADS, 32 + 16)
    arch = _arch(cfg)
    g = jax.random.normal(jax.random.PRNGKey(3), (LENGTH, HIDDEN))

    def system(p, x):
        return module.apply({"params": p}, x, pos)[0]

    _close(system(p, x), reference.latent_attention(x[0], p, arch), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(system(*a) * g), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference.latent_attention(
        x[0], p, arch) * g), argnums=(0, 1))(p, x)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, a), b in zip(flat_got, jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


def test_a_query_rank_keeps_the_low_rank_queries():
    """With `q_lora_rank` set the parameters are what they were: W_qa, its
    norm and W_qb, and no `q`."""
    module = transformer.LatentAttention(_cfg(q_lora_rank=24))
    x = jnp.zeros((1, LENGTH, HIDDEN))
    p = module.init(jax.random.PRNGKey(0), x, jnp.arange(LENGTH)[None])[
        "params"]
    assert sorted(p) == ["kv_a", "kv_b", "kv_norm", "out", "q_a", "q_b",
                         "q_norm"]


# --------------------------------------------------------------------------
# (b) The two-product kernels in the forms a long sequence takes
# --------------------------------------------------------------------------

def _dense_two_products(q, k, v, q2, k2, scale, drop=None):
    """The dense einsum form; `drop` leaves the second product out of the
    scores ("scores"), or its gradient out of q2 ("dq2") or k2 ("dk2"): what
    a kernel that forgot it would compute."""
    H, G = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
    if drop == "dq2":
        q2 = jax.lax.stop_gradient(q2)
    if drop == "dk2":
        k2 = jax.lax.stop_gradient(k2)
    second = 0.0 if drop == "scores" else jnp.einsum(
        "bhqd,bxkd->bhqk", q2, k2)
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k) + second) * scale
    L = q.shape[2]
    s = jnp.where(jnp.arange(L)[:, None] >= jnp.arange(L)[None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _two_product_case(B=2, H=4, G=None, L=384, D=128, D2=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    G = G or H
    return (jax.random.normal(ks[0], (B, H, L, D)),
            jax.random.normal(ks[1], (B, G, L, D)),
            jax.random.normal(ks[2], (B, G, L, D)),
            jax.random.normal(ks[3], (B, H, L, D2)),
            jax.random.normal(ks[4], (B, 1, L, D2)),
            jax.random.normal(ks[5], (B, H, L, D)))


def _forced(form, B, H, L, D, group, dtype, D2, blocks=(None, None)):
    """(the budget, `_BWD_HELD`) that make `flash_plan` choose `form` for a
    call that the k-held one kernel would hold."""
    plan = lambda budget: fa.flash_plan(  # noqa: E731
        B, H, L, D, group, dtype, True, *blocks, vmem_budget=budget,
        shared_dim=D2)
    k_held = plan(fa.RESIDENT_VMEM_BUDGET)[profile.FLASH_BWD]
    assert k_held.held == "k"
    if form == "q_held":  # one byte under the k-held form's own sum
        return k_held.resident_bytes - 1, fa._BWD_HELD
    if form == "dq_and_gridded_dkv":
        saved, fa._BWD_HELD = fa._BWD_HELD, ()
        try:
            two = plan(fa.RESIDENT_VMEM_BUDGET)
        finally:
            fa._BWD_HELD = saved
        return two[profile.FLASH_DKV].resident_bytes - 1, ()
    return 0, ()  # every kernel gridded


# the form, the kernels it names and their paths; heads > 1 so that dk2 sums
# over the heads, a head group, and a length (384) that is no multiple of
# the blocks the tables prefer (512, 1024): the plan takes 128
LONG_FORMS = {
    "q_held": {profile.FLASH_BWD: ("resident", "q")},
    "dq_and_gridded_dkv": {profile.FLASH_DQ: ("resident", "q"),
                           profile.FLASH_DKV: ("gridded", "k")},
    "gridded": {profile.FLASH_DQ: ("gridded", "q"),
                profile.FLASH_DKV: ("gridded", "k")}}


@pytest.mark.parametrize("shape", ["heads_4_L384", "grouped_L256",
                                   "blocks_128_256"])
@pytest.mark.parametrize("form", list(LONG_FORMS))
def test_two_product_kernels_past_the_k_held_form(form, shape, monkeypatch):
    how = {"heads_4_L384": dict(), "grouped_L256": dict(B=1, G=2, L=256),
           "blocks_128_256": dict(L=256)}[shape]
    blocks = (128, 256) if shape == "blocks_128_256" else (None, None)
    q, k, v, q2, k2, g = _two_product_case(**how)
    B, H, L, D = q.shape
    group, scale = H // k.shape[1], 0.11
    want, vjp = jax.vjp(lambda *a: _dense_two_products(*a, scale),
                        q, k, v, q2, k2)
    budget, held = _forced(form, B, H, L, D, group, q.dtype, 64, blocks)
    monkeypatch.setattr(fa, "_BWD_HELD", held)
    plans = fa.flash_plan(B, H, L, D, group, q.dtype, True, *blocks,
                          vmem_budget=budget, shared_dim=64)
    assert {n: (p.path, p.held) for n, p in plans.items()} == LONG_FORMS[form]
    fwd = fa.flash_plan(B, H, L, D, group, q.dtype, False, *blocks,
                        vmem_budget=budget, shared_dim=64)
    assert fwd[profile.FLASH_FWD].path == (
        "gridded" if form == "gridded" else "resident")
    out, lse = fa._pallas_forward_lse(q, k, v, scale, True, True, *blocks,
                                      vmem_budget=budget, shared=(q2, k2))
    _close(out, want, 5e-6)
    grads = fa._pallas_backward(q, k, v, out, lse, g, scale, True, True,
                                *blocks, vmem_budget=budget, shared=(q2, k2))
    # dQ, dK, dV, then the second product's: dQ2 a head, dK2 ONE key's, the
    # sum over the heads
    for got, want_g in zip(grads, vjp(g)):
        _close(got, want_g, 2e-5)
    assert grads[4].shape == k2.shape


@pytest.mark.parametrize("dropped", ["scores", "dq2", "dk2"])
def test_the_comparison_sees_a_dropped_second_product(dropped):
    """What the comparison above must refuse: the dense form without the
    second product in the scores, or without its gradient to q2 or to k2, is
    far from what the q-held kernel returns, in the output or in the one
    gradient the fault touches."""
    q, k, v, q2, k2, g = _two_product_case(B=1, L=256)
    B, H, L, D = q.shape
    scale = 0.11
    budget, _ = _forced("q_held", B, H, L, D, 1, q.dtype, 64)
    out, lse = fa._pallas_forward_lse(q, k, v, scale, True, True,
                                      vmem_budget=budget, shared=(q2, k2))
    grads = fa._pallas_backward(q, k, v, out, lse, g, scale, True, True,
                                vmem_budget=budget, shared=(q2, k2))
    wrong, vjp = jax.vjp(lambda *a: _dense_two_products(
        *a, scale, drop=dropped), q, k, v, q2, k2)
    wrong_grads = vjp(g)
    far = lambda a, b: float(jnp.max(jnp.abs(a - b))) > 1e-2  # noqa: E731
    if dropped == "scores":
        assert far(out, wrong)
    else:
        i = {"dq2": 3, "dk2": 4}[dropped]
        assert far(grads[i], wrong_grads[i])
        assert not far(grads[7 - i], wrong_grads[7 - i])


def _with_budget(plan, budget):
    """`flash_plan` with `budget` where the caller gives none: the kernels'
    callers pass `RESIDENT_VMEM_BUDGET` as it was at import."""
    def planned(*args, **kw):
        args = list(args)
        if len(args) > 9:
            args[9] = budget
        else:
            kw["vmem_budget"] = budget
        return plan(*args, **kw)
    return planned


def test_the_model_runs_the_q_held_kernels(monkeypatch):
    """`flash_attention` on a call the k-held kernel cannot hold, through the
    custom VJP, in the interpreter: what `LatentAttention` hands the kernels
    at 8192 positions, at a toy length."""
    q, k, v, q2, k2, g = _two_product_case(B=1, H=2, L=256)
    budget, _ = _forced("q_held", 1, 2, 256, 128, 1, q.dtype, 64)
    monkeypatch.setattr(fa, "flash_plan", _with_budget(fa.flash_plan, budget))
    assert fa.flash_plan(1, 2, 256, 128, 1, q.dtype, True, shared_dim=64)[
        profile.FLASH_BWD].held == "q"
    want = jax.grad(lambda *a: jnp.sum(_dense_two_products(*a, 0.1) * g),
                    argnums=(0, 1, 2, 3, 4))(q, k, v, q2, k2)
    got = jax.grad(lambda *a: jnp.sum(fa._flash_shared(
        *a, 0.1, True, True) * g), argnums=(0, 1, 2, 3, 4))(q, k, v, q2, k2)
    for a, b in zip(got, want):
        _close(a, b, 2e-5)


# --------------------------------------------------------------------------
# (c) The plan
# --------------------------------------------------------------------------

_MiB = 2 ** 20
# L -> {kernel: (path, held, block_q, block_k, grid, resident_bytes,
# vmem_bytes, vmem_limit_bytes)} of 1 x 32 heads, D=128, D2=64, bf16
SHARED_PLANS = {
    4096: {  # as PR 34 left it: held by the k block
        "hvd_flash_fwd": ("resident", "q", 512, 512, (32, 8), 6 * _MiB,
                          7602176, 19 * _MiB),
        "hvd_flash_bwd": ("resident", "k", 512, 1024, (32, 4), 22 * _MiB,
                          25 * _MiB, 50 * _MiB)},
    8192: {  # the one kernel held by the q block, one buffer an operand
        "hvd_flash_fwd": ("resident", "q", 512, 512, (32, 16), 12 * _MiB,
                          13893632, 26 * _MiB),
        "hvd_flash_bwd": ("resident", "q", 512, 1024, (32, 16), 24 * _MiB,
                          27525120, 52 * _MiB)},
    16384: {  # dQ on k + v + k2, dK/dV a tile a step
        "hvd_flash_fwd": ("resident", "q", 512, 512, (32, 32), 24 * _MiB,
                          26476544, 41 * _MiB),
        "hvd_flash_dq": ("resident", "q", 512, 512, (32, 32), 24 * _MiB,
                         27525120, 43 * _MiB),
        "hvd_flash_dkv": ("gridded", "k", 512, 1024, (32, 16, 32), 0,
                          6553600, None)}}


@pytest.mark.parametrize("L", sorted(SHARED_PLANS))
def test_flash_plan_of_two_products_by_length(L):
    plans = {name: p for backward in (False, True)
             for name, p in profile.flash_plan(
                 1, 32, L, 128, backward=backward, shared_dim=64).items()}
    assert {name: (p.path, p.held, p.block_q, p.block_k, p.grid,
                   p.resident_bytes, p.vmem_bytes, p.vmem_limit_bytes)
            for name, p in plans.items()} == SHARED_PLANS[L]


def test_no_plan_of_two_products_is_empty():
    """Every form is a kernel: past 16384 positions all three are gridded,
    and a budget of nothing leaves them so at any length."""
    far = profile.flash_plan(1, 32, 32768, 128, shared_dim=64)
    assert far[profile.FLASH_FWD].path == "gridded"
    back = profile.flash_plan(1, 32, 32768, 128, backward=True, shared_dim=64)
    assert {n: p.path for n, p in back.items()} == {
        profile.FLASH_DQ: "gridded", profile.FLASH_DKV: "gridded"}
    assert fa.flash_plan(1, 4, 256, 128, backward=True, vmem_budget=0,
                         shared_dim=64)
    # the q-held sums: k, v, k2, dk, dv, dk2 once each in bf16 (D2 pads to
    # 128 lanes) and three f32 accumulators: 3 KiB a position
    q_held = profile.flash_plan(1, 32, 8192, 128, backward=True,
                                shared_dim=64)[profile.FLASH_BWD]
    assert q_held.resident_bytes == 8192 * (6 * 128 * 2 + 3 * 128 * 4)
    # and without a second product the plan is what it was: both buffers
    plain = profile.flash_plan(1, 32, 8192, 128, backward=True)[
        profile.FLASH_BWD]
    assert plain.held == "q" and \
        plain.resident_bytes == 8192 * (2 * 4 * 128 * 2 + 2 * 128 * 4)


# --------------------------------------------------------------------------
# (d) A routed layer's eight shares
# --------------------------------------------------------------------------

def test_eight_shares_of_a_routed_layer_add_up_to_the_uncut_one():
    """16 of 128 experts each, sigmoid scores, top-6 on score + bias, the
    weights renormalised x 2.448, the shared pair counted ONCE: the sum of
    what eight ranks compute is the reference's uncut layer."""
    experts, top_k, dim, width = 128, 6, 32, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, dim))
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    whole = {
        "router": jax.random.normal(ks[0], (dim, experts)),
        "select_bias": 0.3 * jax.random.normal(ks[1], (experts,)),
        "w_gate": 0.3 * jax.random.normal(ks[2], (experts, dim, width)),
        "w_up": 0.3 * jax.random.normal(ks[3], (experts, dim, width)),
        "w_down": 0.3 * jax.random.normal(ks[4], (experts, width, dim)),
        "shared_gate": {"kernel": 0.3 * jax.random.normal(ks[5], (dim, 32))},
        "shared_up": {"kernel": 0.3 * jax.random.normal(ks[6], (dim, 32))},
        "shared_down": {"kernel": 0.3 * jax.random.normal(ks[7], (32, dim))}}
    arch = {"top_k": top_k, "norm_topk_prob": True, "route_scale": SCALE,
            "held": (0, experts)}
    want, chosen, _ = reference.routed_ffn(x[0], whole, arch)
    shared_alone = reference.gated(
        x[0], *(whole[n]["kernel"] for n in ("shared_gate", "shared_up",
                                             "shared_down")))
    total, rows = jnp.zeros_like(want), 0
    for rank in range(8):
        held = (16 * rank, 16)
        module = expert.MoeMlp(
            num_experts=experts, mlp_dim=width, capacity_factor=None,
            top_k=top_k, gated=True, dtype=jnp.float32, scoring="sigmoid",
            route_scale=SCALE, held=held, shared_dim=32)
        p = dict(whole, **{n: whole[n][held[0]:held[0] + 16]
                           for n in ("w_gate", "w_up", "w_down")})
        y, state = module.apply({"params": p}, x, mutable=["intermediates"])
        total = total + y[0] - shared_alone
        stats = parallel.routing_stats(state["intermediates"])
        rows += float(stats["held_share"][0])
        # the share is the reference's layer as THIS rank holds it
        _close(y[0], reference.routed_ffn(
            x[0], p, dict(arch, held=held))[0], 5e-6)
    _close(total + shared_alone, want, 2e-5)
    assert rows == pytest.approx(1.0)
    assert int(jnp.sum(chosen)) == top_k * LENGTH


# --------------------------------------------------------------------------
# (e) The whole model and one train step
# --------------------------------------------------------------------------

def _loss(model, params, tokens, chunk=16):
    hid = model.apply({"params": params}, tokens, return_hidden=True)
    return chunked_softmax_cross_entropy(
        hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, 1),
        chunk=chunk)


@pytest.mark.parametrize("case", ["dense", "flash", "block_remat"])
def test_loss_states_and_gradients_agree_with_the_reference(case):
    cfg = _cfg(**{"dense": {}, "flash": dict(attention="flash"),
                  "block_remat": dict(block_remat=2)}[case])
    model, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    hid, state = model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"],
        capture_intermediates=lambda mdl, name: isinstance(
            mdl, transformer.Block) and name == "__call__")
    inter = state["intermediates"]
    ref = reference.forward(params, tokens[0], arch)
    for i in range(cfg.num_layers):
        _close(inter["block_%d" % i]["__call__"][0][0], ref["states"][i],
               1e-5)
    stats = parallel.routing_stats(inter)
    assert jnp.array_equal(jnp.any(jax.nn.one_hot(
        stats["chosen"], EXPERTS, dtype=bool), axis=-2), ref["chosen"])
    assert ref["chosen"].shape[0] == 2 and float(jnp.max(ref["margin"])) == 0
    assert float(_loss(model, params, tokens)) == pytest.approx(
        float(ref["loss"]), rel=1e-5)
    got = jax.grad(lambda p: _loss(model, p, tokens))(params)
    want = reference.gradient(params, tokens[0], arch)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


def test_the_reference_follows_a_systems_sets():
    """`follow=`: the reference computes with the sets it is handed, says
    where its own differ and how near a tie the choice was."""
    cfg = _cfg()
    _, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    own = reference.forward(params, tokens[0], arch)
    other = jnp.roll(own["chosen"], 1, axis=-1)  # another set a position
    ref = reference.forward(params, tokens[0], arch, follow=other)
    assert jnp.array_equal(ref["chosen"][0], own["chosen"][0])
    assert float(jnp.max(ref["margin"])) > 0.0
    assert float(jnp.max(jnp.abs(ref["states"][1] - own["states"][1]))) > 1e-3
    assert jnp.array_equal(ref["states"][0], own["states"][0])  # dense
    # and without the shared pair it is another model
    bare = reference.forward(params, tokens[0], arch, shared=0.0)
    assert float(jnp.max(jnp.abs(bare["states"][1] - own["states"][1]))) > \
        1e-2


def test_one_train_step_agrees_with_the_reference():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    mesh = parallel.data_parallel_mesh(devices=jax.devices()[:1])
    opt = optax.sgd(0.1)
    step = parallel.make_train_step(
        lambda p, batch: _loss(model, p, batch["x"]), opt, mesh)
    # the reference first: the step donates what it is handed
    ref_loss = float(reference.forward(params, tokens[0], arch)["loss"])
    grads = reference.gradient(params, tokens[0], arch)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    placed = step.place(params, opt.init(params), {"x": tokens})
    new, _, loss = step(*placed)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * max(
            1.0, float(jnp.max(jnp.abs(b)))), jax.tree_util.keystr(path)
