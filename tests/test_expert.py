"""Expert parallelism (routed MoE feed-forward + ep all_to_all): routing
semantics, capacity and drops, dense equivalence, sharded-vs-unsharded
equality, gradients, and the MoeMlp module (virtual 8-device CPU mesh).
The dropless path and OLMoE are in tests/test_moe_dropless.py, its rows'
kernels in tests/test_moe_rows.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.expert import (MoeMlp, ep_param_specs,
                                         moe_capacity, moe_ffn, route,
                                         router_aux_losses,
                                         sort_assignments)

jax.config.update("jax_default_matmul_precision", "highest")


def test_capacity_routing_queues_and_drops():
    """(Replaces the one-hot `switch_dispatch` test.) 4 tokens, 2 experts,
    capacity 2: tokens 0, 1, 3 choose expert 1 and token 2 expert 0, so
    expert 1's queue is token 0, token 1 and token 3 is DROPPED (its output
    is zero: the residual path passes through untouched)."""
    import flax.linen as nn

    logits = jnp.asarray([[0.0, 2.0],
                          [0.0, 3.0],
                          [4.0, 0.0],
                          [0.0, 1.0]], jnp.float32)
    rng = np.random.RandomState(0)
    w_in = jnp.asarray(rng.randn(2, 2, 3).astype(np.float32))
    w_out = jnp.asarray(rng.randn(2, 3, 2).astype(np.float32))
    # x = the logits themselves, through an identity router
    y, stats = moe_ffn(logits, jnp.eye(2), w_in, w_out, capacity_factor=1.0)
    assert moe_capacity(4, 2, 1.0) == 2
    assert int(stats["dropped"]) == 1
    assert list(np.asarray(stats["assignments"])) == [1, 3]
    np.testing.assert_array_equal(np.asarray(y)[3], 0.0)
    # A kept token carries the softmax gate of its expert.
    probs = np.asarray(jax.nn.softmax(logits, -1))
    expect = probs[0, 1] * np.asarray(
        nn.silu(logits[0] @ w_in[1]) @ w_out[1])
    np.testing.assert_allclose(np.asarray(y)[0], expect, rtol=1e-5)
    assert float(stats["load_balance_loss"]) > 0


def test_moe_ffn_matches_per_token_expert_computation():
    """With capacity >= T (no drops), the einsum dispatch must equal
    computing each token through its argmax expert, scaled by gate."""
    rng = np.random.RandomState(0)
    T, D, F, E = 32, 16, 24, 4
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    router = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.3)
    w_in = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
    w_out = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.2)

    y, aux = moe_ffn(x, router, w_in, w_out,
                     capacity_factor=float(E))  # C = T: nothing dropped
    probs = jax.nn.softmax(x @ router, -1)
    idx = np.asarray(jnp.argmax(probs, -1))
    import flax.linen as nn
    expect = np.zeros((T, D), np.float32)
    for t in range(T):
        e = idx[t]
        h = np.asarray(nn.silu(x[t] @ w_in[e]))
        expect[t] = float(probs[t, e]) * np.asarray(h @ w_out[e])
    np.testing.assert_allclose(np.asarray(y), expect, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_dropless_path_is_the_capacity_path_with_room_for_everything(
        gated):
    """One routing and one sorted order under both: with a slot for every
    assignment the capacity path drops nothing, and the dropless path
    (`capacity_factor=None`: `ops/moe_rows`' dispatch and combine around the
    grouped matmul) gives its output and its gradients by x, the router and
    every matrix."""
    rng = np.random.RandomState(11)
    T, D, F, E, k = 24, 16, 12, 4, 2
    args = [jnp.asarray(a.astype(np.float32)) for a in (
        rng.randn(T, D), rng.randn(D, E) * 0.5, rng.randn(E, D, F) * 0.3,
        rng.randn(E, F, D) * 0.3, rng.randn(E, D, F) * 0.3)]
    ct = jnp.asarray(rng.randn(T, D).astype(np.float32))

    def layer(factor):
        def loss(x, router, w_in, w_out, w_gate):
            y, stats = moe_ffn(x, router, w_in, w_out, capacity_factor=factor,
                               top_k=k, w_gate=w_gate if gated else None)
            return jnp.sum(ct * y), (y, stats)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(*args)

    ((_, (y, stats)), grads) = layer(None)
    ((_, (y_cap, stats_cap)), grads_cap) = layer(float(E))  # C = T
    assert int(stats["dropped"]) == int(stats_cap["dropped"]) == 0
    assert "held" not in stats
    np.testing.assert_array_equal(stats["assignments"],
                                  stats_cap["assignments"])
    np.testing.assert_allclose(y, y_cap, atol=1e-5, rtol=1e-5)
    for g, g_cap in zip(grads[:4 + gated], grads_cap):
        assert float(jnp.max(jnp.abs(g))) > 0
        np.testing.assert_allclose(g, g_cap, atol=2e-5, rtol=1e-4)


def _mesh_dp_ep(dp, ep):
    devs = np.array(jax.devices("cpu")[:dp * ep]).reshape(dp, ep)
    return Mesh(devs, ("dp", "ep"))


def test_ep_sharded_matches_unsharded():
    """(dp=2 x ep=4): tokens sharded over BOTH axes (each rank routes
    its own T/8 tokens), experts sharded over ep — output must equal
    the single-device moe_ffn on each token shard."""
    rng = np.random.RandomState(1)
    T, D, F, E = 64, 16, 24, 8
    x = rng.randn(T, D).astype(np.float32)
    router = rng.randn(D, E).astype(np.float32) * 0.3
    w_in = rng.randn(E, D, F).astype(np.float32) * 0.2
    w_out = rng.randn(E, F, D).astype(np.float32) * 0.2
    cf = float(E)  # no drops, so shard/unshard routing agrees exactly

    mesh = _mesh_dp_ep(2, 4)

    def sharded(x, router, w_in, w_out):
        y, stats = moe_ffn(x, router, w_in, w_out, capacity_factor=cf,
                           ep_axis="ep")
        return y, lax_pmean_all(stats["load_balance_loss"])

    from jax import lax

    def lax_pmean_all(v):
        return lax.pmean(lax.pmean(v, "ep"), "dp")

    mapped = jax.jit(jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(("dp", "ep")), P(), P("ep"), P("ep")),
        out_specs=(P(("dp", "ep")), P()),
        check_vma=False))
    y_sharded, aux_sharded = mapped(x, router, w_in, w_out)

    # Reference: same per-shard computation, serially.
    shards = x.reshape(8, T // 8, D)
    y_ref = np.concatenate([
        np.asarray(moe_ffn(jnp.asarray(s), jnp.asarray(router),
                           jnp.asarray(w_in), jnp.asarray(w_out),
                           capacity_factor=cf)[0])
        for s in shards])
    np.testing.assert_allclose(np.asarray(y_sharded), y_ref,
                               rtol=2e-4, atol=2e-4)


def test_ep_sharded_gradients_match():
    """Expert-weight gradients through the all_to_all path must match
    the unsharded computation (summed over token shards)."""
    rng = np.random.RandomState(2)
    T, D, F, E = 32, 8, 12, 4
    x = rng.randn(T, D).astype(np.float32)
    router = rng.randn(D, E).astype(np.float32) * 0.3
    w_in = rng.randn(E, D, F).astype(np.float32) * 0.2
    w_out = rng.randn(E, F, D).astype(np.float32) * 0.2
    cf = float(E)
    mesh = _mesh_dp_ep(2, 2)

    from jax import lax

    from horovod_tpu.parallel.expert import ep_grad_sync

    def loss_sharded(w_in, w_out, x, router):
        # LOCAL loss — no psum: psum's transpose is psum, so a
        # replicated psum'd loss would scale every grad by the rank
        # count. ep_grad_sync's contract is raw local-loss grads.
        y, _ = moe_ffn(x, router, w_in, w_out, capacity_factor=cf,
                       ep_axis="ep")
        return jnp.sum(y ** 2)

    def grads_fn(w_in, w_out, x, router):
        g_in, g_out = jax.grad(loss_sharded, argnums=(0, 1))(
            w_in, w_out, x, router)
        # Expert-sharded grads carry only THIS rank's token shard:
        # sync over the data axes (the library rule, ep_grad_sync).
        return ep_grad_sync({"w_in": g_in, "w_out": g_out},
                            ep_axis="ep", dp_axis="dp")

    grads_sh = jax.jit(jax.shard_map(
        grads_fn, mesh=mesh,
        in_specs=(P("ep"), P("ep"), P(("dp", "ep")), P()),
        out_specs={"w_in": P("ep"), "w_out": P("ep")},
        check_vma=False))(w_in, w_out, x, router)
    grads_sh = (grads_sh["w_in"], grads_sh["w_out"])

    def loss_ref(w_in, w_out):
        total = 0.0
        for s in x.reshape(4, T // 4, D):
            y, _ = moe_ffn(jnp.asarray(s), jnp.asarray(router), w_in,
                           w_out, capacity_factor=cf)
            total = total + jnp.sum(y ** 2)
        return total

    grads_ref = jax.grad(loss_ref, argnums=(0, 1))(jnp.asarray(w_in),
                                                   jnp.asarray(w_out))
    for a, b in zip(grads_sh, grads_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_moe_mlp_module_and_param_specs():
    """MoeMlp init/apply, aux-loss sowing, and ep_param_specs placing
    only expert weights on the ep axis."""
    model = MoeMlp(num_experts=4, mlp_dim=32, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 16)
                    .astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), x)
    y, state = model.apply(variables, x, mutable=["intermediates"])
    assert y.shape == x.shape
    aux = state["intermediates"]["moe_aux_loss"][0]
    assert float(aux) > 0
    balance, z = router_aux_losses(state["intermediates"])
    assert float(balance) == float(aux) and float(z) > 0
    specs = ep_param_specs(variables["params"], "ep")
    assert specs["w_in"] == P("ep") and specs["w_out"] == P("ep")
    assert specs["router"] == P()
    gated = MoeMlp(num_experts=4, mlp_dim=32, gated=True, dtype=jnp.float32)
    gspecs = ep_param_specs(gated.init(jax.random.PRNGKey(0), x)["params"],
                            "ep")
    assert gspecs == {"router": P(), "w_gate": P("ep"), "w_up": P("ep"),
                      "w_down": P("ep")}


def test_capacity_helper():
    assert moe_capacity(64, 8, 1.0) == 8
    assert moe_capacity(64, 8, 1.25) == 10
    assert moe_capacity(3, 8, 1.0) == 1


def test_moe_transformer_train_step_dp_ep():
    """Full (dp=2 x ep=4) MoE-transformer train step: every other block
    swaps its MLP for the expert-parallel MoeMlp; expert weights
    sharded P('ep'), tokens over (dp, ep); one optimizer step with
    ep_grad_sync'd gradients."""
    import dataclasses

    import optax

    from horovod_tpu.models import Transformer, TransformerConfig

    base = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                             embed_dim=32, mlp_dim=64, moe_experts=4,
                             moe_every=2, moe_capacity_factor=2.0,
                             dtype=jnp.float32)
    cfg = dataclasses.replace(base, ep_axis="ep", ep_size=4)
    model = Transformer(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(7).randint(0, 64, size=(8, 16)))
    # Init with the ep_axis-free twin (identical param structure; the
    # axis name only exists inside shard_map).
    variables = Transformer(base).init(jax.random.PRNGKey(0), tokens[:1])
    params = variables["params"]
    specs = ep_param_specs(params, "ep")
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    from horovod_tpu.parallel.expert import ep_grad_sync

    mesh = _mesh_dp_ep(2, 4)

    def loss_fn(params, tokens):
        # mutable=["intermediates"] surfaces the sown Switch aux loss;
        # without it the load-balancing pressure is silently dropped
        # (the canonical expert-collapse failure).
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["intermediates"])
        tgt = jnp.roll(tokens, -1, axis=1)
        logp = jax.nn.log_softmax(logits)
        xent = -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))
        aux, _ = router_aux_losses(state["intermediates"])
        return xent + 0.01 * aux

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        grads = ep_grad_sync(grads, "ep", dp_axis="dp", average=True)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        from jax import lax
        return params, opt_state, lax.pmean(lax.pmean(loss, "ep"), "dp")

    # SGD state is empty; replicate it.
    opt_specs = jax.tree_util.tree_map(lambda _: P(), opt_state)

    params_p = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, opt_specs, P(("dp", "ep"))),
        out_specs=(specs, opt_specs, P()),
        check_vma=False))
    new_params, _, loss = mapped(params_p, opt_state, tokens)
    assert np.isfinite(float(loss))
    # The MoE expert weights moved.
    moved = np.abs(
        np.asarray(new_params["block_1"]["moe_mlp"]["w_in"]) -
        np.asarray(params["block_1"]["moe_mlp"]["w_in"])).max()
    assert moved > 0


def test_moe_with_ring_attention_sp_ep_mesh():
    """ep and sp compose on one mesh: batch sharded over ep (MoE
    all_to_all dispatch inside each sp group), sequence sharded over
    sp (ring attention inside each ep group) — output still matches
    the full unsharded MoE model (capacity high enough that routing
    grouping is irrelevant)."""
    import dataclasses

    from horovod_tpu.models import Transformer, TransformerConfig

    ep, sp = 2, 2
    base = TransformerConfig(vocab_size=97, num_layers=2, num_heads=4,
                             embed_dim=32, mlp_dim=64, moe_experts=4,
                             moe_every=2, moe_capacity_factor=4.0,
                             dtype=jnp.float32)
    full = Transformer(base)
    rng = np.random.RandomState(13)
    tokens = jnp.asarray(rng.randint(0, 97, (2, 32)))
    params = full.init(jax.random.PRNGKey(17), tokens)["params"]
    expected = full.apply({"params": params}, tokens)

    sharded_cfg = dataclasses.replace(base, attention="ring",
                                      sp_axis="sp", ep_axis="ep",
                                      ep_size=ep)
    local = Transformer(sharded_cfg)
    mesh = Mesh(np.array(jax.devices("cpu")[:ep * sp]).reshape(ep, sp),
                ("ep", "sp"))
    specs = ep_param_specs(params, "ep")
    params_p = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)

    def run(p, tokens):
        L = tokens.shape[1]
        positions = jnp.broadcast_to(
            jax.lax.axis_index("sp") * L +
            jnp.arange(L, dtype=jnp.int32)[None], tokens.shape)
        return local.apply({"params": p}, tokens, positions)

    out = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(specs, P("ep", "sp")),
        out_specs=P("ep", "sp"), check_vma=False))(params_p, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_top2_routing_and_queue_order():
    """(Replaces the one-hot `topk_dispatch` test.) Top-2: both chosen
    experts, gates renormalized to 1, and in the sorted order the second
    choices queue after ALL first choices (GShard ordering)."""
    logits = jnp.asarray([[3.0, 2.0, -5.0],
                          [2.5, 3.5, -5.0]], jnp.float32)
    weights, experts, probs = route(logits, 2, renormalize=True)
    np.testing.assert_array_equal(np.asarray(experts), [[0, 1], [1, 0]])
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    raw, _, _ = route(logits, 2, renormalize=False)
    np.testing.assert_allclose(np.asarray(raw)[0],
                               np.asarray(probs)[0, :2], rtol=1e-6)
    flat, order, inv, sizes = sort_assignments(experts, 3)
    # assignment a = choice * T + token: 0: t0 -> e0, 1: t1 -> e1 (first
    # choices), 2: t0 -> e1, 3: t1 -> e0 (second choices)
    np.testing.assert_array_equal(np.asarray(flat), [0, 1, 1, 0])
    # e0's queue: t0's first choice, then t1's second; e1's: t1's first
    # choice, then t0's second.
    np.testing.assert_array_equal(np.asarray(order), [0, 3, 1, 2])
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inv)],
                                  np.arange(4))
    np.testing.assert_array_equal(np.asarray(sizes), [2, 2, 0])


def test_top2_moe_ffn_matches_per_token():
    """Top-2 with ample capacity == per-token sum of the two chosen
    experts weighted by renormalized gates."""
    import flax.linen as nn

    rng = np.random.RandomState(5)
    T, D, F, E = 16, 8, 12, 4
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    router = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.5)
    w_in = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
    w_out = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.2)

    y, _ = moe_ffn(x, router, w_in, w_out, capacity_factor=2.0 * E,
                   top_k=2)
    probs = np.asarray(jax.nn.softmax(x @ router, -1))
    expect = np.zeros((T, D), np.float32)
    for t in range(T):
        order = np.argsort(-probs[t])
        g = probs[t, order[:2]]
        g = g / g.sum()
        for e, gate in zip(order[:2], g):
            h = np.asarray(nn.silu(x[t] @ w_in[e]))
            expect[t] += gate * np.asarray(h @ w_out[e])
    np.testing.assert_allclose(np.asarray(y), expect, rtol=2e-4,
                               atol=2e-4)


def test_top2_ep_sharded_matches_unsharded():
    """Top-2 routing through the ep all_to_all: sharded == per-shard
    unsharded."""
    rng = np.random.RandomState(6)
    T, D, F, E = 32, 8, 12, 4
    x = rng.randn(T, D).astype(np.float32)
    router = rng.randn(D, E).astype(np.float32) * 0.4
    w_in = rng.randn(E, D, F).astype(np.float32) * 0.2
    w_out = rng.randn(E, F, D).astype(np.float32) * 0.2
    cf = 2.0 * E
    mesh = _mesh_dp_ep(2, 2)

    def sharded(x, router, w_in, w_out):
        y, _ = moe_ffn(x, router, w_in, w_out, capacity_factor=cf,
                       ep_axis="ep", top_k=2)
        return y

    y_sh = jax.jit(jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(("dp", "ep")), P(), P("ep"), P("ep")),
        out_specs=P(("dp", "ep")), check_vma=False))(x, router, w_in,
                                                     w_out)
    y_ref = np.concatenate([
        np.asarray(moe_ffn(jnp.asarray(s), jnp.asarray(router),
                           jnp.asarray(w_in), jnp.asarray(w_out),
                           capacity_factor=cf, top_k=2)[0])
        for s in x.reshape(4, T // 4, D)])
    np.testing.assert_allclose(np.asarray(y_sh), y_ref, rtol=2e-4,
                               atol=2e-4)


def test_moe_with_ulysses_attention_sp_ep_mesh():
    """Same composition as the ring variant but with Ulysses attention:
    TWO different all_to_alls (sequence<->heads over sp, tokens<->
    experts over ep) in one compiled program, matching the unsharded
    model."""
    import dataclasses

    from horovod_tpu.models import Transformer, TransformerConfig

    ep, sp = 2, 2
    base = TransformerConfig(vocab_size=97, num_layers=2, num_heads=4,
                             embed_dim=32, mlp_dim=64, moe_experts=4,
                             moe_every=2, moe_capacity_factor=4.0,
                             dtype=jnp.float32)
    full = Transformer(base)
    rng = np.random.RandomState(21)
    tokens = jnp.asarray(rng.randint(0, 97, (2, 32)))
    params = full.init(jax.random.PRNGKey(23), tokens)["params"]
    expected = full.apply({"params": params}, tokens)

    local = Transformer(dataclasses.replace(
        base, attention="ulysses", sp_axis="sp", ep_axis="ep",
        ep_size=ep))
    mesh = Mesh(np.array(jax.devices("cpu")[:ep * sp]).reshape(ep, sp),
                ("ep", "sp"))
    specs = ep_param_specs(params, "ep")
    params_p = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)

    def run(p, tokens):
        L = tokens.shape[1]
        positions = jnp.broadcast_to(
            jax.lax.axis_index("sp") * L +
            jnp.arange(L, dtype=jnp.int32)[None], tokens.shape)
        return local.apply({"params": p}, tokens, positions)

    out = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(specs, P("ep", "sp")),
        out_specs=P("ep", "sp"), check_vma=False))(params_p, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
