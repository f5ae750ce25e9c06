"""The dropless routed feed-forward (sort, grouped matmul, unsort) and the
OLMoE block (QK-norm, top-8 of 16 gated experts here) against the plain
float32 reference the benchmark holds the chip to
(`benchmark/references/olmoe.py`, imported as the builders import it), at
small sizes on the CPU: forward, loss and GRADIENTS in float32; bf16 with
flipped routings counted and bounded; an overloaded and an empty expert;
group sizes; one `make_train_step` step on two devices."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import olmoe as reference
from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
from horovod_tpu.parallel import (make_train_step, moe_ffn,
                                  router_aux_losses, routing_stats)

jax.config.update("jax_default_matmul_precision", "highest")

LAYERS, EXPERTS, TOP_K, VOCAB, LENGTH = 2, 16, 8, 97, 48
W_BALANCE, W_Z = 0.01, 0.001
BASE = TransformerConfig(
    vocab_size=VOCAB, num_layers=LAYERS, num_heads=2, embed_dim=32,
    mlp_dim=16, max_seq_len=64, attention="dense", qk_norm=True,
    norm_eps=1e-5, moe_experts=EXPERTS, moe_every=1, moe_top_k=TOP_K,
    moe_capacity_factor=None, moe_gated=True, moe_renormalize=False,
    dtype=jnp.float32)


def _setup(cfg, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 100), (1, LENGTH),
                                0, VOCAB, jnp.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(seed),
                                   tokens)["params"]
    # Flax starts norm scales at one; the reference must be held to scales
    # that matter, the q and k norms' too.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves), tokens


def system(cfg, params, tokens):
    """(hidden [L, D] f32, loss with its auxiliary terms, routing
    statistics), as `benchmark/builders/olmoe.py` forms them."""
    hid, state = Transformer(cfg).apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"])
    ce = chunked_softmax_cross_entropy(
        hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
        chunk=LENGTH)
    balance, z = router_aux_losses(state["intermediates"])
    return (hid[0].astype(jnp.float32), ce + W_BALANCE * balance + W_Z * z,
            routing_stats(state["intermediates"]))


def plain(cfg, params, tokens):
    return reference.hidden_and_loss(
        params, tokens[0], LAYERS, cfg.rope_base, eps=cfg.norm_eps,
        top_k=TOP_K, renormalize=cfg.moe_renormalize, qk_norm=cfg.qk_norm,
        balance_weight=W_BALANCE, z_weight=W_Z)


def _flipped(stats, parts):
    """[L] bool: tokens whose chosen set differs in any layer."""
    chosen = jnp.any(jax.nn.one_hot(stats["chosen"], EXPERTS,
                                    dtype=jnp.bool_), axis=-2)
    return np.asarray(jnp.any(chosen != parts["chosen"], axis=(0, 2)))


@pytest.mark.parametrize("renormalize", [False, True])
@pytest.mark.parametrize("qk_norm", [True, False])
def test_float32_forward_loss_and_gradients_match_the_reference(
        renormalize, qk_norm):
    """Tolerance 1e-5: both sides compute in float32 at `highest` precision
    and differ only in the order of their sums (sorted rows against dense
    masked experts), a few float32 roundings (6e-8 each) on values of order
    one; a wrong gate, a dropped assignment or a missing normalisation is
    1e-2 and more."""
    cfg = dataclasses.replace(BASE, moe_renormalize=renormalize,
                              qk_norm=qk_norm)
    params, tokens = _setup(cfg)
    hid, loss, stats = system(cfg, params, tokens)
    ref_hid, ref_loss, parts = plain(cfg, params, tokens)
    assert not _flipped(stats, parts).any()
    np.testing.assert_allclose(hid, ref_hid, atol=1e-5, rtol=0)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert float(parts["load_balance"]) > 1.0 and float(parts["router_z"]) > 0
    grads = jax.grad(lambda p: system(cfg, p, tokens)[1])(params)
    ref_grads = jax.grad(lambda p: plain(cfg, p, tokens)[1])(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat)
    for (path, g), r in zip(flat, ref_flat):
        assert float(jnp.max(jnp.abs(r))) > 0, path  # every leaf is trained
        np.testing.assert_allclose(
            g, r, atol=1e-5 * float(jnp.max(jnp.abs(r))) + 1e-8, rtol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def test_group_sizes_sum_to_k_times_tokens_and_nothing_is_dropped():
    for seed in range(3):
        params, tokens = _setup(BASE, seed)
        _, _, stats = system(BASE, params, tokens)
        assert stats["assignments"].shape == (LAYERS, EXPERTS)
        np.testing.assert_array_equal(stats["assignments"].sum(axis=1),
                                      TOP_K * LENGTH)
        assert stats["chosen"].shape == (LAYERS, LENGTH, TOP_K)
        assert int(stats["dropped"]) == 0


# bf16 against the float32 reference. A token whose top-8 set differs from
# the reference's in some layer (a near tie decided the other way by bf16
# inputs) is FLIPPED: counted, bounded, and left out of the hidden-state
# comparison. The tokens routed alike are held to TOL_BF16: the residual
# stream is rounded to bf16 (2^-8 relative) after each of the four
# sublayers and the comparison is relative to the largest reference value,
# so a handful of roundings stay under 1.5e-2 (seen: 6e-3 to 9e-3). Experts
# computed in fp8 (e4m3: 2^-4 relative) miss it, which
# `test_bfloat16_tolerance_fails_float8_experts` shows.
TOL_BF16 = 1.5e-2
TOL_BF16_LOSS = 2e-3  # the mean loss: holds its assembly, not the precision
TOL_FLIPPED = 0.15  # of LENGTH = 48 tokens, over two layers


def _bf16_errors(params_for_system, params, tokens):
    cfg = dataclasses.replace(BASE, dtype=jnp.bfloat16)
    hid, loss, stats = system(cfg, params_for_system, tokens)
    ref_hid, ref_loss, parts = plain(cfg, params, tokens)
    flipped = _flipped(stats, parts)
    err = np.max(np.abs(np.asarray(hid) - np.asarray(ref_hid)), axis=-1) \
        / float(jnp.max(jnp.abs(ref_hid)))
    return flipped, err, abs(float(loss) - float(ref_loss)) / float(ref_loss)


def test_bfloat16_agrees_where_the_routing_agrees_and_flips_are_rare():
    shares = []
    for seed in range(3):
        params, tokens = _setup(BASE, seed)
        flipped, err, loss_err = _bf16_errors(params, params, tokens)
        shares.append(flipped.mean())
        assert flipped.mean() <= TOL_FLIPPED, (seed, flipped.mean())
        assert err[~flipped].max() <= TOL_BF16, (seed, err[~flipped].max())
        assert loss_err <= TOL_BF16_LOSS, (seed, loss_err)
    # the rule is exercised: with 16 experts near ties do happen
    assert max(shares) > 0


def test_bfloat16_limits_fail_float8_precision():
    """The same comparison with every matrix rounded to fp8's precision
    (e4m3: four significant bits) before the bf16 system uses it, as the
    chip's reading in PERF.md was made: it comes out as NOT correct, by the
    share of flipped routings or by the tokens routed alike, so the limits
    would catch a path computing in a lower precision than the
    configuration states."""
    def round4(x):
        m, e = jnp.frexp(x)
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)

    for seed in range(3):
        params, tokens = _setup(BASE, seed)
        lowered = jax.tree_util.tree_map(
            lambda x: round4(x) if x.ndim >= 2 else x, params)
        flipped, err, _ = _bf16_errors(lowered, params, tokens)
        assert (flipped.mean() > TOL_FLIPPED
                or err[~flipped].max() > TOL_BF16), (seed, flipped.mean())


@pytest.fixture(params=["jnp", "kernels"])
def layer_path(request, monkeypatch):
    """How the dropless layer runs: by jnp and `lax.ragged_dot`, as the CPU
    does, or by its kernels in Pallas' interpreter (the rows' two and the
    grouped matmuls' three), on tiles small enough that a test's buffer
    crosses them. Returns (tokens, width): the rows' kernels take a width
    that is a multiple of 128 and whole sublane tiles of tokens."""
    if request.param == "jnp":
        return 40, 16
    import functools

    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import moe_rows as mr
    from horovod_tpu.parallel import expert

    monkeypatch.setattr(gm, "BLOCK_ROWS", 32)
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)
    monkeypatch.setattr(mr, "TILE_ROWS", 48)
    monkeypatch.setattr(expert, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=True))
    for name in ("dispatch", "combine"):
        monkeypatch.setattr(mr, name, functools.partial(
            getattr(mr, name), interpret=True))
    return 64, 128


def test_overloaded_and_empty_expert_lose_nothing(layer_path):
    """A routing so uneven that expert 0 is chosen by every token and
    expert 5 by none: the group sizes still sum to k*T, the empty group is
    legal, and output and gradients equal the dense masked computation."""
    rng = np.random.RandomState(3)
    (T, D), F, E, k = layer_path, 12, 8, 3
    x = rng.randn(T, D).astype(np.float32)
    x[:, 0] = 1.0 + 0.1 * rng.rand(T)
    router = rng.randn(D, E).astype(np.float32) * 0.1
    router[0, 0], router[0, 5] = 20.0, -20.0
    args = [jnp.asarray(a) for a in (
        x, router, rng.randn(E, D, F).astype(np.float32) * 0.3,
        rng.randn(E, D, F).astype(np.float32) * 0.3,
        rng.randn(E, F, D).astype(np.float32) * 0.3)]

    def routed(x, router, w_gate, w_up, w_down):
        return moe_ffn(x, router, w_up, w_down, capacity_factor=None,
                       top_k=k, w_gate=w_gate, renormalize=False)

    def dense(x, router, w_gate, w_up, w_down):
        y, _, _, _ = reference.routed_ffn(
            x, {"router": router, "w_gate": w_gate, "w_up": w_up,
                "w_down": w_down}, k)
        return y

    y, stats = routed(*args)
    sizes = np.asarray(stats["assignments"])
    assert sizes.sum() == k * T and sizes[0] == T and sizes[5] == 0
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(y, dense(*args), atol=1e-5, rtol=1e-5)
    ct = jnp.asarray(rng.randn(T, D).astype(np.float32))
    got = jax.grad(lambda *a: jnp.sum(routed(*a)[0] * ct),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):  # float32 sums in another order
        np.testing.assert_allclose(
            g, w, atol=2e-5 + 1e-6 * float(jnp.max(jnp.abs(w))), rtol=1e-4)
    # the empty expert's matrices get exactly zero
    assert float(jnp.max(jnp.abs(got[2][5]))) == 0.0


def test_make_train_step_on_two_devices():
    """One data-parallel step of the OLMoE-shaped model on a 2-device CPU
    mesh (a sequence a device): the loss is the mean of the two sequences'
    single-device losses and every leaf moves."""
    import optax

    from horovod_tpu.parallel import data_parallel_mesh

    cfg = BASE
    params, _ = _setup(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, LENGTH), 0, VOCAB,
                                jnp.int32)
    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:2])

    def loss_fn(p, batch):
        return system(cfg, p, batch["x"])[1]

    opt = optax.adamw(1e-2)
    step = make_train_step(loss_fn, opt, mesh)
    expect = np.mean([float(system(cfg, params, tokens[i:i + 1])[1])
                      for i in range(2)])
    before = jax.tree_util.tree_map(np.asarray, params)
    p, o, b = step.place(params, opt.init(params), {"x": tokens})
    p, o, loss = step(p, o, b)
    np.testing.assert_allclose(float(loss), expect, rtol=1e-5)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - b))), p, before)
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved)), moved
