"""The looped stack (`TransformerConfig.num_passes` > 1: one stack of blocks
run several times on shared weights), the sandwich norm, the gated dense
feed-forward, the exit gate, and the expected loss over the exits
(`ops.losses.expected_exit_loss` over the per-row weights of
`chunked_softmax_cross_entropy`): the system against the plain reference
`benchmark/references/ouro.py` at a small size, values and gradients."""

import dataclasses

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")

from benchmark.references import ouro as reference  # noqa: E402
from horovod_tpu import models, parallel, profile  # noqa: E402
from horovod_tpu.models.transformer import Block, _rms_norm  # noqa: E402
from horovod_tpu.ops import losses  # noqa: E402
from horovod_tpu.ops.losses import (  # noqa: E402
    chunked_softmax_cross_entropy, exit_distribution, exit_stats,
    expected_exit_loss)

VOCAB, HIDDEN, HEADS, WIDTH, LENGTH, BETA, BASE = 512, 64, 2, 96, 32, 0.05, 1e6


def _cfg(passes, layers, attention="dense", **over):
    return models.TransformerConfig(
        vocab_size=VOCAB, num_layers=layers, num_heads=HEADS,
        embed_dim=HIDDEN, mlp_dim=WIDTH, max_seq_len=LENGTH,
        attention=attention, rope_base=BASE, num_passes=passes,
        sandwich_norm=True, mlp_gated=True, exit_gate=True,
        dtype=jnp.float32, **over)


def _seeded(cfg, batch=2, seed=0):
    """(model, parameters with every norm scale and the gate moved off
    their initial 1 and 0, tokens [batch, LENGTH])."""
    model = models.Transformer(cfg)
    k_p, k_t, k_n = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_t, (batch, LENGTH), 0, VOCAB, jnp.int32)
    params = model.init(k_p, tokens)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(k_n, len(leaves))
    leaves = [x + 0.3 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for k, x in zip(keys, leaves)]
    return model, jax.tree_util.tree_unflatten(tree, leaves), tokens


def _system_loss(model, params, tokens, chunk=16):
    hidden, gates = model.apply({"params": params}, tokens,
                                return_hidden=True)
    return expected_exit_loss(hidden, gates, params["lm_head"]["kernel"],
                              jnp.roll(tokens, -1, axis=1), beta=BETA,
                              chunk=chunk)


def _reference(cfg, params, seq, passes=None, separate=None):
    return reference.forward(params, seq, cfg.num_layers,
                             passes or cfg.num_passes, BASE,
                             eps=cfg.norm_eps, beta=BETA, separate=separate)


def _reference_loss(cfg, params, tokens, **kw):
    return sum(_reference(cfg, params, seq, **kw)["loss"]
               for seq in tokens) / tokens.shape[0]


CASES = {"T4_N2_dense": (4, 2, "dense"), "T2_N3_dense": (2, 3, "dense"),
         "T4_N2_flash": (4, 2, "flash")}


@pytest.mark.parametrize("case", list(CASES))
def test_every_pass_agrees_with_the_reference(case):
    passes, layers, attention = CASES[case]
    cfg = _cfg(passes, layers, attention)
    model, params, tokens = _seeded(cfg)
    hidden, gates = model.apply({"params": params}, tokens,
                                return_hidden=True)
    assert hidden.shape == (passes, 2, LENGTH, HIDDEN)
    assert gates.shape == (passes, 2, LENGTH) and gates.dtype == jnp.float32
    p, _ = exit_distribution(gates)
    for b in range(2):
        ref = _reference(cfg, params, tokens[b])
        for t in range(passes):
            np.testing.assert_allclose(hidden[t, b], ref["hidden"][t],
                                       rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(gates[:, b], ref["gate_logits"],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(p[:, b], ref["p"], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(float(_system_loss(model, params, tokens)),
                               float(_reference_loss(cfg, params, tokens)),
                               rtol=1e-5)
    # the logits are the last pass's
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens),
        hidden[-1] @ params["lm_head"]["kernel"], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("case", list(CASES))
def test_gradients_of_every_parameter_agree_with_the_reference(case):
    """The layer weights' gradients are sums over the passes, the gate's
    come through the weights of the rows, the head's through all exits."""
    passes, layers, attention = CASES[case]
    cfg = _cfg(passes, layers, attention)
    model, params, tokens = _seeded(cfg)
    got = jax.grad(lambda p: _system_loss(model, p, tokens))(params)
    want = jax.grad(lambda p: _reference_loss(cfg, p, tokens))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 11 * layers + 5
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(w))),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("wrong", ["one_pass", "separate_weights"])
def test_the_comparison_tells_the_mechanism(wrong):
    """A reference that runs the stack once, or gives each pass weights of
    its own, is another model: the comparisons above must fail on it."""
    cfg = _cfg(4, 2)
    model, params, tokens = _seeded(cfg)
    if wrong == "one_pass":
        kw = {"passes": 1}
    else:
        others = [_seeded(cfg, seed=s)[1] for s in (1, 2, 3)]
        kw = {"separate": [params] + others}
    ref = _reference(cfg, params, tokens[0], **kw)
    hidden, gates = model.apply({"params": params}, tokens[:1],
                                return_hidden=True)
    # the first pass is the same model in all three ...
    np.testing.assert_allclose(hidden[0, 0], ref["hidden"][0], rtol=2e-4,
                               atol=2e-5)
    # ... the last pass and the loss are not
    last = np.abs(np.asarray(hidden[-1, 0] - ref["hidden"][-1]))
    assert last.max() > 0.1 * np.abs(ref["hidden"][-1]).max()
    system = float(_system_loss(model, params, tokens[:1]))
    assert abs(system - float(ref["loss"])) > 1e-3 * abs(system)


def _dense_weighted(hidden, kernel, targets, weights):
    logits = (hidden @ kernel).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * nll)


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_weighted_rows_match_the_dense_formula(chunk, dtype, monkeypatch):
    """Value and the three gradients (hidden, head, weights)."""
    monkeypatch.setattr(losses, "LOGITS_BUDGET_BYTES", 24 * 50 * 4)
    dtype, rtol, g_rtol, g_atol = {
        "f32": (jnp.float32, 1e-6, 1e-5, 1e-6),
        "bf16": (jnp.bfloat16, 2e-2, 5e-2, 2e-3)}[dtype]
    B, L, D, V = 2, 64, 16, 50
    rng = np.random.RandomState(chunk)
    hidden = jnp.asarray(rng.randn(B, L, D), dtype)
    kernel = jnp.asarray(rng.randn(D, V) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, V, (B, L)))
    weights = jnp.asarray(rng.rand(B, L) / (B * L), jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda h, k, w: chunked_softmax_cross_entropy(
            h, k, targets, chunk=chunk, weights=w),
        argnums=(0, 1, 2))(hidden, kernel, weights)
    dense, dense_grads = jax.value_and_grad(
        lambda h, k, w: _dense_weighted(h, k.astype(dtype), targets, w),
        argnums=(0, 1, 2))(hidden, kernel, weights)
    np.testing.assert_allclose(float(loss), float(dense), rtol=rtol)
    for got, exp in zip(grads, dense_grads):
        assert got.dtype == exp.dtype and got.shape == exp.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=g_rtol, atol=g_atol)
    # without gradients the value alone is the same number
    np.testing.assert_allclose(float(chunked_softmax_cross_entropy(
        hidden, kernel, targets, chunk=chunk, weights=weights)),
        float(loss), rtol=1e-6)


def test_uniform_weights_are_the_unweighted_call():
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(2, 32, 16), jnp.float32)
    kernel = jnp.asarray(rng.randn(16, 50) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, 50, (2, 32)))
    f = lambda w: jax.value_and_grad(  # noqa: E731
        lambda h, k: chunked_softmax_cross_entropy(
            h, k, targets, chunk=16, weights=w), argnums=(0, 1))(
                hidden, kernel)
    (mean, g_mean), (weighted, g_weighted) = f(None), f(
        jnp.full((2, 32), 1.0 / 64))
    np.testing.assert_allclose(float(mean), float(weighted), rtol=1e-6)
    for a, b in zip(g_mean, g_weighted):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_weighted_loss_scales_by_a_cotangent_that_is_not_one():
    rng = np.random.RandomState(1)
    hidden = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
    kernel = jnp.asarray(rng.randn(8, 20) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, 20, (1, 32)))
    weights = jnp.asarray(rng.rand(1, 32), jnp.float32)
    args = (hidden, kernel, weights)
    got = jax.grad(lambda h, k, w: 0.3 * chunked_softmax_cross_entropy(
        h, k, targets, chunk=8, weights=w) ** 2, argnums=(0, 1, 2))(*args)
    want = jax.grad(lambda h, k, w: 0.3 * _dense_weighted(
        h, k, targets, w) ** 2, argnums=(0, 1, 2))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_exits_share_one_scan_and_one_head_gradient():
    """T * B * L rows in ONE scan whose rows do not grow with the exits
    (`loss_plan` of one sequence of all the rows), one f32 carry of the
    head's shape."""
    cfg = _cfg(4, 2)
    model, params, tokens = _seeded(cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: _system_loss(model, p, tokens, chunk=8)))(params)

    def scans(j):
        for eq in j.eqns:
            if eq.primitive.name == "scan":
                yield eq
            for sub in jax.core.jaxprs_in_params(eq.params):
                yield from scans(sub)

    (scan,) = list(scans(jaxpr.jaxpr))
    plan = profile.loss_plan(1, 4 * 2 * LENGTH, HIDDEN, VOCAB, 8,
                             jnp.float32, weighted=True)
    assert scan.params["length"] == plan["iterations"]
    carries = [v.aval.shape for v in scan.outvars[:scan.params["num_carry"]]]
    assert carries.count((HIDDEN, VOCAB)) == 1
    # the benchmark's call: 16384 rows, 16 iterations of 1024
    plan = profile.loss_plan(1, 4 * 4096, 2048, 49152, 512, jnp.bfloat16,
                             weighted=True)
    assert (plan["rows"], plan["iterations"]) == (1024, 16)
    assert plan["residual_bytes"] == (4 * 2048 * 49152 + 2 * 16384 * 2048
                                      + 4 * 16384)


def test_exit_distribution_sums_to_one_and_the_last_takes_the_rest():
    gates = jnp.asarray(np.random.RandomState(0).randn(4, 3, 7) * 3.0,
                        jnp.float32)
    p, logp = exit_distribution(gates)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(p[-1], jnp.prod(1.0 - lam[:-1], axis=0),
                               rtol=1e-4)
    np.testing.assert_allclose(
        p, reference.exit_distribution(lam.reshape(4, -1)).reshape(p.shape),
        rtol=1e-4)
    np.testing.assert_allclose(jnp.exp(logp), p, rtol=1e-6)
    # the last gate is not read, and saturated gates keep log p finite
    moved = gates.at[-1].add(5.0)
    np.testing.assert_array_equal(exit_distribution(moved)[0], p)
    assert bool(jnp.all(jnp.isfinite(
        exit_distribution(jnp.full((4, 2), 200.0))[1])))
    stats = exit_stats(gates)
    np.testing.assert_allclose(float(jnp.sum(stats["p_mean"])), 1.0,
                               rtol=1e-6)
    assert 0.0 < float(stats["entropy"]) <= np.log(4) + 1e-6
    assert parallel.exit_stats is exit_stats


class _ParentTransformer(nn.Module):
    """`Transformer.__call__` as it was before the looped stack (PR 31),
    on the same `Block`: what one pass with the new options off must be."""
    cfg: models.TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        cfg = self.cfg
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape)
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim, param_dtype=jnp.float32,
                     dtype=cfg.dtype, name="embed")(tokens)
        for i in range(cfg.num_layers):
            x = Block(cfg, name="block_%d" % i)(x, positions)
        x = _rms_norm(cfg, "norm_f")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        name="lm_head")(x).astype(jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_with_the_options_off_is_the_model_as_it_was(dtype):
    cfg = models.TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=WIDTH, max_seq_len=LENGTH, dtype=dtype)
    assert (cfg.num_passes, cfg.sandwich_norm, cfg.mlp_gated,
            cfg.exit_gate) == (1, False, False, False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, VOCAB)
    now, was = models.Transformer(cfg), _ParentTransformer(cfg)
    params = now.init(jax.random.PRNGKey(0), tokens)["params"]
    parent = was.init(jax.random.PRNGKey(0), tokens)["params"]
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(parent))
    assert sorted(params["block_0"]) == ["attn", "mlp_in", "mlp_out",
                                         "norm1", "norm2"]
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(parent)):
        np.testing.assert_array_equal(a, b)
    for hidden in (False, True):
        np.testing.assert_array_equal(
            now.apply({"params": params}, tokens, return_hidden=hidden),
            was.apply({"params": params}, tokens, return_hidden=hidden))


def test_several_passes_without_a_gate_return_the_last_pass():
    cfg = dataclasses.replace(_cfg(3, 2), exit_gate=False)
    model = models.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, LENGTH), 0, VOCAB)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert "exit_gate" not in params
    hidden = model.apply({"params": params}, tokens, return_hidden=True)
    assert hidden.shape == (1, LENGTH, HIDDEN)
    gated = dict(params, exit_gate={"kernel": jnp.zeros((HIDDEN, 1)),
                                    "bias": jnp.zeros((1,))})
    ref = _reference(cfg, gated, tokens[0])
    np.testing.assert_allclose(hidden[0], ref["hidden"][-1], rtol=2e-4,
                               atol=2e-5)


REFUSED = {
    "passes_with_tp_axis": dict(num_passes=2, tp_axis="tp"),
    "passes_with_sp_axis": dict(num_passes=2, sp_axis="sp"),
    "passes_with_ep_axis": dict(num_passes=2, moe_experts=4, ep_axis="ep"),
    "passes_with_routed_blocks": dict(num_passes=2, moe_experts=4),
    "no_pass_at_all": dict(num_passes=0),
    "exit_gate_on_one_pass": dict(exit_gate=True),
    "gated_mlp_with_tp_axis": dict(mlp_gated=True, tp_axis="tp"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    with pytest.raises(ValueError, match="num_passes|exit_gate|mlp_gated"):
        models.TransformerConfig(**REFUSED[case])


def test_the_program_names_the_loop_its_passes_and_the_exits():
    """`hvd_loop/pass_<t>` around each pass with the blocks' scopes
    beneath, `hvd_exit` around the gate and the exit distribution,
    `hvd_loss` the loss's own and outside both."""
    cfg = _cfg(4, 2)
    model, params, tokens = _seeded(cfg, batch=1)
    text = jax.jit(jax.grad(
        lambda p: _system_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)
    for t in range(1, 5):
        scope = "%s/%s/" % (profile.LOOP, profile.LOOP_PASS % t)
        assert scope + profile.BLOCK + "/block_1/attn" in text
        assert scope + profile.HEAD in text
    assert profile.LOOP_PASS % 5 not in text
    assert profile.EXIT + "/exit_gate" in text
    assert profile.LOSS in text
    for line in text.splitlines():
        assert not (profile.LOSS in line and profile.EXIT in line), line
        assert not (profile.LOOP + "/" in line and profile.EXIT in line), line
