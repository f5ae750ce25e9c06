"""What each layer of a `Transformer` is, said once:
`TransformerConfig.layers()` makes a `Layer` a block from the three
spellings a configuration may use (`moe_every` / `first_k_dense`;
`layer_types`; `attention_types`), `Block` runs it. Held here: the table, by
the rule of each spelling written out below, and the parameter paths and
shapes of every family of model as they were before the table existed
(PR 59's tree): what `benchmark/builders/*` place reference weights by."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import models
from horovod_tpu.models import Layer
from horovod_tpu.models.transformer import Block

VOCAB, HIDDEN, LENGTH = 64, 32, 16


def _cfg(**over):
    base = dict(vocab_size=VOCAB, num_layers=4, num_heads=2, embed_dim=HIDDEN,
                mlp_dim=48, max_seq_len=LENGTH, attention="dense",
                dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


MOE = dict(moe_experts=4, moe_every=1, moe_capacity_factor=None)
LATENT = dict(kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8)
SSM = dict(ssm_heads=2, ssm_head_dim=8, ssm_state=4, ssm_chunk=8)


def two(mixer, ffn, kind=None, remat=False, sandwich=False):
    """A two-branch block: the mixer behind `norm1`, the feed-forward
    behind `norm2`; a sandwich norm is its branch's norm + `_out`."""
    return Layer((mixer, ffn), ("norm1", "norm2"),
                 ("norm1_out", "norm2_out") if sandwich else (None, None),
                 kind, remat)


def one(mixer, remat=False):
    """A single-mixer layer: one branch behind `norm`."""
    return Layer((mixer,), ("norm",), (None,), None, remat)


# (the spelling, as the named cell's configuration uses it; the table by
# that spelling's rule)
TABLES = {
    # no spelling at all: every block attention + the dense feed-forward
    "plain_lm1b4": (_cfg(), [two("attn", "mlp")] * 4),
    # routed where i % moe_every == moe_every - 1: the default of 2 routes
    # the odd blocks, 1 all of them
    "moe_every_2_default": (_cfg(moe_experts=4), [
        two("attn", "mlp"), two("attn", "moe")] * 2),
    "moe_every_1_olmoe": (_cfg(**MOE), [two("attn", "moe")] * 4),
    # the first `block_remat` blocks are recomputed
    "moe_every_1_remat_sdar": (_cfg(block_remat=2, **MOE), [
        two("attn", "moe", remat=True)] * 2 + [two("attn", "moe")] * 2),
    # ... and i >= first_k_dense; `kv_lora_rank` makes attention latent
    "first_k_dense_latent_kanana": (
        _cfg(first_k_dense=1, block_remat=3, **MOE, **LATENT),
        [two("latent", "mlp", remat=True)]
        + [two("latent", "moe", remat=True)] * 2 + [two("latent", "moe")]),
    # streams change no record (the block's residual is the
    # configuration's); the prediction module's block follows the stack's:
    # routed whatever `first_k_dense` says, never recomputed
    "streams_mtp_xing": (
        _cfg(num_layers=2, first_k_dense=2, block_remat=2, hc_mult=2,
             mtp_depth=1, **MOE, **LATENT),
        [two("latent", "mlp", remat=True)] * 2 + [two("latent", "moe")]),
    # a looped stack is ONE stack (a record a block, not a pass); the
    # sandwich norms are `norm1_out` and `norm2_out`
    "looped_sandwich_ouro": (
        _cfg(num_layers=3, num_passes=4, sandwich_norm=True),
        [two("attn", "mlp", sandwich=True)] * 3),
    # a pattern: one mixer a layer behind `norm`, plain attention whatever
    # else is set
    "layer_types_nemo3": (
        _cfg(layer_types=("ssm", "moe", "attn", "mlp"), block_remat=1, **MOE,
             **SSM),
        [one("ssm", remat=True), one("moe"), one("attn"), one("mlp")]),
    # an attention kind a layer of the two-branch block
    "attention_types_mellum": (
        _cfg(attention_types=("window", "window", "window", "full"),
             attention_window=4, block_remat=4, **MOE),
        [two("attn", "moe", "window", remat=True)] * 3
        + [two("attn", "moe", "full", remat=True)]),
    # "kda" is a mixer of its own and has no kind; beside it a "full" layer
    # is latent attention
    "attention_types_kda_kimi": (
        _cfg(attention_types=("kda", "kda", "kda", "full"), rotary=False,
             first_k_dense=1, kda_head_dim=8, kda_chunk=16, **MOE, **LATENT),
        [two("kda", "mlp")] + [two("kda", "moe")] * 2
        + [two("latent", "moe", "full")]),
    "block_remat_0": (_cfg(num_layers=2), [two("attn", "mlp")] * 2),
    "block_remat_all": (_cfg(num_layers=2, block_remat=2),
                        [two("attn", "mlp", remat=True)] * 2),
    # past the stack's depth it is every block, no more
    "block_remat_past_the_depth": (_cfg(num_layers=2, block_remat=5),
                                   [two("attn", "mlp", remat=True)] * 2),
}


@pytest.mark.parametrize("name", sorted(TABLES))
def test_layers_is_the_table_the_spelling_says(name):
    cfg, table = TABLES[name]
    got = cfg.layers()
    assert isinstance(got, tuple) and all(type(r) is Layer for r in got)
    assert len(got) == cfg.num_layers + cfg.mtp_depth
    assert list(got) == table
    hash(got)  # a flax field, and a key: values all the way down


def test_the_default_layer_is_the_dense_two_branch_block():
    """`Block(cfg)` alone (the pipeline's stage, `examples/jax_pp_lm.py`)
    is attention then the dense feed-forward, whatever the record's
    defaults are called."""
    assert Layer() == two("attn", "mlp")
    cfg = _cfg()
    x = jnp.ones((1, LENGTH, HIDDEN))
    positions = jnp.arange(LENGTH, dtype=jnp.int32)[None]
    shapes = jax.eval_shape(Block(cfg).init, jax.random.PRNGKey(0), x,
                            positions)["params"]
    assert _paths(shapes) == [
        "attn/key/kernel (32, 2, 16)", "attn/out/kernel (2, 16, 32)",
        "attn/query/kernel (32, 2, 16)", "attn/value/kernel (32, 2, 16)",
        "mlp_in/kernel (32, 48)", "mlp_out/kernel (48, 32)",
        "norm1/scale (32,)", "norm2/scale (32,)"]
    # and the same parameters as the stack's block of that record
    model = jax.eval_shape(models.Transformer(cfg).init,
                           jax.random.PRNGKey(0),
                           jnp.zeros((1, LENGTH), jnp.int32))["params"]
    assert _paths(model["block_0"]) == _paths(shapes)


def test_a_record_with_an_unknown_mixer_is_refused_by_name():
    x = jnp.ones((1, LENGTH, HIDDEN))
    positions = jnp.arange(LENGTH, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="'mpl'.*attn, latent, kda, ssm"):
        Block(_cfg(), Layer(("attn", "mpl"))).init(
            jax.random.PRNGKey(0), x, positions)


def _paths(tree):
    return sorted(
        "%s %s" % ("/".join(k.key for k in path), tuple(leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])


def _expand(lines):
    """`block_{0,1}/norm1/scale (32,)` is that line for block_0 and for
    block_1."""
    out = []
    for line in lines:
        head, brace, rest = line.partition("{")
        if not brace:
            out.append(line)
            continue
        names, _, tail = rest.partition("}")
        out.extend(head + name + tail for name in names.split(","))
    return sorted(out)


ATTN = ["attn/key/kernel (32, 2, 16)", "attn/out/kernel (2, 16, 32)",
        "attn/query/kernel (32, 2, 16)", "attn/value/kernel (32, 2, 16)"]
# Direct queries (`q_lora_rank` None), and through a normed projection.
LATENT_KV = ["attn/kv_a/kernel (32, 12)", "attn/kv_b/kernel (8, 2, 16)",
             "attn/kv_norm/scale (8,)", "attn/out/kernel (2, 8, 32)"]
ROUTED = ["moe_mlp/router (32, 4)", "moe_mlp/w_in (4, 32, 48)",
          "moe_mlp/w_out (4, 48, 32)"]
TOP = ["embed/embedding (64, 32)", "lm_head/kernel (32, 64)",
       "norm_f/scale (32,)"]


def _in(block, lines):
    return ["%s/%s" % (block, line) for line in lines]


# One case a family of model: (configuration, the parent's parameter tree).
FAMILIES = {
    "plain": (_cfg(num_layers=2), TOP + _in("block_{0,1}", ATTN + [
        "mlp_in/kernel (32, 48)", "mlp_out/kernel (48, 32)",
        "norm1/scale (32,)", "norm2/scale (32,)"])),
    "looped": (
        _cfg(num_layers=2, num_passes=3, sandwich_norm=True, mlp_gated=True,
             exit_gate=True),
        TOP + ["exit_gate/bias (1,)", "exit_gate/kernel (32, 1)"]
        + _in("block_{0,1}", ATTN + [
            "mlp_gate/kernel (32, 48)", "mlp_up/kernel (32, 48)",
            "mlp_out/kernel (48, 32)", "norm1/scale (32,)",
            "norm1_out/scale (32,)", "norm2/scale (32,)",
            "norm2_out/scale (32,)"])),
    "streams_and_prediction_module": (
        _cfg(num_layers=2, first_k_dense=1, hc_mult=2, mtp_depth=1,
             q_lora_rank=6, moe_shared_dim=16, moe_scoring="sigmoid", **MOE,
             **LATENT),
        TOP + ["mtp_norm_e/scale (32,)", "mtp_norm_f/scale (32,)",
               "mtp_norm_h/scale (32,)", "mtp_proj/kernel (64, 32)"]
        + _in("{block_0,block_1,mtp_block}", LATENT_KV + [
            "attn/q_a/kernel (32, 6)", "attn/q_b/kernel (6, 2, 12)",
            "attn/q_norm/scale (6,)",
            "hc_attn/alpha (3,)", "hc_attn/bias (8,)", "hc_attn/phi (64, 8)",
            "hc_mlp/alpha (3,)", "hc_mlp/bias (8,)", "hc_mlp/phi (64, 8)",
            "norm1/scale (32,)", "norm2/scale (32,)"])
        + _in("block_0", ["mlp_in/kernel (32, 48)",
                          "mlp_out/kernel (48, 32)"])
        + _in("{block_1,mtp_block}", ROUTED + [
            "moe_mlp/select_bias (4,)",
            "moe_mlp/shared_down/kernel (16, 32)",
            "moe_mlp/shared_gate/kernel (32, 16)",
            "moe_mlp/shared_up/kernel (32, 16)"])),
    "pattern": (
        _cfg(layer_types=("ssm", "moe", "attn", "mlp"), moe_latent_dim=8,
             **MOE, **SSM),
        TOP + ["block_{0,1,2,3}/norm/scale (32,)"]
        + _in("block_0/ssm", [
            "A_log (2,)", "D (2,)", "conv_bias (24,)",
            "conv_kernel (4, 24)", "dt_bias (2,)",
            "in_proj/kernel (32, 42)", "norm (16,)",
            "out_proj/kernel (16, 32)"])
        + _in("block_1/moe_mlp", [
            "latent_in/kernel (32, 8)", "latent_out/kernel (8, 32)",
            "router (32, 4)", "w_in (4, 8, 48)", "w_out (4, 48, 8)"])
        + _in("block_2", ATTN)
        + _in("block_3", ["mlp_in/kernel (32, 48)",
                          "mlp_out/kernel (48, 32)"])),
    "window_and_full": (
        _cfg(num_layers=2, attention_types=("window", "full"),
             attention_window=4, num_kv_heads=1, qk_norm="head",
             rope_yarn=models.Yarn(4.0, original_len=8), **MOE),
        TOP + _in("block_{0,1}", ROUTED + [
            "attn/k_norm/scale (16,)", "attn/key/kernel (32, 1, 16)",
            "attn/out/kernel (2, 16, 32)", "attn/q_norm/scale (16,)",
            "attn/query/kernel (32, 2, 16)", "attn/value/kernel (32, 1, 16)",
            "norm1/scale (32,)", "norm2/scale (32,)"])),
    "kda_and_latent": (
        _cfg(num_layers=2, attention_types=("kda", "full"), rotary=False,
             first_k_dense=1, kda_head_dim=8, kda_chunk=16, **MOE, **LATENT),
        TOP + ["block_{0,1}/norm1/scale (32,)", "block_{0,1}/norm2/scale (32,)"]
        + _in("block_0", [
            "attn/A_log (2,)", "attn/conv_kernel (4, 48)",
            "attn/dt_bias (16,)", "attn/f_up/kernel (8, 16)",
            "attn/g_up/kernel (8, 16)", "attn/in_proj/kernel (32, 66)",
            "attn/norm (8,)", "attn/out_proj/kernel (16, 32)",
            "mlp_in/kernel (32, 48)", "mlp_out/kernel (48, 32)"])
        + _in("block_1", LATENT_KV + ["attn/q/kernel (32, 2, 12)"] + ROUTED)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_parameter_paths_and_shapes_are_the_parents(family):
    cfg, lines = FAMILIES[family]
    shapes = jax.eval_shape(models.Transformer(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, LENGTH), jnp.int32))["params"]
    assert _paths(shapes) == _expand(lines)
