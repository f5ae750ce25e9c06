"""The short convolutions of a Kimi Delta Attention mixer with their SiLU
and the head norms of q and k (`ops/kda_conv.py`): the two Pallas kernels in
the interpreter against the jnp form (the mixer's own lines until PR 64),
what `conv_plan` says of a call, and the mixer through the kernels against
the mixer as it stood.
"""

import functools
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
import flax.linen as nn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import models, profile  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops import kda_conv  # noqa: E402
from horovod_tpu.ops.kda import kda_chunked  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

PARTS = ("q", "k", "v")
# (B, L, H, D, taps, columns past 3 H D, BLOCK_ROWS, CHUNK_ROWS,
# BLOCK_LANES, CHUNK_UNROLL): one block and one chunk; blocks smaller than L
# and chunks smaller than a block (the halo at every border, of a block and
# of a chunk); two taps; two sequences; heads a block that do not make up
# all; a head of two lane tiles; chunks side by side in a loop's iteration
CASES = {
    "one_block": (1, 64, 2, 128, 4, 40, 1024, 64, 512, 1),
    "three_blocks": (1, 96, 2, 128, 4, 40, 32, 16, 512, 1),
    "chunks_in_blocks": (1, 192, 1, 128, 4, 0, 96, 48, 512, 1),
    "two_taps": (1, 64, 2, 128, 2, 8, 32, 32, 512, 1),
    "two_sequences": (2, 64, 2, 128, 4, 40, 32, 16, 512, 1),
    "a_head_a_block": (2, 64, 4, 128, 3, 40, 32, 16, 128, 1),
    "wide_heads": (1, 64, 1, 256, 4, 40, 32, 16, 512, 1),
    "chunks_side_by_side": (2, 128, 2, 128, 4, 40, 64, 16, 512, 2),
}


def _operands(case, dtype=jnp.float32, seed=0):
    B, L, H, D, taps, rest = CASES[case][:6]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    proj = jax.random.normal(ks[0], (B, L, 3 * H * D + rest)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, 3 * H * D))
    cot = tuple(jax.random.normal(k, (B, L, H * D)).astype(dtype)
                for k in ks[2:])
    return proj, w, cot


def _sized(monkeypatch, case):
    rows, chunk, lanes, unroll = CASES[case][6:]
    monkeypatch.setattr(kda_conv, "BLOCK_ROWS", rows)
    monkeypatch.setattr(kda_conv, "CHUNK_ROWS", chunk)
    monkeypatch.setattr(kda_conv, "BLOCK_LANES", lanes)
    monkeypatch.setattr(kda_conv, "CHUNK_UNROLL", unroll)
    H, D = CASES[case][2:4]
    return functools.partial(kda_conv.kda_qkv, heads=H, head_dim=D,
                             interpret=True), \
        functools.partial(kda_conv._qkv_jnp, heads=H, head_dim=D)


def _scalar(f, cot):
    return lambda proj, w: sum(
        jnp.sum(o.astype(jnp.float32) * c.astype(jnp.float32))
        for o, c in zip(f(proj, w), cot))


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


# --------------------------------------------------------------------------
# (a) The kernels against the jnp form
# --------------------------------------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("part", PARTS)
def test_the_forward_kernel_agrees_with_jnp(part, case, monkeypatch):
    """`hvd_kda_qkv` in Pallas' interpreter: every row of q, k and v, the
    rows at a block's and a chunk's border among them."""
    kernels, in_jnp = _sized(monkeypatch, case)
    B, L, H, D, taps = CASES[case][:5]
    plan = kda_conv.conv_plan(B, L, H, D, taps, jnp.float32, interpret=True)
    assert plan["path"] == "kernel"
    assert plan["block_rows"] == min(CASES[case][6], L)
    proj, w, _ = _operands(case)
    i = PARTS.index(part)
    got, want = kernels(proj, w)[i], in_jnp(proj, w)[i]
    assert got.shape == (B, L, H * D) and got.dtype == proj.dtype
    _close(got, want, 2e-6)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("what", ["dproj", "dtaps"])
def test_the_backward_kernel_agrees_with_the_gradient_of_jnp(what, case,
                                                             monkeypatch):
    """`hvd_kda_qkv_bwd` in the interpreter against `jax.grad` of the jnp
    form: the cotangent of `proj` (zeros in the columns the op does not
    read) and the taps' gradient, summed over the batch and the blocks."""
    kernels, in_jnp = _sized(monkeypatch, case)
    proj, w, cot = _operands(case)
    i = ["dproj", "dtaps"].index(what)
    got = jax.grad(_scalar(kernels, cot), argnums=i)(proj, w)
    want = jax.grad(_scalar(in_jnp, cot), argnums=i)(proj, w)
    _close(got, want, 1e-5)
    if what == "dproj" and CASES[case][5]:
        inner = 3 * CASES[case][2] * CASES[case][3]
        assert not np.any(np.asarray(got[..., inner:]))


@pytest.mark.parametrize("what", PARTS + ("dproj", "dtaps"))
def test_the_kernels_in_the_models_dtype(what, monkeypatch):
    """bf16 in and out, f32 between: the forward rounds once, as jnp does;
    the backward's cotangent of `proj` is ONE rounding of an f32 sum where
    autodiff of the jnp form adds four rounded terms in bf16."""
    kernels, in_jnp = _sized(monkeypatch, "three_blocks")
    proj, w, cot = _operands("three_blocks", jnp.bfloat16)
    if what in PARTS:
        i = PARTS.index(what)
        got, want = kernels(proj, w)[i], in_jnp(proj, w)[i]
        assert got.dtype == jnp.bfloat16
        _close(got.astype(jnp.float32), want.astype(jnp.float32), 8e-3)
        return
    i = ["dproj", "dtaps"].index(what)
    got = jax.grad(_scalar(kernels, cot), argnums=i)(proj, w)
    # the gradient of the jnp form on the same numbers in f32
    want = jax.grad(_scalar(in_jnp, cot), argnums=i)(
        proj.astype(jnp.float32), w)
    assert got.dtype == (jnp.bfloat16 if what == "dproj" else jnp.float32)
    _close(got.astype(jnp.float32), want, 8e-3 if what == "dproj" else 1e-5)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_no_row_leaks_into_the_next_sequence(direction, monkeypatch):
    """A batch's second sequence reads what it reads alone: zeros before
    its first token, not the first sequence's last rows (the halo), and
    nothing of the first sequence's cotangents (the carried scratch)."""
    kernels, _ = _sized(monkeypatch, "two_sequences")
    proj, w, cot = _operands("two_sequences")

    def run(proj, cot):
        if direction == "forward":
            return kernels(proj, w)
        return (jax.grad(_scalar(kernels, cot))(proj, w),)

    both = run(proj, cot)
    alone = run(proj[1:], tuple(c[1:] for c in cot))
    for a, b in zip(both, alone):
        np.testing.assert_array_equal(np.asarray(a[1:]), np.asarray(b))


@pytest.mark.parametrize("taps", [2, 4])
def test_the_first_tokens_see_zeros(taps, monkeypatch):
    """Token t < taps - 1 is convolved with zeros before the sequence:
    v's first row is silu(the last tap x the first token), alone."""
    case = "two_taps" if taps == 2 else "three_blocks"
    kernels, _ = _sized(monkeypatch, case)
    proj, w, _ = _operands(case)
    H, D = CASES[case][2:4]
    v = kernels(proj, w)[2]
    _close(v[:, 0], jax.nn.silu(w[-1, 2 * H * D:] * proj[:, 0, 2 * H * D:
                                                         3 * H * D]), 1e-6)
    # token 0 reaches the rows before `taps`, and no row from there on
    other = kernels(proj.at[:, :1].add(1.0), w)[2]
    assert float(jnp.max(jnp.abs(other[:, taps:] - v[:, taps:]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(other[:, :taps] - v[:, :taps]),
                                 axis=-1))) > 0.0


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_columns_past_the_three_parts_are_not_read(direction, monkeypatch):
    """Whatever the in-projection's other columns hold (f, z, b), q, k, v
    and the gradients do not see it, and their cotangent here is zero."""
    kernels, _ = _sized(monkeypatch, "three_blocks")
    proj, w, cot = _operands("three_blocks")
    inner = 3 * CASES["three_blocks"][2] * CASES["three_blocks"][3]
    poisoned = proj.at[..., inner:].set(jnp.nan)
    if direction == "forward":
        for a, b in zip(kernels(poisoned, w), kernels(proj, w)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    got = jax.grad(_scalar(kernels, cot), argnums=(0, 1))(poisoned, w)
    want = jax.grad(_scalar(kernels, cot), argnums=(0, 1))(proj, w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.any(np.asarray(got[0][..., inner:]))


# --------------------------------------------------------------------------
# (b) The plan
# --------------------------------------------------------------------------

# (B, L, H, D, taps), interpret, a TPU -> the path, and why
PLANS = {
    "the_cell": ((1, 8192, 32, 128, 4), None, True, "kernel"),
    "interpreter": ((2, 64, 2, 128, 4), True, False, "kernel"),
    "no_tpu": ((1, 8192, 32, 128, 4), None, False, "jnp"),
    "narrow_head": ((1, 8192, 32, 64, 4), True, False, "jnp"),
    "ragged_length": ((1, 8200, 32, 128, 4), True, False, "jnp"),
    "short_length": ((1, 8, 2, 128, 4), True, False, "jnp"),
    "long_taps": ((1, 8192, 32, 128, 10), True, False, "jnp"),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_conv_plan_says_which_calls_take_the_kernels(case, monkeypatch):
    shape, interpret, tpu, path = PLANS[case]
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = hvd.profile.kda_conv_plan(*shape, jnp.bfloat16,
                                     interpret=interpret)
    assert plan == kda_conv.conv_plan(*shape, jnp.bfloat16,
                                      interpret=interpret)
    assert plan["path"] == path
    B, L, H, D, taps = shape
    columns, weights = B * L * 3 * H * D * 2, taps * 3 * H * D * 4
    if path == "jnp":
        assert (plan["block_rows"], plan["lane_tiles"],
                plan["grid_steps"]) == (0, 0, 0)
        # the least a call moves, whatever XLA's passes do
        assert plan["bytes"] == {"forward": 2 * columns + weights,
                                 "backward": 3 * columns + 2 * weights}
        return
    rows = min(kda_conv.BLOCK_ROWS, L)
    assert plan["block_rows"] == rows
    assert plan["lane_tiles"] == min(kda_conv.BLOCK_LANES, H * D) // 128
    assert plan["chunk_rows"] == min(kda_conv.CHUNK_ROWS, rows)
    assert plan["grid_steps"] == B * (L // rows) * (
        H * D // (128 * plan["lane_tiles"]))
    halos = B * (L // rows) * kda_conv.HALO_ROWS * 3 * H * D * 2
    assert plan["bytes"] == {
        "forward": 2 * columns + halos + weights,
        "backward": 3 * columns + halos + 2 * weights}


def test_the_cells_call_moves_what_the_issue_counted(monkeypatch):
    """`kimilin48b_1chip`'s call: 201 MB of columns read and 201 written
    forward, three such passes backward (0.49 and 0.74 ms at 819 GB/s)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = hvd.profile.kda_conv_plan(1, 8192, 32, 128, 4, jnp.bfloat16)
    assert plan["path"] == "kernel"
    assert 402e6 < plan["bytes"]["forward"] < 410e6
    assert 603e6 < plan["bytes"]["backward"] < 612e6


def _kernels_in(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return {name for name in profile.KERNELS
            if re.search(r"name=%s\b" % name, text)}


@pytest.mark.parametrize("head_dim,kernels", [(64, False), (128, True)])
def test_a_narrow_head_takes_the_jnp_path(head_dim, kernels):
    """D = 64: no kernel in the program, the plan says so, the numbers are
    the jnp form's; D = 128 under `interpret=True`: both kernels."""
    B, L, H, taps = 1, 64, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    proj = jax.random.normal(ks[0], (B, L, 3 * H * head_dim + 24))
    w = jax.random.normal(ks[1], (taps, 3 * H * head_dim))
    plan = kda_conv.conv_plan(B, L, H, head_dim, taps, jnp.float32,
                              interpret=True)
    assert plan["path"] == ("kernel" if kernels else "jnp")

    def both(proj, w):
        out, vjp = jax.vjp(lambda p, w: kda_conv.kda_qkv(
            p, w, H, head_dim, interpret=True), proj, w)
        return out, vjp(out)

    found = _kernels_in(both, proj, w)
    assert found == (set(profile.KDA_CONV_KERNELS) if kernels else set())
    for a, b in zip(kda_conv.kda_qkv(proj, w, H, head_dim, interpret=True),
                    kda_conv._qkv_jnp(proj, w, H, head_dim)):
        if kernels:
            _close(a, b, 2e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_conv_kernels_have_a_tuple_of_their_own():
    assert profile.KDA_CONV_KERNELS == ("hvd_kda_qkv", "hvd_kda_qkv_bwd")
    assert set(profile.KDA_CONV_KERNELS) <= set(profile.KERNELS)
    assert not set(profile.KDA_CONV_KERNELS) & set(
        profile.KDA_KERNELS + profile.KDA_SCAN_KERNELS)
    # `kda_kernel_ms` and `kda_kernel_roofline` keep meaning the chunk stage
    assert profile.KDA_KERNELS == (
        "hvd_kda_scores", "hvd_kda_scores_bwd", "hvd_kda_wy",
        "hvd_kda_wy_bwd")


# --------------------------------------------------------------------------
# (c) The mixer through the kernels against the mixer as it stood
# --------------------------------------------------------------------------

HIDDEN, HEADS, HEAD_DIM, LENGTH = 64, 2, 128, 64


def _mixer_cfg():
    return models.TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=HEADS, embed_dim=HIDDEN,
        mlp_dim=96, max_seq_len=LENGTH, attention="dense", norm_eps=1e-5,
        rotary=False, attention_types=("kda", "full"),
        kda_head_dim=HEAD_DIM, kda_chunk=16, kv_lora_rank=16,
        q_lora_rank=None, qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32,
        dtype=jnp.float32)


def _mixer_as_it_stood(cfg, p, x):
    """`KimiDeltaAttention.__call__` of the parent commit on the parameters
    `p`: the convolution, SiLU, the reshape to heads and the l2 norms as its
    lines had them."""
    H, D, taps = cfg.num_heads, cfg.kda_head_dim, cfg.kda_conv
    inner = H * D
    B, L, _ = x.shape
    f32 = jnp.float32

    def heads(t):
        return t.reshape(B, L, H, D)

    def l2(t):
        return t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                     keepdims=True) + 1e-6)

    proj = x @ p["in_proj"]["kernel"]
    qkv = proj[..., :3 * inner]
    f = proj[..., 3 * inner:3 * inner + D]
    z = proj[..., 3 * inner + D:3 * inner + 2 * D]
    b = proj[..., 3 * inner + 2 * D:]
    w = p["conv_kernel"]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = nn.silu(sum(w[j] * padded[:, j:j + L].astype(f32)
                      for j in range(taps)))
    q, k, v = (heads(qkv[..., i * inner:(i + 1) * inner])
               for i in range(3))
    f = f @ p["f_up"]["kernel"]
    z = z @ p["g_up"]["kernel"]
    q = (l2(q) * D ** -0.5).astype(cfg.dtype)
    k = l2(k).astype(cfg.dtype)
    v = v.astype(cfg.dtype)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(heads(
        f.astype(f32) + p["dt_bias"]))
    beta = jax.nn.sigmoid(b.astype(f32))
    o, _, _ = kda_chunked(q, k, v, g, beta, cfg.kda_chunk)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + cfg.norm_eps) * p["norm"]
    o = (o * jax.nn.sigmoid(heads(z.astype(f32)))).reshape(
        B, L, inner).astype(cfg.dtype)
    return o @ p["out_proj"]["kernel"]


@functools.lru_cache(maxsize=None)
def _mixer_case():
    cfg = _mixer_cfg()
    module = transformer.KimiDeltaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, LENGTH, HIDDEN))
    p = module.init(jax.random.PRNGKey(1), x)["params"]
    flat, tree = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))
    p = jax.tree_util.tree_unflatten(tree, [
        t + 0.3 * jax.random.normal(k, t.shape) if t.ndim == 1 else t
        for k, t in zip(keys, flat)])
    g = jax.random.normal(jax.random.PRNGKey(3), (2, LENGTH, HIDDEN))
    return cfg, module, p, x, g


MIXER_LEAVES = ("in_proj", "conv_kernel", "f_up", "g_up", "A_log", "dt_bias",
                "norm", "out_proj")


@pytest.mark.parametrize("what", ("output", "x") + MIXER_LEAVES)
def test_the_mixer_through_the_kernels_is_the_mixer_as_it_stood(
        what, monkeypatch):
    """`KimiDeltaAttention` with `kda_qkv`'s two kernels in the interpreter
    (blocks of 32 rows: two a sequence) against the parent's lines, within
    the model tests' tolerances (`tests/test_kimi_linear.py`: 2e-5 of the
    output, 5e-4 of a gradient leaf)."""
    cfg, module, p, x, g = _mixer_case()
    monkeypatch.setattr(kda_conv, "BLOCK_ROWS", 32)
    monkeypatch.setattr(kda_conv, "CHUNK_ROWS", 16)
    monkeypatch.setattr(kda_conv, "kda_qkv", functools.partial(
        kda_conv.kda_qkv, interpret=True))

    def system(p, x):
        return module.apply({"params": p}, x, mutable=["intermediates"])[0]

    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(system(p, x) * g)))(p, x))
    assert all(re.search(r"name=%s\b" % n, text)
               for n in profile.KDA_CONV_KERNELS)
    if what == "output":
        _close(system(p, x), _mixer_as_it_stood(cfg, p, x), 2e-5)
        return
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * g),
                   argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(_mixer_as_it_stood(cfg, p, x) * g),
                    argnums=(0, 1))(p, x)
    a, b = (got[1], want[1]) if what == "x" else (
        jax.tree_util.tree_leaves(got[0][what])[0],
        jax.tree_util.tree_leaves(want[0][what])[0])
    scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
    assert float(jnp.max(jnp.abs(a - b))) <= 5e-4 * scale
