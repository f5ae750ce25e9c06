"""Kimi-Linear-48B-A3B's stack at toy sizes on the CPU: the chunked Kimi
Delta Attention recurrence (`ops/kda.py`) against the token-by-token one,
the mixer and the model (KDA three layers in four, latent attention without
position in the fourth, sigmoid experts beside a shared one) against
`benchmark/references/kimi.py`, a routed layer's 32 shares, a train step,
and what is refused by name.
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
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.references import kimi as reference  # noqa: E402
from horovod_tpu import models, parallel, profile  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops import kda  # noqa: E402
from horovod_tpu.ops.losses import chunked_softmax_cross_entropy  # noqa: E402
from horovod_tpu.parallel import expert  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

VOCAB, HIDDEN, HEADS, LENGTH = 256, 64, 2, 64
EXPERTS, HELD, TOP_K, SCALE = 16, (4, 4), 4, 2.446
KINDS = ("kda", "kda", "kda", "full", "kda", "full")


def _cfg(**over):
    base = dict(
        vocab_size=VOCAB, num_layers=len(KINDS), num_heads=HEADS,
        embed_dim=HIDDEN, mlp_dim=96, mlp_gated=True, max_seq_len=LENGTH,
        attention="dense", norm_eps=1e-5, rotary=False,
        attention_types=KINDS, kda_head_dim=32, kda_chunk=16,
        kv_lora_rank=16, q_lora_rank=None, qk_nope_dim=32, qk_rope_dim=16,
        v_head_dim=32, moe_experts=EXPERTS, moe_every=1, first_k_dense=1,
        moe_dim=32, moe_top_k=TOP_K, moe_capacity_factor=None,
        moe_gated=True, moe_renormalize=True, moe_scoring="sigmoid",
        moe_route_scale=SCALE, moe_shared_dim=32, moe_held=HELD,
        dtype=jnp.float32)
    base.update(over)
    return models.TransformerConfig(**base)


def _arch(cfg, held=HELD):
    return {"kinds": cfg.attention_types, "first_k_dense": cfg.first_k_dense,
            "eps": cfg.norm_eps, "kda_heads": cfg.num_heads,
            "kda_head_dim": cfg.kda_head_dim, "kda_chunk": cfg.kda_chunk,
            "nope": cfg.qk_nope_dim,
            "rope": cfg.qk_rope_dim, "top_k": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "route_scale": cfg.moe_route_scale, "held": held}


def _seeded(cfg, seed=0):
    """(model, parameters with every vector moved off its initial value:
    norm scales, the selection bias, A_log and dt_bias; tokens [1,
    LENGTH])."""
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


def _leaves_close(got, want, tol):
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
        assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, \
            (jax.tree_util.keystr(path), float(jnp.max(jnp.abs(a - b))),
             scale)


# --------------------------------------------------------------------------
# (a) The chunked recurrence against the token-by-token one
# --------------------------------------------------------------------------

def _recurrence_case(L, H=2, D=16, Dv=16, least=1e-3, seed=0):
    """q, k of norm 1, v, g = log(alpha) with alpha drawn down to `least` a
    channel and token (64 tokens at 1e-3 are e^-442: the product of two
    exponentials would overflow), beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    return (unit(jax.random.normal(ks[0], (1, L, H, D))),
            unit(jax.random.normal(ks[1], (1, L, H, D))),
            jax.random.normal(ks[2], (1, L, H, Dv)),
            jnp.log(least) * jax.random.uniform(ks[3], (1, L, H, D)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (1, L, H))))


NAMES = ("q", "k", "v", "g", "beta")


@pytest.mark.parametrize("chunk,sub", [(32, 8), (64, 16)])
@pytest.mark.parametrize("what", ["output", "state", "state_max"]
                         + ["d" + n for n in NAMES])
def test_chunked_kda_agrees_with_the_recurrence(what, chunk, sub):
    """Several chunks, decays down to 1e-3 a token: outputs, the final
    state, the counter, and the gradient of each of the five inputs."""
    args = _recurrence_case(L=4 * chunk, seed=chunk)
    cot_o = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    cot_s = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 16, 16))

    def chunked(*a):
        return kda.kda_chunked(*a, chunk=chunk, sub=sub)

    def sequential(*a):
        o, s, top = reference.kda_recurrence(*(t[0] for t in a), block=chunk)
        return o[None], s[None], top

    if not what.startswith("d"):
        i = ("output", "state", "state_max").index(what)
        got, want = chunked(*args)[i], sequential(*args)[i]
        assert np.all(np.isfinite(np.asarray(got)))
        _close(got, want, 2e-5)
        return
    i = NAMES.index(what[1:])

    def scalar(f):
        return lambda *a: (lambda r: jnp.sum(r[0] * cot_o)
                           + jnp.sum(r[1] * cot_s))(f(*a))

    got = jax.grad(scalar(chunked), argnums=i)(*args)
    want = jax.grad(scalar(sequential), argnums=i)(*args)
    assert np.all(np.isfinite(np.asarray(got)))
    _close(got, want, 5e-5)


def test_chunked_kda_takes_bf16_operands_and_refuses_a_ragged_length():
    args = _recurrence_case(L=128)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    o, s, top = kda.kda_chunked(q, k, v, *args[3:], chunk=64)
    assert o.dtype == s.dtype == top.dtype == jnp.float32
    want = reference.kda_recurrence(*(t[0].astype(jnp.float32)
                                      for t in (q, k, v)),
                                    *(t[0] for t in args[3:]))
    _close(o[0], want[0], 3e-2)
    _close(s[0], want[1], 3e-2)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda.kda_chunked(*_recurrence_case(L=96), chunk=64)
    with pytest.raises(ValueError, match="sub-block"):
        kda.kda_chunked(*args, chunk=64, sub=24)


@pytest.mark.parametrize("what", ["scores", "dq", "dk", "dG"])
@pytest.mark.parametrize("block,group", [(4, 4), (8, 2), (3, 1)])
def test_the_own_block_kernels_agree_with_jnp(what, block, group,
                                              monkeypatch):
    """`hvd_kda_scores` / `hvd_kda_scores_bwd` in Pallas' interpreter
    against the jnp form they stand for, at three ways of cutting the
    sub-blocks into grid steps and register groups."""
    monkeypatch.setattr(kda, "BLOCK_SUBS", block)
    monkeypatch.setattr(kda, "GROUP", group)
    N, sub, D = 24, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(block), 5)
    q, k = (jax.random.normal(kk, (N, sub, D)) for kk in ks[:2])
    G = jnp.cumsum(jnp.log(1e-3) * jax.random.uniform(ks[2], (N, sub, D)),
                   axis=1)
    cot = [jax.random.normal(kk, (N, sub, sub)) for kk in ks[3:]]
    assert kda.own_plan(N, sub, D, interpret=True) == block
    jax.clear_caches()

    def kernels(*a):
        return kda.own_block_scores(*a, interpret=True)

    if what == "scores":
        for got, want in zip(kernels(q, k, G), kda._own_jnp(q, k, G)):
            assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0
            _close(got, want, 1e-5)
        return
    i = ("dq", "dk", "dG").index(what)

    def scalar(f):
        return lambda *a: sum(jnp.sum(r * c) for r, c in zip(f(*a), cot))

    _close(jax.grad(scalar(kernels), argnums=i)(q, k, G),
           jax.grad(scalar(kda._own_jnp), argnums=i)(q, k, G), 1e-5)


def test_own_plan_says_which_calls_take_the_kernels():
    assert kda.own_plan(16384, 16, 128, interpret=True) == kda.BLOCK_SUBS
    assert kda.own_plan(8, 16, 128, interpret=True) == 8
    # no TPU and no interpreter asked for; a narrow head; a short sub-block;
    # sub-blocks no block divides
    assert kda.own_plan(16384, 16, 128) is None
    assert kda.own_plan(16384, 16, 64, interpret=True) is None
    assert kda.own_plan(16384, 8, 128, interpret=True) is None
    assert kda.own_plan(kda.BLOCK_SUBS + 1, 16, 128, interpret=True) is None


def _stage_case(name):
    """The chunk stage's two test shapes: [1, 256, 2, 128] in f32 with
    decays down to 1e-3 a token, and one head of the benchmark's layer cut
    to 4 chunks (bf16 operands, q carrying its scale, the mixer's spread of
    decays)."""
    if name == "f32":
        return _recurrence_case(L=256, H=2, D=128, Dv=128, seed=11), 1e-5
    q, k, v, g, beta = _recurrence_case(L=256, H=1, D=128, Dv=128, seed=12)
    bf16 = jnp.bfloat16
    g = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(13), g.shape,
                                    minval=-7.0, maxval=0.5))
    return ((q * 128 ** -0.5).astype(bf16), k.astype(bf16), v.astype(bf16),
            g, beta), 1e-2


def _stage_jnp(*a):
    """The jnp form's five results laid as the kernels write them: chunks
    leading, e^G_last [B, H, nc, D] a chunk a row."""
    *four, keep = kda._chunk_stage_jnp(*a, 64, 16, None)
    return tuple(jnp.moveaxis(t, 2, 0) for t in four) + (keep[..., 0],)


def _stage_kernels(q, k, v, g, beta):
    B, L, H, _ = q.shape
    block = kda.chunk_plan(B, L, H, 128, 128, 64, 16, interpret=True)
    return kda._chunk_stage_kernels(q, k, v, g, beta, 64, 16, block, True)


def _saved_by_the_forward_kernel(q, k, v, g, beta):
    """(the inverse of the unit triangle, the k-k scores) [B, H, nc, 64,
    64] as `hvd_kda_wy` saves them for the backward rule."""
    B, L, H, D = q.shape
    *five, pq, pk = kda._chunk_stage_operands(q, k, v, g, beta, 64, 16, True)
    return kda._pallas_wy(
        *five, (pq, pk), None, None, 64, 16,
        kda.chunk_plan(B, L, H, D, D, 64, 16, True), True, True)[5:]


STAGE = ("w_and_qe", "u", "qk", "k_out", "keep")


@pytest.mark.parametrize("case", ["f32", "one_head_bf16"])
@pytest.mark.parametrize("what", STAGE)
def test_the_chunk_stage_forward_kernel_agrees_with_jnp(what, case):
    """`hvd_kda_wy` in Pallas' interpreter against the jnp form, every
    result: f32 operands to f32 rounding, bf16 ones to a bf16 step (both
    round at the same places)."""
    args, tol = _stage_case(case)
    i = STAGE.index(what)
    got, want = _stage_kernels(*args)[i], _stage_jnp(*args)[i]
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got.astype(jnp.float32), want.astype(jnp.float32), tol)
    if what == "qk":
        assert float(jnp.max(jnp.abs(jnp.triu(
            got.astype(jnp.float32), 1)))) == 0.0


@pytest.mark.parametrize("case", ["f32", "one_head_bf16"])
@pytest.mark.parametrize("what", ["d" + n for n in NAMES])
def test_the_chunk_stage_backward_kernel_agrees_with_jnp(what, case,
                                                         monkeypatch):
    """`jax.grad` of a scalar of `kda_chunked`'s output and final state
    through `hvd_kda_wy_bwd` against the same through the jnp form."""
    args, tol = _stage_case(case)
    i = NAMES.index(what[1:])
    cot_o = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    cot_s = jax.random.normal(jax.random.PRNGKey(8),
                              (1, args[0].shape[2], 128, 128))

    def scalar(interpret):
        def f(*a):
            o, s, _ = kda.kda_chunked(*a, chunk=64, interpret=interpret)
            return jnp.sum(o * cot_o) + jnp.sum(s * cot_s)
        return f

    got = jax.grad(scalar(True), argnums=i)(*args)
    monkeypatch.setattr(kda, "chunk_plan", lambda *a, **k: None)
    want = jax.grad(scalar(None), argnums=i)(*args)
    assert got.dtype == want.dtype
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    _close(got.astype(jnp.float32), want.astype(jnp.float32),
           5e-5 if case == "f32" else 2e-2)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("alpha", [1e-3, 1.0])
def test_the_chunk_stage_kernels_at_the_decays_ends(alpha, direction):
    """alpha = 1e-3 on every channel over whole chunks (e^-442 a chunk: the
    product of two exponentials would overflow) and alpha exactly 1 (no
    decay: G = 0): no inf, no nan, and the jnp form's numbers."""
    q, k, v, g, beta = _recurrence_case(L=256, H=1, D=128, Dv=128, seed=5)
    g = jnp.full_like(g, np.log(alpha))
    args = (q, k, v, g, beta)
    if direction == "forward":
        for got, want in zip(_stage_kernels(*args), _stage_jnp(*args)):
            assert np.all(np.isfinite(np.asarray(got)))
            _close(got, want, 1e-5)
        return
    cot = jax.random.normal(jax.random.PRNGKey(6), v.shape)

    def scalar(f):
        return lambda *a: sum(jnp.sum(r) for r in f(*a)[1:]) + jnp.sum(
            f(*a)[0][:, 0, :, :64, :] * cot[0, :64, 0])

    got = jax.grad(scalar(_stage_kernels), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(scalar(_stage_jnp), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a)))
        _close(a, b, 5e-5)


@pytest.mark.parametrize("how", ["product", "side_by_side", "kernel"])
def test_the_unit_triangle_inverse_agrees_with_the_triangular_solve(how):
    """The finite product that stands for the solve, alone, for two
    triangles side by side and as the kernel saves it, against
    `lax.linalg.triangular_solve` at f32."""
    args, _ = _stage_case("f32")
    if how == "product":
        a = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (64, 64)), -1)
        got, unit = kda.unit_lower_inverse(a, 16), (a + jnp.eye(64))[None]
    elif how == "side_by_side":
        a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64)),
                     -1)
        got = kda.unit_lower_inverse(jnp.concatenate(list(a), axis=1), 16)
        got, unit = jnp.stack([got[:, :64], got[:, 64:]]), a + jnp.eye(64)
    else:
        got, kk = _saved_by_the_forward_kernel(*args)
        beta = args[4].transpose(0, 2, 1).reshape(1, 2, 4, 64, 1)
        unit = jnp.tril(beta * kk, -1) + jnp.eye(64)
    want = lax.linalg.triangular_solve(
        unit, jnp.broadcast_to(jnp.eye(64), unit.shape), left_side=True,
        lower=True, unit_diagonal=True)
    _close(got, want.reshape(got.shape), 1e-5)
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0


@pytest.mark.parametrize("shape,interpret,takes", [
    ((1, 8192, 32, 128, 128, 64, 16), True, True),   # the benchmark's layer
    ((1, 256, 2, 128, 128, 64, 16), True, True),     # 4 chunks: one block
    ((2, 1024, 4, 256, 128, 64, 16), True, True),
    ((1, 8192, 32, 128, 128, 64, 16), None, False),  # no TPU, no interpreter
    ((1, 8192, 32, 64, 128, 64, 16), True, False),   # a narrow head
    ((1, 8192, 32, 128, 64, 64, 16), True, False),   # a narrow value
    ((1, 8192, 32, 128, 128, 64, 8), True, False),   # a short sub-block
    ((1, 8192, 32, 128, 128, 32, 16), True, False),  # another chunk
    ((1, 768, 2, 128, 128, 64, 16), True, False),    # 12 chunks: no block
])
def test_chunk_plan_says_which_calls_take_the_kernels(shape, interpret,
                                                      takes):
    block = kda.chunk_plan(*shape, interpret=interpret)
    if not takes:
        assert block is None
        return
    chunks = shape[1] // 64
    assert block == min(kda.BLOCK_CHUNKS, chunks) and chunks % block == 0


# --- the scan over the chunks: `hvd_kda_scan` / `hvd_kda_scan_bwd` ---------

SCAN = ("output", "state", "largest")
SCAN_OPERANDS = ("w_and_qe", "u", "qk", "k_out", "keep")


def _scan_oracle(wq, u, qk, k_out, keep):
    """The jnp scan on the operands as the kernels read them."""
    return kda._scan_jnp(wq, u, qk, k_out,
                         jnp.moveaxis(keep, 2, 0)[..., None])


def _scan_through_kernels(heads):
    def scan(*operands):
        o, final, top = kda._scan_kernels(*operands, heads, True)
        return o, final, jnp.max(top)
    return scan


def _scan_scalar(scan, shapes):
    cot_o = jax.random.normal(jax.random.PRNGKey(7), shapes[0])
    cot_s = jax.random.normal(jax.random.PRNGKey(8), shapes[1])
    return lambda *s: (lambda r: jnp.sum(r[0] * cot_o)
                       + jnp.sum(r[1] * cot_s))(scan(*s))


@functools.lru_cache(maxsize=None)
def _scan_readings(case, heads, alpha=None):
    """{"results": (kernels', jnp's), "gradients": (kernels', jnp's)} of the
    scan alone on the jnp chunk stage's operands: four heads of four chunks
    (`case` "f32": decays down to 1e-3 a token, or `alpha` on every channel;
    "bf16": rounded W, scores and keys, the mixer's spread of decays)."""
    q, k, v, g, beta = _recurrence_case(L=256, H=4, D=128, Dv=128, seed=21)
    if alpha is not None:
        g = jnp.full_like(g, np.log(alpha))
    if case == "bf16":
        g = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(22), g.shape,
                                        minval=-7.0, maxval=0.5))
        q, k, v = (t.astype(jnp.bfloat16)
                   for t in (q * 128 ** -0.5, k, v))
    operands = _stage_jnp(q, k, v, g, beta)
    kernels, both = _scan_through_kernels(heads), {}
    both["results"] = kernels(*operands), _scan_oracle(*operands)
    shapes = tuple(t.shape for t in both["results"][1][:2])
    both["gradients"] = tuple(
        jax.grad(_scan_scalar(f, shapes), argnums=(0, 1, 2, 3, 4))(*operands)
        for f in (kernels, _scan_oracle))
    return both


SCAN_READINGS = SCAN + tuple("d_" + n for n in SCAN_OPERANDS)


def _scan_reading(read, what):
    """(the kernels', jnp's) reading `what` of `_scan_readings`' result."""
    if what in SCAN:
        return tuple(r[SCAN.index(what)] for r in read["results"])
    return tuple(r[SCAN_OPERANDS.index(what[2:])] for r in read["gradients"])


@pytest.mark.parametrize("heads", [1, 2, 4])
@pytest.mark.parametrize("what", SCAN_READINGS)
def test_the_scan_kernels_agree_with_the_jnp_scan(what, heads):
    """`hvd_kda_scan` / `hvd_kda_scan_bwd` in Pallas' interpreter, 1, 2 and
    4 heads a grid step, against `lax.scan` over the same operands: the
    output as the mixer lays it, the final state, the largest |S| a chunk
    ends in; the five operands' cotangents against `jax.grad` of the jnp
    scan (f32 operands: both round nowhere)."""
    got, want = _scan_reading(_scan_readings("f32", heads), what)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.all(np.isfinite(np.asarray(got)))
    _close(got, want, 1e-6 if what in SCAN else 5e-5)


@pytest.mark.parametrize("what", SCAN_READINGS)
def test_the_scan_kernels_round_where_the_jnp_scan_rounds(what):
    """bf16 operands: the state and V' rounded to bf16 where `carry` rounds
    them, f32 accumulation; the forward to f32 rounding, the cotangents to a
    bf16 step (autodiff rounds a bf16 operand's cotangent a chunk at a
    time, the kernel keeps f32 between its products)."""
    got, want = _scan_reading(_scan_readings("bf16", 2), what)
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got.astype(jnp.float32), want.astype(jnp.float32),
           1e-6 if what in SCAN else 2e-2)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("alpha", [1e-3, 1.0])
def test_the_scan_kernels_at_the_decays_ends(alpha, direction):
    """alpha = 1e-3 on every channel (a chunk keeps e^-442 of the state: 0
    in f32) and alpha exactly 1 (the state kept whole): no inf, no nan, and
    the jnp scan's numbers."""
    read = _scan_readings("f32", 2, alpha)[
        "results" if direction == "forward" else "gradients"]
    for got, want in zip(*read):
        assert np.all(np.isfinite(np.asarray(got)))
        _close(got, want, 1e-6 if direction == "forward" else 5e-5)


def test_scan_heads_are_a_tile_of_the_output_or_all():
    """A block of o [B, L, H, Dv] is [C, heads, Dv]: the tile's eight
    sublanes, or every head."""
    assert kda.SCAN_HEADS == 8
    assert [kda.scan_heads(H) for H in (32, 16, 8, 12, 4, 2, 1)] == [
        8, 8, 8, 12, 4, 2, 1]


ALL_KERNELS = profile.KDA_KERNELS + profile.KDA_SCAN_KERNELS


@functools.lru_cache(maxsize=None)
def _whole_call_readings():
    """`kda_chunked` through all six kernels in the interpreter and the
    token-by-token recurrence: (results, gradients of a scalar of the output
    and the final state) each side, and the kernels of the program."""
    was = kda.BLOCK_SUBS
    kda.BLOCK_SUBS = 8
    jax.clear_caches()
    try:
        args = _recurrence_case(L=256, H=2, D=128, Dv=128, seed=3)

        def chunked(*a):
            return kda.kda_chunked(*a, chunk=64, interpret=True)

        def sequential(*a):
            o, s, top = reference.kda_recurrence(*(t[0] for t in a))
            return o[None], s[None], top

        want = sequential(*args)
        shapes = tuple(t.shape for t in want[:2])
        text = str(jax.make_jaxpr(jax.grad(_scan_scalar(chunked, shapes)))(
            *args))
        return tuple(
            (f(*args), jax.grad(_scan_scalar(f, shapes),
                                argnums=(0, 1, 2, 3, 4))(*args))
            for f in (chunked, sequential)) + (
                {n for n in ALL_KERNELS
                 if re.search(r"name=%s\b" % n, text)},)
    finally:
        kda.BLOCK_SUBS = was
        jax.clear_caches()


@pytest.mark.parametrize("what", ["output", "state", "state_max"]
                         + ["d" + n for n in NAMES])
def test_chunked_kda_through_the_kernels_agrees_with_the_recurrence(what):
    """The whole call through its six kernels (the own blocks' two, the
    chunk stage's two, the scan's two) against the token-by-token
    recurrence: outputs, the final state, the counter, the five inputs'
    gradients."""
    got, want, kernels = _whole_call_readings()
    assert kernels == set(ALL_KERNELS)
    if what.startswith("d"):
        i = NAMES.index(what[1:])
        _close(got[1][i], want[1][i], 5e-5)
    else:
        i = ("output", "state", "state_max").index(what)
        _close(got[0][i], want[0][i], 2e-5)


@pytest.mark.parametrize("kernels", [False, True])
def test_decayed_scores_never_form_an_l_by_l_array_or_a_token_loop(kernels):
    """The program of the chunked form, forward and backward. In jnp: one
    scan over the L / C chunks and its transpose. Through the kernels: no
    scan of that length (the chunks are the grid of `hvd_kda_scan` /
    `hvd_kda_scan_bwd`), only the chunk stage's kernels' loops over a grid
    step's chunks and sub-blocks. Never an array with two axes of the
    sequence's length."""
    L, chunk = (2048, 64) if kernels else (256, 32)
    args = _recurrence_case(L=L, D=128 if kernels else 16,
                            Dv=128 if kernels else 16)

    def program(*a):
        return jax.grad(lambda *b: jnp.sum(kda.kda_chunked(
            *b, chunk=chunk, interpret=kernels or None)[0]))(*a)

    jaxpr = jax.make_jaxpr(program)(*args)
    text = str(jaxpr)
    block = kda.chunk_plan(1, L, 2, 128, 128, chunk, 16, kernels or None)
    assert (block == kda.BLOCK_CHUNKS) is kernels
    # the chunks' scan and its transpose, or a kernel's loop over its chunks
    lengths = [int(n) for n in re.findall(r"length=(\d+)", text)]
    assert len(lengths) == text.count("scan[")
    if kernels:
        assert set(lengths) == {block // kda.SIDE,
                                kda.BLOCK_SUBS // kda.GROUP}
        assert all(re.search(r"name=%s\b" % n, text) for n in ALL_KERNELS)
    else:
        assert lengths == [L // chunk] * 2
    assert "while[" not in text
    assert ("pallas_call" in text) is kernels

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield getattr(v.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    assert not any(sum(1 for d in s if d >= L) > 1 for s in shapes(
        jaxpr.jaxpr))


# --------------------------------------------------------------------------
# (b) The mixer and latent attention without position
# --------------------------------------------------------------------------

def test_the_kda_mixer_agrees_with_the_reference():
    cfg = _cfg()
    module = transformer.KimiDeltaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    p = module.init(jax.random.PRNGKey(1), x)["params"]
    inner = HEADS * 32
    assert {k: (v["kernel"] if isinstance(v, dict) else v).shape
            for k, v in p.items()} == {
        "in_proj": (HIDDEN, 3 * inner + 2 * 32 + HEADS),
        "conv_kernel": (4, 3 * inner), "f_up": (32, inner),
        "g_up": (32, inner), "A_log": (HEADS,), "dt_bias": (inner,),
        "norm": (32,), "out_proj": (inner, HIDDEN)}
    p = dict(p, norm=p["norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (32,)))
    arch = _arch(cfg)
    g = jax.random.normal(jax.random.PRNGKey(3), (LENGTH, HIDDEN))

    def system(p, x):
        y, state = module.apply({"params": p}, x, mutable=["intermediates"])
        return y[0], state["intermediates"]["kda_state_max"][0]

    want, top = reference.kda_mixer(x[0], p, arch)
    _close(system(p, x)[0], want, 2e-5)
    assert float(system(p, x)[1]) == pytest.approx(float(top), rel=1e-4)
    got = jax.grad(lambda *a: jnp.sum(system(*a)[0] * g), argnums=(0, 1))(
        p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference.kda_mixer(
        x[0], p, arch)[0] * g), argnums=(0, 1))(p, x)
    _leaves_close(got, want, 5e-4)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_latent_attention_without_position(attention):
    """`rotary=False` beside "kda" layers is the rotated layer turned by
    nothing (every token at position 0: all angles are zero), and the
    reference's."""
    cfg = _cfg(attention=attention)
    turned = _cfg(attention=attention, rotary=True, attention_types=None)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    pos = jnp.arange(LENGTH)[None]
    p = transformer.LatentAttention(cfg).init(
        jax.random.PRNGKey(1), x, pos)["params"]
    got = transformer.LatentAttention(cfg).apply({"params": p}, x, pos)
    _close(got, transformer.LatentAttention(turned).apply(
        {"params": p}, x, jnp.zeros_like(pos)), 1e-6)
    rotated = transformer.LatentAttention(turned).apply({"params": p}, x, pos)
    assert float(jnp.max(jnp.abs(got - rotated))) > 1e-3
    _close(got[0], reference.latent_attention(x[0], p, _arch(cfg)), 2e-5)
    # and a position moves nothing
    _close(got, transformer.LatentAttention(cfg).apply(
        {"params": p}, x, pos + 5), 0.0)


# --------------------------------------------------------------------------
# (c) A routed layer's 32 shares
# --------------------------------------------------------------------------

def test_32_shares_of_a_routed_layer_add_up_to_the_uncut_one():
    """8 of 256 experts each, sigmoid scores, top-8 on score + bias, the
    weights renormalised x 2.446, the shared expert counted ONCE: the sum of
    what 32 ranks compute is the reference's uncut layer."""
    experts, top_k, dim, width = 256, 8, 32, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, dim))
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    whole = {
        "router": jax.random.normal(ks[0], (dim, experts)),
        "select_bias": 0.3 * jax.random.normal(ks[1], (experts,)),
        "w_gate": 0.3 * jax.random.normal(ks[2], (experts, dim, width)),
        "w_up": 0.3 * jax.random.normal(ks[3], (experts, dim, width)),
        "w_down": 0.3 * jax.random.normal(ks[4], (experts, width, dim)),
        "shared_gate": {"kernel": 0.3 * jax.random.normal(ks[5], (dim, 16))},
        "shared_up": {"kernel": 0.3 * jax.random.normal(ks[6], (dim, 16))},
        "shared_down": {"kernel": 0.3 * jax.random.normal(ks[7], (16, dim))}}
    arch = {"top_k": top_k, "norm_topk_prob": True, "route_scale": SCALE,
            "held": (0, experts)}
    want, chosen, _ = reference.routed_ffn(x[0], whole, arch)
    shared_alone = reference.gated(
        x[0], *(whole[n]["kernel"] for n in ("shared_gate", "shared_up",
                                             "shared_down")))
    total, rows = jnp.zeros_like(want), 0
    for rank in range(32):
        held = (8 * rank, 8)
        module = expert.MoeMlp(
            num_experts=experts, mlp_dim=width, capacity_factor=None,
            top_k=top_k, gated=True, dtype=jnp.float32, scoring="sigmoid",
            route_scale=SCALE, held=held, shared_dim=16)
        p = dict(whole, **{n: whole[n][held[0]:held[0] + 8]
                           for n in ("w_gate", "w_up", "w_down")})
        y, state = module.apply({"params": p}, x, mutable=["intermediates"])
        total = total + y[0] - shared_alone
        stats = parallel.routing_stats(state["intermediates"])
        rows += float(stats["held_share"][0])
        if rank in (0, 31):  # the share is the reference's layer as HELD
            _close(y[0], reference.routed_ffn(
                x[0], p, dict(arch, held=held))[0], 5e-6)
    _close(total + shared_alone, want, 2e-5)
    assert rows == pytest.approx(1.0)
    assert int(jnp.sum(chosen)) == top_k * LENGTH


# --------------------------------------------------------------------------
# (d) The whole model and one train step
# --------------------------------------------------------------------------

def _loss(model, params, tokens, chunk=16):
    hid = model.apply({"params": params}, tokens, return_hidden=True)
    return chunked_softmax_cross_entropy(
        hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, 1),
        chunk=chunk)


@pytest.mark.parametrize("case", ["dense", "flash", "block_remat"])
def test_loss_states_and_gradients_agree_with_the_reference(case):
    cfg = _cfg(**{"dense": {}, "flash": {"attention": "flash"},
                  "block_remat": {"block_remat": 5}}[case])
    model, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    ref = reference.forward(params, tokens[0], arch)
    hid, state = model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["intermediates"],
        capture_intermediates=lambda m, n: isinstance(
            m, transformer.Block) and n == "__call__")
    inter = state["intermediates"]
    for i in range(cfg.num_layers):
        _close(inter["block_%d" % i]["__call__"][0][0], ref["states"][i],
               5e-5)
    assert float(models.kda_stats(inter)) == pytest.approx(
        float(ref["kda_state_max"]), rel=1e-4)
    stats = parallel.routing_stats(inter)
    chosen = jnp.any(jax.nn.one_hot(stats["chosen"], EXPERTS,
                                    dtype=jnp.bool_), axis=-2)
    assert bool(jnp.all(chosen == ref["chosen"]))
    assert [int(v) for v in stats["assignments"][
        :, HELD[0]:HELD[0] + HELD[1]].sum(1)] \
        == [int(v) for v in ref["held_rows"]]
    loss, grads = jax.value_and_grad(
        lambda p: _loss(model, p, tokens))(params)
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    want = reference.gradient(params, tokens[0], arch)
    # a layer of each kind: KDA, latent, dense, routed
    for name in ("block_0", "block_3", "block_4", "block_5", "embed"):
        _leaves_close(grads[name], want[name], 1e-3)


@pytest.mark.parametrize("other", ["shared", "decay"])
def test_the_reference_of_another_model_is_far(other):
    """Without the shared expert, and with alpha = 1 (the plain delta rule):
    the comparison a chip run makes must see both."""
    cfg = _cfg()
    _, params, tokens = _seeded(cfg)
    arch = _arch(cfg)
    ref = reference.forward(params, tokens[0], arch)["states"]
    off = reference.forward(params, tokens[0], arch,
                            **{other: 0.0})["states"]
    err = jnp.max(jnp.abs(ref - off), axis=(1, 2)) \
        / jnp.max(jnp.abs(ref), axis=(1, 2))
    assert float(jnp.max(err)) > 0.05


def test_one_train_step_agrees_with_the_reference():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    opt = optax.adamw(1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    mesh = parallel.data_parallel_mesh(devices=jax.devices()[:1])
    step = parallel.make_train_step(
        lambda p, b: _loss(model, p, b["x"]), opt, mesh)
    arch = _arch(cfg)
    want_loss = float(reference.forward(params, tokens[0], arch)["loss"])
    grads = reference.gradient(params, tokens[0], arch)
    updates, _ = opt.update(grads, opt.init(params), params)
    want = optax.apply_updates(params, updates)
    state = step.place(jax.tree_util.tree_map(jnp.copy, params),
                       opt.init(params), {"x": tokens})
    new, _, loss = step(*state)
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    # Adam's first step is lr * sign(g) wherever |g| >> eps: where the two
    # gradients agree in sign the parameters agree to rounding.
    moved = jax.tree_util.tree_map(
        lambda a, b, g: jnp.mean((jnp.abs(a - b) <= 1e-6)
                                 | (jnp.abs(g) < 1e-6)), new, want, grads)
    assert min(float(v) for v in jax.tree_util.tree_leaves(moved)) > 0.97


def test_the_program_names_the_mixers_parts():
    cfg = _cfg()
    model, params, tokens = _seeded(cfg)
    text = jax.jit(jax.grad(lambda p: _loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    assert profile.KDA_SCOPES == (
        "hvd_kda", "hvd_kda_proj", "hvd_kda_conv", "hvd_kda_gate",
        "hvd_kda_chunk", "hvd_kda_carry")
    for i, kind in enumerate(KINDS):
        here = "block_%d/%s" % (i, profile.KDA if kind == "kda"
                                else profile.ATTN_FULL)
        there = "block_%d/%s" % (i, profile.ATTN_FULL if kind == "kda"
                                 else profile.KDA)
        assert here in text and there not in text
    for part in profile.KDA_SCOPES[1:]:
        assert "%s/attn/%s" % (profile.KDA, part) in text
    for turn in ("cos", "sin"):  # nothing is rotated
        assert "%s/%s" % (profile.ATTN_ROPE, turn) not in text


@pytest.mark.parametrize("kernel", profile.KDA_CONV_KERNELS)
def test_the_program_names_the_convolutions_kernels(kernel, monkeypatch):
    """A mixer whose heads are whole lane tiles runs `ops.kda_conv.kda_qkv`'s
    two kernels (here in the interpreter), called under `hvd_kda_conv`
    through one jitted function, so that a model's layers share a lowering
    of each; they are in `profile.KERNELS` and in neither tuple of the
    recurrence's."""
    from horovod_tpu.ops import kda_conv
    monkeypatch.setattr(kda_conv, "kda_qkv", functools.partial(
        kda_conv.kda_qkv, interpret=True))
    cfg = _cfg(kda_head_dim=128)
    module = transformer.KimiDeltaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, LENGTH, HIDDEN))
    p = module.init(jax.random.PRNGKey(1), x)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(module.apply(
        {"params": p}, x, mutable=["intermediates"])[0]))).lower(
            p).as_text(debug_info=True)
    assert "%s/jit(_pallas_qkv)" % profile.KDA_CONV in text
    assert "%s/pallas_call" % kernel in text
    assert kernel in profile.KERNELS and kernel not in ALL_KERNELS
    assert len(profile.KERNELS) == len(set(profile.KERNELS)) == 24


# --------------------------------------------------------------------------
# (e) What is not built is refused by name
# --------------------------------------------------------------------------

REFUSED = {
    "hc_mult": dict(hc_mult=2),
    "mtp_depth": dict(mtp_depth=1),
    "attention_mask": dict(attention_mask=object()),
    "tp_axis": dict(tp_axis="tp", moe_experts=None, moe_held=None,
                    moe_scoring="softmax", moe_shared_dim=None,
                    first_k_dense=0, moe_dim=None),
    "sp_axis": dict(attention="ring", sp_axis="sp"),
    "layer_types": dict(layer_types=("attn",) * len(KINDS)),
    # latent attention has no band, and its no-position form stands beside
    # "kda" layers alone
    "kv_lora_rank": dict(attention_types=("kda", "window") * 3,
                         attention_window=8),
    "rotary=False": dict(attention_types=None),
    "each of full, window, kda": dict(
        attention_types=("kda", "linear") * 3)}


@pytest.mark.parametrize("case", list(REFUSED))
def test_combinations_not_built_are_refused_by_name(case):
    with pytest.raises(ValueError) as err:
        _cfg(**REFUSED[case])
    assert case in str(err.value)


def test_kda_layers_stand_beside_plain_attention_too():
    """Without `kv_lora_rank` the "full" layers are plain attention, rotated
    or not."""
    for rotary in (True, False):
        cfg = _cfg(kv_lora_rank=None, rotary=rotary, head_dim=32)
        model, params, tokens = _seeded(cfg)
        assert "query" in params["block_3"]["attn"]
        assert "in_proj" in params["block_0"]["attn"]
        assert np.isfinite(float(_loss(model, params, tokens)))
