"""The short convolutions of a Kimi Delta Attention mixer with what follows
them a token (Pallas, TPU): what `models/transformer.py::KimiDeltaAttention`
does to the q | k | v columns of its in-projection before the recurrence,

    pre[t] = sum_j conv_kernel[j] * x[t - (taps - 1 - j)]    zeros before the
                                                             sequence; f32
    a      = silu(pre)
    q = l2(a) D^-1/2;  k = l2(a);  v = a      l2 a head: a / sqrt(|a|^2 + 1e-6)

f32 from the widened input to the one rounding of q, k, v.

Why kernels (`PERF.md` s6, PR 64): XLA ran the forward at twice its bytes,
laid q and k out by heads ([B, L, H, D], which the TPU tiles by heads) for
the norms and re-laid them in f32 for the recurrence's kernels, which read
[B, L, H D]; autodiff's backward was four padded f32 arrays of the
activation's size, four reductions for the taps' gradients and the norms'
backward, a dozen passes for work whose bytes are three. Two kernels, each
one read of its operands, tokens on the sublanes and a head a block of
lanes (so a head's norm is a reduction along a row of vregs):

- `hvd_kda_qkv`: a grid step reads a block of rows of the SAME heads'
  columns in each of the three parts of `proj` (three BlockSpecs on one
  operand: the columns past 3 H D are never read and no slice is made) and
  the 16 rows before the block (a second BlockSpec a part: the halo; zeros
  at a sequence's start), and writes the block of q, k and v, each
  [B, L, H D] in the model's dtype: what `kda_chunked`'s kernels read.
- `hvd_kda_qkv_bwd`: from `proj`, the taps and the three cotangents, the
  cotangent of the 3 H D columns WHERE THEY LIE in `proj`'s own cotangent
  [B, L, W] (the model's dtype; the columns past them get their zeros in
  place afterwards: no concatenation, no pass of XLA's) and the taps'
  gradient (f32, summed over the batch and the blocks of rows in an output
  block that stays in VMEM). A grid step is one part's block: the grid's
  first axis walks the blocks of lanes of q's, then k's, then v's columns,
  and the cotangents a step does not read hold the block index they were
  left on, so nothing is fetched for them. It forms the pre-activation
  again; nothing in f32 is a residual. The blocks of rows are walked in
  REVERSE: a token's cotangent needs the pre-activation's cotangent of the
  taps - 1 tokens after it, which the block behind leaves in VMEM scratch
  (zeros past a sequence's end).

Inside a grid step a loop takes `CHUNK_ROWS` rows of one head at a time
(`CHUNK_UNROLL` such chunks side by side an iteration), so that what lies
between the load and the store stays in registers; a tap's shifted rows are
a sublane rotation of the chunk with the eight rows before it (after it, in
the backward).

`conv_plan` says which path a call takes, from the shapes alone; the op runs
what it returns. Where a head is no whole number of lane tiles or no block
divides the sequence, and on a backend that is no TPU, the same numbers come
from jnp (`_qkv_jnp`: the mixer's own lines until PR 64, the tests'
reference), unless `interpret=True` asks for the kernels in Pallas'
interpreter (the tests do).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile

# Rows (tokens) of a grid step's block and the lanes of each of q, k and v it
# takes (whole heads); the rows of one head a loop's chunk holds in registers,
# and the chunks of an iteration, their code side by side: a chunk's work is
# ONE chain (load, taps, SiLU, the norm's reduction along the lanes, store)
# that takes ~130 cycles however few rows it has, and the chains of the
# chunks beside it fill its waits. On the v5e at the benchmark's layer
# ([1, 8192, 12576] bf16, 4 taps; my chip run, PR 64; `examples/kda_sweep.py
# --conv`; ms a call forward | backward): chunks of 64 one at a time 1.69 |
# 2.70, two 1.19 | 1.88, four 0.94 | 1.50; of 128 1.17 | 1.90, 0.94 | 1.50,
# **0.86 | 1.48**; of 256 0.94 | 1.54, 0.88 | 1.50, 0.87 | 1.49; of 512 one
# 0.93 | 1.58; the block's shape moves neither by 3% (512 to 2048 rows, 256
# to 1024 lanes). The bytes alone are 0.50 | 0.74.
BLOCK_ROWS = 1024
BLOCK_LANES = 512
CHUNK_ROWS = 128
CHUNK_UNROLL = 4
# The rows before a block that a grid step also reads: a sublane tile of the
# narrowest dtype a model runs in (bf16: 16 rows). The kernels use the last
# eight, an f32 tile, so a convolution has at most nine taps.
HALO_ROWS = 16
_BEFORE = 8
_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20   # of the v5e's 128 MiB
_EPS = 1e-6


def _l2(t):
    return t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True)
                         + _EPS)


def _qkv_jnp(proj, conv_kernel, heads, head_dim):
    """The op in jnp (a call `conv_plan` gives no kernel; the kernels'
    reference): the mixer's lines as they stood, q and k normed on the
    [B, L, H, D] form."""
    H, D = heads, head_dim
    B, L, _ = proj.shape
    taps, inner, f32 = conv_kernel.shape[0], H * D, jnp.float32
    qkv = proj[..., :3 * inner]
    # Tap j reads the token taps - 1 - j behind, zeros before the
    # sequence; widened a tap at a time, as `Mamba2`'s.
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(conv_kernel[j] * padded[:, j:j + L].astype(f32)
                          for j in range(taps)))
    q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(B, L, H, D)
               for i in range(3))
    q = (_l2(q) * D ** -0.5).astype(proj.dtype)
    k = _l2(k).astype(proj.dtype)
    v = v.astype(proj.dtype)
    return tuple(t.reshape(B, L, inner) for t in (q, k, v))


def _blocks(L, heads, head_dim, taps, interpret):
    """(rows of a block, heads of a block, rows of a chunk, chunks side by
    side) for a call, or None where the jnp form runs it."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    if head_dim % _LANES or not 1 <= taps <= _BEFORE + 1:
        return None
    rows = min(BLOCK_ROWS, L)
    chunk = min(CHUNK_ROWS, rows)
    if L % rows or rows % chunk or chunk % HALO_ROWS:
        return None
    wide = max(h for h in range(1, heads + 1)
               if heads % h == 0 and (h == 1 or h * head_dim <= BLOCK_LANES))
    return rows, wide, chunk, \
        CHUNK_UNROLL if (rows // chunk) % CHUNK_UNROLL == 0 else 1


def conv_plan(B, L, H, D, taps, dtype=jnp.bfloat16, interpret=None):
    """How `kda_qkv` runs a call on `proj` [B, L, >= 3 H D] in `dtype`
    against [taps, 3 H D] taps (`hvd.profile.kda_conv_plan`; the op runs
    what this returns):

        {"path": "kernel" or "jnp",
         "block_rows": rows (tokens) of a grid step's block,
         "lane_tiles": 128-lane tiles of each of q, k, v a grid step takes,
         "chunk_rows": rows a kernel's loop holds in registers at a time,
         "grid_steps": steps a call issues,
         "bytes": {"forward": ..., "backward": ...} a call moves}

    The path is "kernel" (`hvd_kda_qkv`, and `hvd_kda_qkv_bwd` in the
    backward rule) where a head is a whole number of lane tiles (D a
    multiple of 128), a block of rows divides the sequence (a multiple of
    `HALO_ROWS` rows), the taps reach no further back than an f32 tile and
    the backend is a TPU (or `interpret` asks for the interpreter); else
    "jnp". The bytes: `proj`'s 3 H D columns read once and q, k, v written
    once (backward: the columns and the three cotangents read, the
    columns' cotangent written), the taps and their gradient, and on the
    kernels' path the halos; XLA's passes on the jnp path move more than
    this, the least."""
    blocks = _blocks(L, H, D, taps, interpret)
    rows, wide, chunk, _ = blocks or (0, 0, 0, 0)
    itemsize = jnp.dtype(dtype).itemsize
    columns = B * L * 3 * H * D * itemsize
    halos = B * (L // rows) * HALO_ROWS * 3 * H * D * itemsize if blocks \
        else 0
    weights = taps * 3 * H * D * 4
    return {"path": "kernel" if blocks else "jnp", "block_rows": rows,
            "lane_tiles": wide * D // _LANES, "chunk_rows": chunk,
            "grid_steps": B * (L // rows) * (H // wide) if blocks else 0,
            "bytes": {"forward": 2 * columns + halos + weights,
                      "backward": 3 * columns + halos + 2 * weights}}


def _behind(x_ref, halo_ref, w, starts, c, chunk, lanes):
    """Chunk c of a block's `lanes` as each tap reads it: for tap j the rows
    [c chunk - s, (c + 1) chunk - s), s = taps - 1 - j tokens behind, in f32
    ([chunk, d] each), and their sum under the taps `w`, the pre-activation.
    The rows before the chunk are the block's own, the halo's last for the
    block's first chunk, zeros where the block `starts` a sequence; a shift
    is a sublane rotation of the chunk with those eight rows."""
    f32, taps = jnp.float32, len(w)
    r0 = pl.multiple_of(c * chunk, chunk)
    own = x_ref[pl.ds(pl.multiple_of(jnp.maximum(r0 - HALO_ROWS, 0),
                                     HALO_ROWS), HALO_ROWS), lanes]
    halo = jnp.where(starts, 0.0, halo_ref[:, lanes].astype(f32))
    before = jnp.where(c == 0, halo, own.astype(f32))[HALO_ROWS - _BEFORE:]
    x = jnp.concatenate(
        [before, x_ref[pl.ds(r0, chunk), lanes].astype(f32)], axis=0)
    shifted = [(pltpu.roll(x, taps - 1 - j, 0) if j < taps - 1 else x)[
        _BEFORE:] for j in range(taps)]
    return shifted, _taps_sum(w, shifted)


def _ahead(rows, s):
    """`rows` [n + 8, d] (a chunk, then eight rows) -> the chunk's rows read
    s tokens ahead, [n, d]."""
    n = rows.shape[0] - _BEFORE
    return (pltpu.roll(rows, n + _BEFORE - s, 0) if s else rows)[:n]


def _taps_sum(w, shifted):
    """sum_j w[j] * shifted[j] in tap order."""
    total = w[0] * shifted[0]
    for w_j, x_j in zip(w[1:], shifted[1:]):
        total = total + w_j * x_j
    return total


def _a_head(heads, head_dim, body):
    """Runs body(lanes) for each head of a block."""
    def one(h, _):
        body(pl.ds(pl.multiple_of(h * head_dim, head_dim), head_dim))

    lax.fori_loop(0, heads, one, None)


def _chunks(n, unroll, a_chunk, carry=None):
    """a_chunk(i, carry) -> carry for i = 0 .. n - 1 in order, `unroll` of
    them an iteration of the loop, their code side by side (Pallas' own
    `unroll=` takes a whole loop or nothing)."""
    def some(i, carry):
        for k in range(unroll):
            carry = a_chunk(i * unroll + k, carry)
        return carry

    return lax.fori_loop(0, n // unroll, some, carry)


def _qkv_kernel(*refs, taps, chunk, unroll, head_dim, parts):
    """`hvd_kda_qkv`: for each part (`parts`: (normed, scale) of q, k, v) a
    block [rows, heads D] of its columns, the rows before it and its taps
    -> the block of its result. A head's chunk at a time."""
    n = len(parts)
    x_refs, halo_refs, w_refs, out_refs = (refs[i * n:(i + 1) * n]
                                           for i in range(4))
    starts = pl.program_id(2) == 0
    rows, lanes_a_block = x_refs[0].shape

    for p, (normed, scale) in enumerate(parts):
        def a_head(lanes, p=p, normed=normed, scale=scale):
            out_ref = out_refs[p]
            w = [w_refs[p][j:j + 1, lanes] for j in range(taps)]

            def a_chunk(c, _):
                _, pre = _behind(x_refs[p], halo_refs[p], w, starts, c,
                                 chunk, lanes)
                a = pre * jax.nn.sigmoid(pre)
                if normed:
                    a = _l2(a)
                if scale is not None:
                    a = a * scale
                out_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk),
                        lanes] = a.astype(out_ref.dtype)

            _chunks(rows // chunk, unroll, a_chunk)

        _a_head(lanes_a_block // head_dim, head_dim, a_head)


def _qkv_bwd_kernel(x_ref, halo_ref, w_ref, *refs, taps, chunk, unroll,
                    head_dim, parts):
    """`hvd_kda_qkv_bwd`: a grid step is ONE part's block (the grid's first
    axis walks the blocks of lanes of all of `proj`'s 3 H D columns, q's,
    then k's, then v's): the block of columns, the rows before it, its taps
    and the block of its result's cotangent (`refs`: the three cotangents'
    blocks, of which the step's part reads its own) -> the block of the
    columns' cotangent, written where it lies in `proj`'s; the taps'
    gradient [taps, heads D] f32 summed into a block that stays in VMEM
    over the batch and the blocks of rows, which the grid walks in reverse:
    `after_ref` [8, heads D] f32 carries the pre-activation's cotangent of
    the eight rows after a block (zeros past the sequence's end). With u =
    a r, r = rsqrt(|a|^2 + eps) a head:

        da   = r (dy - u sum(dy u))            (dy = the cotangent x scale)
        dpre = da sig (1 + pre (1 - sig))      sig = sigmoid(pre)
        dw_j = sum_t dpre[t] x[t - (taps - 1 - j)]
        dx   = sum_j w_j dpre[t + (taps - 1 - j)]"""
    dy_refs, (dx_ref, dw_ref, after_ref) = refs[:len(parts)], \
        refs[len(parts):]
    f32 = jnp.float32
    step = pl.program_id(2)
    starts = step == pl.num_programs(2) - 1   # the sequence's first block
    first = (step == 0) & (pl.program_id(1) == 0)
    part = pl.program_id(0) // (pl.num_programs(0) // len(parts))
    rows, lanes_a_block = x_ref.shape
    chunks = rows // chunk

    @pl.when(step == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def a_part(dy_ref, normed, scale):
        def a_head(lanes):
            w = [w_ref[j:j + 1, lanes] for j in range(taps)]

            def a_chunk(i, carry):
                after, sums = carry
                c = chunks - 1 - i
                at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                shifted, pre = _behind(x_ref, halo_ref, w, starts, c,
                                       chunk, lanes)
                sig = jax.nn.sigmoid(pre)
                da = dy_ref[at, lanes].astype(f32)
                if scale is not None:
                    da = da * scale
                if normed:
                    a = pre * sig
                    r = lax.rsqrt(jnp.sum(jnp.square(a), axis=-1,
                                          keepdims=True) + _EPS)
                    u = a * r
                    da = r * (da - u * jnp.sum(da * u, axis=-1,
                                               keepdims=True))
                dpre = da * (sig * (1.0 + pre * (1.0 - sig)))
                sums = tuple(s + jnp.sum((dpre * x_j).reshape(
                    chunk // 8, 8, -1), axis=0)
                    for s, x_j in zip(sums, shifted))
                both = jnp.concatenate([dpre, after], axis=0)
                dx_ref[at, lanes] = _taps_sum(
                    w, [_ahead(both, taps - 1 - j) for j in range(taps)]
                ).astype(dx_ref.dtype)
                return dpre[:_BEFORE], sums

            after, sums = _chunks(
                chunks, unroll, a_chunk,
                (after_ref[:, lanes],
                 (jnp.zeros((8, head_dim), f32),) * taps))
            after_ref[:, lanes] = after
            for j in range(taps):
                dw_ref[j:j + 1, lanes] += jnp.sum(sums[j], axis=0,
                                                  keepdims=True)

        _a_head(lanes_a_block // head_dim, head_dim, a_head)

    for p, (normed, scale) in enumerate(parts):
        pl.when(part == p)(functools.partial(a_part, dy_refs[p], normed,
                                             scale))


# The calls are jitted, as `ops/kda.py`'s are: a model's layers share one
# trace and one lowering of each kernel, and the call site's scope path still
# reaches each call's `op_name`.
@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "blocks",
                                             "interpret"))
def _pallas_qkv(proj, conv_kernel, cot, heads, head_dim, blocks, interpret):
    """The kernels on `proj` [B, L, W >= 3 H D] and the taps [taps, 3 H D]
    f32. With `cot` None the forward one -> (q, k, v) [B, L, H D] in
    `proj`'s dtype; with `cot` = their three cotangents the backward one ->
    (`proj`'s cotangent [B, L, W] in its dtype, of which the kernel writes
    the 3 H D columns it read and NOTHING ELSE, and the taps' gradient
    [taps, 3 H D] f32)."""
    B, L, _ = proj.shape
    taps = conv_kernel.shape[0]
    rows, wide, chunk, unroll = blocks
    inner, lanes = heads * head_dim, wide * head_dim
    across, down = inner // lanes, L // rows
    halos = rows // HALO_ROWS
    # q, k, v: the l2 norm a head, and q's scale
    parts = ((True, head_dim ** -0.5), (True, None), (False, None))
    static = dict(taps=taps, chunk=chunk, unroll=unroll, head_dim=head_dim,
                  parts=parts)
    wide_out = jax.ShapeDtypeStruct((B, L, inner), proj.dtype)
    if cot is None:
        # grid (block of heads, batch, block of rows); a part's blocks lie
        # p * across blocks of lanes to the right in `proj`
        columns = [pl.BlockSpec((None, rows, lanes), lambda c, b, l, p=p: (
            b, l, p * across + c)) for p in range(3)]
        halo = [pl.BlockSpec((None, HALO_ROWS, lanes), lambda c, b, l, p=p: (
            b, jnp.maximum(l * halos - 1, 0), p * across + c))
            for p in range(3)]
        weights = [pl.BlockSpec((taps, lanes), lambda c, b, l, p=p: (
            0, p * across + c)) for p in range(3)]
        return pl.pallas_call(
            functools.partial(_qkv_kernel, **static),
            name=profile.KDA_QKV, in_specs=columns + halo + weights,
            out_specs=[columns[0]] * 3, out_shape=[wide_out] * 3,
            grid=(across, B, down), interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3,
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        )(*[proj] * 6, *[conv_kernel] * 3)
    # grid (block of lanes of the 3 H D columns, batch, block of rows from
    # the last to the first): a step is one part's block, and the two
    # cotangents it does not read stay on the block they were left on, or
    # wait on their first (no fetch while the index holds)
    row = lambda l: down - 1 - l  # noqa: E731

    def cotangent(p):
        def index(c, b, l):
            ahead, behind = c < p * across, c >= (p + 1) * across
            hold = lambda first, mine, last: jnp.where(  # noqa: E731
                ahead, first, jnp.where(behind, last, mine))
            return (hold(0, b, B - 1), row(hold(0, l, down - 1)),
                    hold(0, c - p * across, across - 1))
        return pl.BlockSpec((None, rows, lanes), index)

    columns = pl.BlockSpec((None, rows, lanes),
                           lambda c, b, l: (b, row(l), c))
    weights = pl.BlockSpec((taps, lanes), lambda c, b, l: (0, c))
    return pl.pallas_call(
        functools.partial(_qkv_bwd_kernel, **static),
        name=profile.KDA_QKV_BWD,
        in_specs=[columns, pl.BlockSpec(
            (None, HALO_ROWS, lanes), lambda c, b, l: (
                b, jnp.maximum(row(l) * halos - 1, 0), c)), weights]
        + [cotangent(p) for p in range(3)],
        out_specs=[columns, weights],
        out_shape=[jax.ShapeDtypeStruct(proj.shape, proj.dtype),
                   jax.ShapeDtypeStruct(conv_kernel.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_BEFORE, lanes), jnp.float32)],
        grid=(3 * across, B, down), interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(proj, proj, conv_kernel, *cot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _qkv_kernels(proj, conv_kernel, heads, head_dim, blocks, interpret):
    return tuple(_pallas_qkv(proj, conv_kernel, None, heads, head_dim,
                             blocks, interpret))


def _qkv_kernels_fwd(proj, conv_kernel, heads, head_dim, blocks, interpret):
    return _qkv_kernels(proj, conv_kernel, heads, head_dim, blocks,
                        interpret), (proj, conv_kernel)


def _qkv_kernels_bwd(heads, head_dim, blocks, interpret, res, cot):
    proj, conv_kernel = res
    d_proj, d_taps = _pallas_qkv(proj, conv_kernel, tuple(cot), heads,
                                 head_dim, blocks, interpret)
    # The kernel wrote the cotangent of the columns it read where they lie
    # in `proj`'s; the columns the op does not read get their zeros in
    # place (their own cotangents are added by whoever sliced them).
    inner = 3 * heads * head_dim
    if proj.shape[-1] > inner:
        d_proj = lax.dynamic_update_slice(d_proj, jnp.zeros(
            proj.shape[:-1] + (proj.shape[-1] - inner,), proj.dtype),
            (0, 0, inner))
    return d_proj, d_taps


_qkv_kernels.defvjp(_qkv_kernels_fwd, _qkv_kernels_bwd)


def kda_qkv(proj, conv_kernel, heads, head_dim, interpret=None):
    """proj [B, L, W] with W >= 3 H D (the mixer's in-projection: q | k | v
    | whatever else, which is not read), conv_kernel [taps, 3 H D] f32 ->
    (q, k, v), each [B, L, H D] in `proj`'s dtype: the causal depthwise
    convolution of the first 3 H D columns (tap j times the token taps - 1 -
    j behind, summed in tap order, zeros before the sequence), SiLU, for q
    and k the l2 norm a head (eps 1e-6), for q the scale D^-1/2; f32 from
    the widened input to the one rounding. Two Pallas kernels under one
    rule (`conv_plan`), else jnp. `interpret`: None takes the kernels on a
    TPU where the shapes fit; True runs them in Pallas' interpreter."""
    blocks = _blocks(proj.shape[1], heads, head_dim, conv_kernel.shape[0],
                     interpret)
    if blocks is None:
        return _qkv_jnp(proj, conv_kernel, heads, head_dim)
    return _qkv_kernels(proj, conv_kernel, heads, head_dim, blocks,
                        bool(interpret))
