"""Kimi Delta Attention's recurrence in its chunked form (Kimi Linear,
arXiv:2510.26692: a gated delta rule whose decay is a vector a head and
token): Pallas kernels for what is made a chunk at a time and for the scan
over the chunks, and the same in jnp (a `lax.scan`) for the calls the kernels
do not take.

Per head, with a state S [D, Dv], a key k_t of norm 1, a decay alpha_t =
exp(g_t) in (0, 1]^D and a step beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

With u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t) the update reads
S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T, so inside a chunk of C tokens that
starts from S (G_t = sum_{r <= t} g_r over the chunk's tokens, f32, <= 0):

    A    = strict-lower(beta_t sum_c k_tc k_sc exp(G_tc - G_sc))   [C, C]
    W, U = (I + A)^-1 Diag(beta) [K (.) exp(G) | V]      the WY form: one
                                  unit-lower-triangular solve a chunk
    V'   = U - W S                                       the u_t of the chunk
    O    = (Q (.) exp(G)) S + lower(sum_c q_tc k_sc exp(G_tc - G_sc)) V'
    S'   = Diag(exp(G_last)) S + (K (.) exp(G_last - G))^T V'

A, the solve, W and U are made for all chunks at once (the chunk stage); the
L / C chunks are tied by a scan whose body is the last three lines. Never a
loop over the tokens, never an [L, L] array.

The decayed scores do not factor into one product: exp(G_t) exp(-G_s) has a
factor that overflows (64 tokens at alpha = 1e-3 are e^442). They are made
by GLA's secondary chunking (arXiv:2312.06635 s4.3) in sub-blocks of `sub`
tokens: a block of rows against the tokens BEFORE it through the decay at
the block's first token r (exp(G_t - G_r) on the row, exp(G_r - G_s) on the
key: both exponents <= 0, one product), and a block against itself term by
term (the exponent masked to -inf above the diagonal before the
exponential). No exponential ever takes a positive sum. In jnp the
term-by-term part's [sub, sub, D] terms are 2 GiB a layer at 8192 tokens x
32 heads and the backward pass writes them out several times over; a kernel
holds a few sub-blocks' terms in registers, a column of the scores at a
time, and writes [sub, sub].

The chunk stage is Pallas kernels under one rule (`chunk_plan`; a TPU,
heads and values multiples of 128 wide, chunks of 64 in sub-blocks of 16)
around one product of XLA's, G = the triangle of ones times g. They read q,
k, v, G as the mixer lays them ([B, L, H D]: a head's slab is a block of
columns, and cutting L into chunks or sub-blocks moves nothing). The
term-by-term part is `hvd_kda_scores` / `hvd_kda_scores_bwd`
(`own_block_scores`'s two, which the Kimi-Linear cell's configuration
names: `program_must_contain`); `hvd_kda_wy` takes their [sub, sub] squares
and makes the two [C, C] score blocks, (I + A)^-1 as a finite product of
[C, C] products (`unit_lower_inverse`: no row loop), W, U and the scan's
decayed operands in VMEM, a chunk at a time, and writes what the scan's
body reads, chunks leading; `hvd_kda_wy_bwd` turns those results'
cotangents into dq, dk, dv, dG, dbeta and the squares' cotangents from the
five inputs, the saved inverse and the saved k-k scores. The scan of such a
call is `hvd_kda_scan`: its grid walks a head's chunks in order with the
state resident in VMEM, `SCAN_HEADS` heads a grid step side by side (a
chunk's products wait on each other, the heads' do not), reads the chunk
stage's results as they lie and writes o as the mixer reads it ([B, L, H,
Dv], which the TPU tiles by heads: a head is a sublane of a token's tile);
under `jax.vjp` it also writes S at the start of every chunk, and
`hvd_kda_scan_bwd` walks the chunks in reverse with the state's cotangent
resident. Any other call (the CPU) makes the same numbers in jnp around
`own_block_scores` (`own_plan`: its two kernels on [N, sub, D] operands or,
off a TPU, jnp too) and ties the chunks by `lax.scan` (`_scan_jnp`, the
kernels' oracle); `interpret=True` runs whichever kernels in Pallas'
interpreter.

Cumulative decays, the term-by-term blocks, the inverse, U and the carried
state are f32, and so is every product of f32 operands (six bf16 passes of
the matrix unit); the operands of the other products are rounded to the
dtype of `v` (bf16 in training) and accumulate in f32, as `ssd_scan`'s.
Everything before the scan lies under the scope `hvd_kda_chunk` (the
kernels too), the scan, kernels or jnp, under `hvd_kda_carry`.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile


# Sub-blocks a grid step of the two kernels takes, and how many of them its
# loop holds in registers at a time ([GROUP, sub, D] f32 is GROUP * sub / 8
# vregs an operand).
BLOCK_SUBS = 64
GROUP = 4


def _decay_to(G, u):
    """exp(G_t - G_u) for the rows t >= u of each sub-block, 0 above: G
    [n, sub, D] f32 -> [n, sub, D]. The exponent is masked to -inf BEFORE
    the exponential (G_t - G_u > 0 where t < u)."""
    rows = lax.broadcasted_iota(jnp.int32, G.shape, 1)
    return jnp.exp(jnp.where(rows >= u, G - G[:, u:u + 1, :], -jnp.inf))


def _own_terms(q, k, G, column):
    """q, k, G [n, sub, D] f32 -> (pq, pk) f32 shaped as `column`, [n, sub,
    width] of int32: the column of its sub-block's [sub, sub] scores a
    position holds (any other number: 0 there). A column u from one
    [sub, D] product reduced over the lanes."""
    pq = pk = jnp.zeros(column.shape, jnp.float32)
    for u in range(G.shape[1]):
        e = _decay_to(G, u) * k[:, u:u + 1, :]
        pq = jnp.where(column == u, jnp.sum(q * e, -1, keepdims=True), pq)
        pk = jnp.where(column == u, jnp.sum(k * e, -1, keepdims=True), pk)
    return pq, pk


def _own_cotangent_terms(q, k, G, dpq, dpk):
    """The gradient of `_own_terms`: q, k, G [n, sub, D] f32, the two
    cotangents [n, sub, sub] -> (dq, the rows' part of dk, the keys' part
    of dk) [n, sub, D] f32, and dG = q dq + k (rows' part) - k (keys' part).
    With e_u = exp(G_t - G_u) k_u and a_u, b_u the column u of the two
    cotangents, dq = sum_u a_u e_u; the rows' part of dk = sum_u b_u e_u; the
    keys' part of dk, row u, = sum_t (a_u q + b_u k) exp(G_t - G_u)."""
    rows = lax.broadcasted_iota(jnp.int32, G.shape, 1)
    dq = dk_rows = dk_keys = jnp.zeros(G.shape, jnp.float32)
    for u in range(G.shape[1]):
        decay = _decay_to(G, u)
        e = decay * k[:, u:u + 1, :]
        a, b = dpq[:, :, u:u + 1], dpk[:, :, u:u + 1]
        dq = dq + a * e
        dk_rows = dk_rows + b * e
        dk_keys = jnp.where(rows == u, jnp.sum(
            (a * q + b * k) * decay, axis=1, keepdims=True), dk_keys)
    return dq, dk_rows, dk_keys


def _sub_blocks(ref, i, group, sub):
    """Where sub-blocks i * group.. lie in a ref [n, sub, D], or in one of
    rows, [n * sub, D] (the model's layout), and the shape they have
    there."""
    if len(ref.shape) == 3:
        return pl.ds(i * group, group), (group, sub, ref.shape[-1])
    rows = group * sub
    return (pl.ds(pl.multiple_of(i * rows, rows), rows), slice(None)), (
        rows, ref.shape[-1])


def _own_scores_kernel(q_ref, k_ref, g_ref, pq_ref, pk_ref, *, sub, group):
    """`hvd_kda_scores`: a block of sub-blocks, `group` at a time; a column
    u of a sub-block's [sub, sub] scores from one [sub, D] product reduced
    over the lanes."""
    f32 = jnp.float32

    def some(i, _):
        at, _ = _sub_blocks(q_ref, i, group, sub)
        q, k, G = (r[at].astype(f32).reshape(group, sub, -1)
                   for r in (q_ref, k_ref, g_ref))
        squares = pl.ds(i * group, group)
        pq_ref[squares], pk_ref[squares] = _own_terms(
            q, k, G, lax.broadcasted_iota(jnp.int32, (group, sub, sub), 2))

    lax.fori_loop(0, pq_ref.shape[0] // group, some, None)


def _own_scores_bwd_kernel(q_ref, k_ref, g_ref, dpq_ref, dpk_ref,
                           dq_ref, dk_ref, dg_ref, *, sub, group):
    """`hvd_kda_scores_bwd`: `_own_cotangent_terms` on a block of
    sub-blocks, `group` at a time."""
    f32 = jnp.float32

    def some(i, _):
        at, there = _sub_blocks(q_ref, i, group, sub)
        q, k, G = (r[at].astype(f32).reshape(group, sub, -1)
                   for r in (q_ref, k_ref, g_ref))
        squares = pl.ds(i * group, group)
        dq, dk_rows, dk_keys = _own_cotangent_terms(
            q, k, G, dpq_ref[squares], dpk_ref[squares])
        dq_ref[at] = dq.reshape(there).astype(dq_ref.dtype)
        dk_ref[at] = (dk_rows + dk_keys).reshape(there).astype(dk_ref.dtype)
        dg_ref[at] = (q * dq + k * (dk_rows - dk_keys)).reshape(there)

    lax.fori_loop(0, dpq_ref.shape[0] // group, some, None)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "heads",
                                             "sub"))
def _pallas_own(q, k, G, cot, block, interpret, heads=None, sub=None):
    """The forward kernel on q, k [N, sub, D], G [N, sub, D] f32 -> (pq, pk)
    [N, sub, sub] f32; with `cot` = (dpq, dpk) the backward kernel -> (dq,
    dk, dG). With `heads` and `sub` the operands are as a mixer lays them,
    [B, L, heads D] (a head's slab a block of columns, a sub-block `sub`
    rows of it), and the squares [B, L / sub, heads, sub, sub]."""
    if heads is None:
        N, sub, D = G.shape
        grid = (N // block,)
        wide = pl.BlockSpec((block, sub, D), lambda i: (i, 0, 0))
        square = pl.BlockSpec((block, sub, sub), lambda i: (i, 0, 0))
        squares = (N, sub, sub)
    else:
        B, N, D = G.shape[0], G.shape[1] // sub, G.shape[2] // heads
        grid = (B, heads, N // block)
        wide = pl.BlockSpec((None, block * sub, D),
                            lambda b, h, j: (b, j, h))
        square = pl.BlockSpec((None, block, None, sub, sub),
                              lambda b, h, j: (b, j, h, 0, 0))
        squares = (B, N, heads, sub, sub)
    how = dict(grid=grid, interpret=interpret,
               compiler_params=pltpu.CompilerParams(
                   dimension_semantics=("parallel",) * len(grid)))
    group = GROUP if block % GROUP == 0 else 1
    if cot is None:
        return pl.pallas_call(
            functools.partial(_own_scores_kernel, sub=sub, group=group),
            name=profile.KDA_SCORES, in_specs=[wide] * 3,
            out_specs=[square] * 2,
            out_shape=[jax.ShapeDtypeStruct(squares, jnp.float32)] * 2,
            **how)(q, k, G)
    return pl.pallas_call(
        functools.partial(_own_scores_bwd_kernel, sub=sub, group=group),
        name=profile.KDA_SCORES_BWD, in_specs=[wide] * 3 + [square] * 2,
        out_specs=[wide] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(G.shape, jnp.float32)],
        **how)(q, k, G, *cot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _own_kernels(q, k, G, block, interpret, heads=None, sub=None):
    return tuple(_pallas_own(q, k, G, None, block, interpret, heads, sub))


def _own_kernels_fwd(q, k, G, block, interpret, heads, sub):
    return _own_kernels(q, k, G, block, interpret, heads, sub), (q, k, G)


def _own_kernels_bwd(block, interpret, heads, sub, res, cot):
    return tuple(_pallas_own(*res, tuple(cot), block, interpret, heads, sub))


_own_kernels.defvjp(_own_kernels_fwd, _own_kernels_bwd)


@jax.checkpoint
def _own_jnp(q, k, G):
    """The kernels' result in jnp (no TPU, or shapes they do not take); the
    [sub, sub, D] terms are recomputed in the backward pass, no residual."""
    s = G.shape[-2]
    seen = lax.broadcasted_iota(jnp.int32, (s, s, 1), 0) >= \
        lax.broadcasted_iota(jnp.int32, (s, s, 1), 1)
    decay = jnp.exp(jnp.where(
        seen, G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    f32 = jnp.float32
    return tuple(jnp.einsum("ntd,ntud,nud->ntu", x.astype(f32), decay,
                            k.astype(f32)) for x in (q, k))


def own_plan(N, sub, D, interpret=None):
    """How N sub-blocks' own scores are made: the sub-blocks a grid step of
    the kernels takes, or None for the jnp form (no TPU and no interpreter
    asked for; a head no multiple of 128 wide; a sub-block no multiple of 16
    tokens; no block divides N)."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    if D % 128 or sub % 16:
        return None
    block = min(BLOCK_SUBS, N)
    return block if N % block == 0 else None


def own_block_scores(q, k, G, interpret=None):
    """q, k [N, sub, D], G [N, sub, D] f32, non-increasing along `sub` ->
    (pq, pk) [N, sub, sub] f32: sum_c x_tc k_uc exp(G_tc - G_uc) for u <= t
    inside each sub-block (x = q, k), 0 above the diagonal. Two Pallas
    kernels under one rule (`own_plan`), else jnp."""
    block = own_plan(*G.shape, interpret)
    if block is None:
        return _own_jnp(q, k, G)
    return _own_kernels(q, k, G, block, bool(interpret))


# Chunks a grid step of the chunk stage's two kernels takes, and how many of
# them an iteration of its loop takes side by side: a chunk's inverse is ten
# [64, 64] products that wait on each other; side by side they fill the
# matrix unit's columns at the passes of one, and one chunk's other work
# fills another's wait (a forward call at the benchmark's layer: 9.1 ms one
# at a time, 5.9 two, 5.2 four; my chip run, PR 59).
BLOCK_CHUNKS = 8
SIDE = 4


def _square_iotas(C):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _exact(a, b, dims=((1,), (0,))):
    """A product of f32 operands at full f32 precision (the MXU's six bf16
    passes); `dims`: the contracted axis of each side."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _narrow(a, b, dims=((1,), (0,))):
    """A product of operands rounded to the model's dtype, one pass, f32
    accumulation, whatever the ambient `jax_default_matmul_precision` (Mosaic
    refuses full precision on bf16 operands)."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def unit_lower_inverse(A, sub):
    """(I + A)^-1 for A [C, C] f32, strictly lower triangular, as a finite
    product of products: no row loop, nothing cut short. With D the `sub` x
    `sub` diagonal blocks' strict triangles as one block-diagonal matrix
    and N the strictly block-lower rest, I + A = (I + D)(I + M) with
    M = (I + D)^-1 N; D^sub = 0 and M^(C/sub) = 0, and for X^n = 0
    (I + X)^-1 = (I - X)(I + X^2)(I + X^4)... up to the last power under
    n. A [C, m C] is m such triangles side by side, and so is the result:
    a product's right side is then their block diagonal, [m C, m C]."""
    C, wide = A.shape
    rows = lax.broadcasted_iota(jnp.int32, (C, wide), 0)
    cols = lax.broadcasted_iota(jnp.int32, (C, wide), 1) % C
    eye = (rows == cols).astype(A.dtype)
    own = rows // sub == cols // sub
    if wide == C:
        times = _exact
    else:
        a, b = _square_iotas(wide)
        same = a // C == b // C

        def times(X, Y):
            return _exact(X, jnp.where(same, jnp.concatenate(
                [Y] * (wide // C), axis=0), 0.0))

    def inverse(X, order):
        out, power, n = eye - X, X, 2
        while n < order:
            power = times(power, power)
            out = times(out, eye + power)
            n *= 2
        return out

    P = inverse(jnp.where(own, A, 0.0), sub)
    return times(inverse(times(P, jnp.where(own, 0.0, A)), C // sub), P)


def _place_own(squares, C):
    """A chunk's own blocks where they lie in its scores: [n, sub, sub] ->
    [C, C], block i at rows and columns i * sub.., 0 elsewhere."""
    n, sub, _ = squares.shape
    across = jnp.concatenate([squares] * n, axis=2)             # [n, sub, C]
    mine = lax.broadcasted_iota(jnp.int32, across.shape, 2) // sub \
        == lax.broadcasted_iota(jnp.int32, across.shape, 0)
    return jnp.where(mine, across, 0.0).reshape(C, C)


def _take_own(x, sub):
    """The [n, sub, sub] diagonal blocks of x [C, C]."""
    return jnp.stack([x[i:i + sub, i:i + sub]
                      for i in range(0, x.shape[0], sub)])


def _against_earlier(q, k, G, i, sub):
    """Block i of a chunk's rows against the tokens before it, through the
    decay at its first token r (`decayed_scores`' rule; q, k, G [C, D] f32):
    (the rows of q, then of k, times exp(G_t - G_r) [2 sub, D]; the keys
    times exp(G_r - G_s) [C, D], 0 from r on; the two decays alone)."""
    C, D = G.shape
    r = i * sub
    first = G[r:r + 1, :]
    to_rows = jnp.exp(G[r:r + sub, :] - first)
    to_keys = jnp.concatenate(
        [jnp.exp(first - G[:r, :]), jnp.zeros((C - r, D), G.dtype)], axis=0)
    rows = jnp.concatenate(
        [q[r:r + sub, :] * to_rows, k[r:r + sub, :] * to_rows], axis=0)
    return rows, k * to_keys, to_rows, to_keys


def _below(blocks, sub, width):
    """Blocks 1.. of [sub, width] rows under a block of zeros."""
    return jnp.concatenate(
        [jnp.zeros((sub, width), jnp.float32)] + blocks, axis=0)


def _column(row):
    """[1, C] -> [C, 1] through the diagonal (no transpose of a row)."""
    rows, cols = _square_iotas(row.shape[-1])
    return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1, keepdims=True)


def _row(column):
    """[C, 1] -> [1, C] through the diagonal."""
    rows, cols = _square_iotas(column.shape[0])
    return jnp.sum(jnp.where(rows == cols, column, 0.0), axis=0,
                   keepdims=True)


def _chunk_of(refs, c, chunk):
    """Chunk c of a grid step's token blocks, f32."""
    at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    return at, [r[at, :].astype(jnp.float32) for r in refs]


def _wy_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, pq_ref, pk_ref,
               wq_ref, u_ref, qk_ref, ko_ref, keep_ref, *saved, chunk, sub):
    """`hvd_kda_wy`: a head's block of whole chunks, `SIDE` at a time. q, k,
    v, G (the cumulative decays) [block * C, D] as the mixer lays them, beta
    [block, C], the own blocks' squares [block * C / sub, sub, sub] -> what
    the scan's body reads (W over Q e^G [2C, D], U, the lower q-k scores,
    K e^(G_last - G), e^G_last) and, for the backward rule, the inverse of
    the unit triangle and the k-k scores."""
    C, n = chunk, chunk // sub
    rows, cols = _square_iotas(C)
    narrow = k_ref.dtype

    def scores(c):
        """Chunk c up to its triangle; what needs no inverse is written."""
        _, (q, k, v, G) = _chunk_of((q_ref, k_ref, v_ref, g_ref), c, C)
        beta = _column(beta_ref[pl.ds(c, 1), :])
        earlier_q, earlier_k = [], []
        for i in range(1, n):
            x, keys, _, _ = _against_earlier(q, k, G, i, sub)
            s = _narrow(x.astype(narrow), keys.astype(narrow),
                        ((1,), (1,)))
            earlier_q.append(s[:sub])
            earlier_k.append(s[sub:])
        own = pl.ds(c * n, n)
        qk = _place_own(pq_ref[own], C) + _below(earlier_q, sub, C)
        kk = _place_own(pk_ref[own], C) + _below(earlier_k, sub, C)
        decayed, last = jnp.exp(G), G[C - 1:C, :]
        wq_ref[c, C:, :] = (q * decayed).astype(wq_ref.dtype)
        qk_ref[c] = qk.astype(qk_ref.dtype)
        ko_ref[c] = (k * jnp.exp(last - G)).astype(ko_ref.dtype)
        keep_ref[pl.ds(c, 1), :] = jnp.exp(last)
        if saved:
            saved[1][c] = kk
        return (jnp.where(rows > cols, beta * kk, 0.0),
                beta * (k * decayed), beta * v)

    def some(p, _):
        made = [scores(p * SIDE + h) for h in range(SIDE)]
        inverses = unit_lower_inverse(
            jnp.concatenate([m[0] for m in made], axis=1), sub)
        for h, (_, k_side, v_side) in enumerate(made):
            c, inverse = p * SIDE + h, inverses[:, h * C:(h + 1) * C]
            wq_ref[c, :C, :] = _exact(inverse, k_side).astype(wq_ref.dtype)
            u_ref[c] = _exact(inverse, v_side)
            if saved:
                saved[0][c] = inverse

    lax.fori_loop(0, beta_ref.shape[0] // SIDE, some, None)


def _wy_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref, kk_ref,
                   dwq_ref, du_ref, dqk_ref, dko_ref, dkeep_ref,
                   dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dpq_ref,
                   dpk_ref, *, chunk, sub):
    """`hvd_kda_wy_bwd`: the cotangents of `hvd_kda_wy`'s five results, its
    first five inputs, the saved inverse and k-k scores -> dq, dk, dv, dG,
    dbeta as the mixer lays them and the own blocks' squares' cotangents.
    With R = [W | U] = T^-1 Diag(beta) [K e^G | V] in f32: dB = T^-T dR,
    dT = -strict-lower(dB R^T); the earlier blocks' gradients by products;
    every decay's gradient into dG. (The decay at a block's first token
    cancels between its rows and its keys: no term of it is formed.)"""
    f32, C = jnp.float32, chunk
    n = C // sub
    D = q_ref.shape[-1]
    rows, cols = _square_iotas(C)
    narrow = k_ref.dtype
    across, down = ((1,), (1,)), ((0,), (0,))

    def one(c):
        at, (q, k, v, G) = _chunk_of((q_ref, k_ref, v_ref, g_ref), c, C)
        beta = _column(beta_ref[pl.ds(c, 1), :])
        inverse, kk = inverse_ref[c], kk_ref[c]
        decayed, last = jnp.exp(G), G[C - 1:C, :]
        to_last = jnp.exp(last - G)
        ke = k * decayed
        w, u = _exact(inverse, beta * ke), _exact(inverse, beta * v)
        dwq = dwq_ref[c].astype(f32)
        dqe = dwq[C:]
        dbk = _exact(inverse, dwq[:C], down)
        dbv = _exact(inverse, du_ref[c], down)
        da = jnp.where(rows > cols,
                       -(_exact(dbk, w, across) + _exact(dbv, u, across)),
                       0.0)
        dkk = beta * da
        dbeta = jnp.sum(da * kk, axis=1, keepdims=True) \
            + jnp.sum(dbk * ke, axis=1, keepdims=True) \
            + jnp.sum(dbv * v, axis=1, keepdims=True)
        dv_ref[at, :] = (beta * dbv).astype(dv_ref.dtype)
        dke = beta * dbk
        dqk = jnp.where(rows >= cols, dqk_ref[c].astype(f32), 0.0)
        dko = dko_ref[c].astype(f32)
        out = dko * k * to_last
        dq = dqe * decayed
        dk = dke * decayed + dko * to_last
        dG = dke * ke + dq * q - out
        # a block against itself: the own kernels' part
        own = pl.ds(c * n, n)
        dpq_ref[own] = _take_own(dqk, sub)
        dpk_ref[own] = _take_own(dkk, sub)
        # a block against the tokens before it
        into_q, into_k, into_G = [], [], []
        for i in range(1, n):
            r = i * sub
            x, keys, to_rows, to_keys = _against_earlier(q, k, G, i, sub)
            cot = jnp.concatenate([dqk[r:r + sub], dkk[r:r + sub]],
                                  axis=0).astype(narrow)
            dx = _narrow(cot, keys.astype(narrow))
            dkeys = _narrow(cot, x.astype(narrow), down)
            into_q.append(dx[:sub] * to_rows)
            into_k.append(dx[sub:] * to_rows)
            into_G.append(dx[:sub] * x[:sub] + dx[sub:] * x[sub:])
            dk = dk + dkeys * to_keys
            dG = dG - dkeys * keys
        dq = dq + _below(into_q, sub, D)
        dk = dk + _below(into_k, sub, D)
        dG = dG + _below(into_G, sub, D)
        # G_last: every row of K e^(G_last - G), and e^G_last itself
        at_last = jnp.sum(out, axis=0, keepdims=True) \
            + dkeep_ref[pl.ds(c, 1), :] * jnp.exp(last)
        dq_ref[at, :] = dq.astype(dq_ref.dtype)
        dk_ref[at, :] = dk.astype(dk_ref.dtype)
        dg_ref[at, :] = dG + jnp.where(
            lax.broadcasted_iota(jnp.int32, (C, D), 0) == C - 1, at_last, 0.0)
        dbeta_ref[pl.ds(c, 1), :] = jnp.sum(
            jnp.where(rows == cols, dbeta, 0.0), axis=0, keepdims=True)

    def some(p, _):  # SIDE chunks an iteration: one fills another's waits
        for h in range(SIDE):
            one(p * SIDE + h)

    lax.fori_loop(0, beta_ref.shape[0] // SIDE, some, None)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "block", "save",
                                             "interpret"))
def _pallas_wy(q, k, v, G, beta, own, cot, saved, chunk, sub, block, save,
               interpret):
    """The chunk stage's kernels on q, k [B, L, H D], v [B, L, H Dv], G
    [B, L, H D] f32 (the cumulative decays), beta [B, H, L / C, C] f32 and
    `own`, the own blocks' two squares [B, L / sub, H, sub, sub] f32. With
    `cot` None the forward one -> (W over Q e^G [nc, B, H, 2C, D], U [nc,
    B, H, C, Dv] f32, qk [nc, B, H, C, C], K e^(G_last - G) [nc, B, H, C,
    D], e^G_last [B, H, nc, D] f32) and, where `save`, the inverse and the
    k-k scores [B, H, nc, C, C] f32; with the five cotangents and `saved`
    (and no `own`) the backward one -> (dq, dk, dv, dG, dbeta, the two
    squares' cotangents) laid as the inputs."""
    B, H, nc, C = beta.shape
    D, Dv = q.shape[-1] // H, v.shape[-1] // H
    f32, dt = jnp.float32, v.dtype
    tokens = lambda d: pl.BlockSpec(  # noqa: E731
        (None, block * C, d), lambda b, h, j: (b, j, h))
    leading = lambda t, d: pl.BlockSpec(  # noqa: E731
        (block, None, None, t, d), lambda b, h, j: (j, b, h, 0, 0))
    a_head = lambda *d: pl.BlockSpec(  # noqa: E731
        (None, None, block) + d, lambda b, h, j: (b, h, j) + (0,) * len(d))
    inputs = [tokens(D), tokens(D), tokens(Dv), tokens(D), a_head(C)]
    squares = [pl.BlockSpec((None, block * C // sub, None, sub, sub),
                            lambda b, h, j: (b, j, h, 0, 0))] * 2
    results = [leading(2 * C, D), leading(C, Dv), leading(C, C),
               leading(C, D), a_head(D)]
    shapes = [jax.ShapeDtypeStruct((nc, B, H, 2 * C, D), dt),
              jax.ShapeDtypeStruct((nc, B, H, C, Dv), f32),
              jax.ShapeDtypeStruct((nc, B, H, C, C), dt),
              jax.ShapeDtypeStruct((nc, B, H, C, D), dt),
              jax.ShapeDtypeStruct((B, H, nc, D), f32)]
    kept = [a_head(C, C)] * 2
    how = dict(grid=(B, H, nc // block), interpret=interpret,
               compiler_params=pltpu.CompilerParams(
                   dimension_semantics=("parallel",) * 3))
    if cot is None:
        return pl.pallas_call(
            functools.partial(_wy_kernel, chunk=C, sub=sub),
            name=profile.KDA_WY, in_specs=inputs + squares,
            out_specs=results + kept * save,
            out_shape=shapes + [jax.ShapeDtypeStruct(
                (B, H, nc, C, C), f32)] * (2 * save),
            **how)(q, k, v, G, beta, *own)
    return pl.pallas_call(
        functools.partial(_wy_bwd_kernel, chunk=C, sub=sub),
        name=profile.KDA_WY_BWD, in_specs=inputs + kept + results,
        out_specs=inputs + squares,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v, G, beta)] + [jax.ShapeDtypeStruct(
                       (B, nc * C // sub, H, sub, sub), f32)] * 2,
        **how)(q, k, v, G, beta, *saved, *cot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _wy_kernels(q, k, v, G, beta, pq, pk, chunk, sub, block, interpret):
    return tuple(_pallas_wy(q, k, v, G, beta, (pq, pk), None, None, chunk,
                            sub, block, False, interpret))


def _wy_kernels_fwd(q, k, v, G, beta, pq, pk, chunk, sub, block, interpret):
    out = _pallas_wy(q, k, v, G, beta, (pq, pk), None, None, chunk, sub,
                     block, True, interpret)
    return tuple(out[:5]), ((q, k, v, G, beta), tuple(out[5:]))


def _wy_kernels_bwd(chunk, sub, block, interpret, res, cot):
    inputs, saved = res
    return tuple(_pallas_wy(*inputs, None, tuple(cot), saved, chunk, sub,
                            block, False, interpret))


_wy_kernels.defvjp(_wy_kernels_fwd, _wy_kernels_bwd)


def chunk_plan(B, L, H, D, Dv, chunk, sub, interpret=None):
    """How a call's chunk stage is made, and with it its scan (`hvd_kda_scan`
    / `hvd_kda_scan_bwd` where this gives a block, `_scan_jnp` where None):
    the chunks a grid step of the two kernels `hvd_kda_wy` /
    `hvd_kda_wy_bwd` takes, or None for the jnp form (no TPU and no
    interpreter asked for; a head or a value no multiple of 128 wide;
    another chunk than 64 or sub-block than 16: what the kernels' tiles and
    the inverse's product are written for; chunks no block divides, a block
    the tiling does not take, or sub-blocks the own blocks' kernels do not
    take, `own_plan`)."""
    if L % chunk or own_plan(L // sub, sub, D, interpret) is None:
        return None
    if Dv % 128 or chunk != 64 or sub != 16:
        return None
    nc = L // chunk
    block = min(BLOCK_CHUNKS, nc)
    return block if nc % block == 0 and block % SIDE == 0 \
        and (block % 8 == 0 or block == nc) else None


def decayed_scores(q, k, G, sub, interpret=None):
    """q, k [..., C, D], G [..., C, D] f32, non-increasing along C ->
    [..., 2, C, C] f32: sum_c x_tc k_sc exp(G_tc - G_sc) where s <= t, 0
    elsewhere, for x = q and x = k."""
    f32 = jnp.float32
    C, D = G.shape[-2:]
    n = C // sub
    lead = G.shape[:-2]
    flat = lambda t: t.reshape((-1, sub, D))  # noqa: E731
    own = jnp.stack(own_block_scores(flat(q), flat(k), flat(G), interpret))
    own = jnp.moveaxis(own.reshape((2,) + lead + (n, sub, sub)), 0, -4)
    xs = jnp.stack([q, k], axis=-3).astype(f32)               # [2, C, D]
    kf = k.astype(f32)
    blocks = []
    for i in range(n):
        # A block of rows against the tokens BEFORE it, through the decay at
        # its first token r: exp(G_t - G_r) on the rows, exp(G_r - G_s) on
        # the keys, both exponents <= 0; then its own scores; then nothing.
        at, first = slice(i * sub, (i + 1) * sub), G[..., i * sub, None, :]
        parts = []
        if i:
            rows = (xs[..., at, :] * jnp.exp(G[..., at, :] - first)[
                ..., None, :, :]).astype(k.dtype)
            keys = (kf[..., :i * sub, :] * jnp.exp(
                first - G[..., :i * sub, :])).astype(k.dtype)
            parts.append(jnp.einsum("...rtd,...ud->...rtu", rows, keys,
                                    preferred_element_type=f32))
        parts.append(own[..., i, :, :])
        if i < n - 1:
            parts.append(jnp.zeros(lead + (2, sub, C - (i + 1) * sub), f32))
        blocks.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(blocks, axis=-2)


def chunk_cumsum(g):
    """The running sum of g [..., C, D] f32 along C as ONE product with the
    lower triangle of ones, at full f32 precision (`jnp.cumsum` is a
    reduce-window on the TPU: at the benchmark's shape the whole forward
    call took 22.9 ms with it and 15.6 with this; my chip run, PR 58)."""
    C = g.shape[-2]
    ones = jnp.tril(jnp.ones((C, C), g.dtype))
    return jnp.einsum("ts,...sd->...td", ones, g,
                      precision=lax.Precision.HIGHEST)


def _chunk_stage_jnp(q, k, v, g, beta, chunk, sub, interpret):
    """The chunk stage in jnp around `own_block_scores` (a call `chunk_plan`
    does not take; the kernels' oracle): q, k, v, g [B, L, H, d], beta
    [B, L, H] -> (W over Q e^G [B, H, nc, 2C, D], U f32, the lower q-k
    scores, K e^(G_last - G), e^G_last [B, H, nc, D, 1] f32)."""
    B, L, H, D = k.shape
    nc, f32, dt = L // chunk, jnp.float32, v.dtype

    def by_chunk(t):  # [B, L, H, d] -> [B, H, nc, C, d]
        return t.reshape(B, nc, chunk, H, -1).transpose(0, 3, 1, 2, 4)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    G = chunk_cumsum(by_chunk(g.astype(f32)))
    bc = by_chunk(beta.astype(f32)[..., None])                 # [.., C, 1]
    scores = decayed_scores(qc, kc, G, sub, interpret)
    qk, kk = scores[..., 0, :, :], scores[..., 1, :, :]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    unit = jnp.where(rows > cols, bc * kk, 0.0) \
        + (rows == cols).astype(f32)                           # I + A
    decayed = jnp.exp(G)
    wu = lax.linalg.triangular_solve(
        unit, bc * jnp.concatenate(
            [kc.astype(f32) * decayed, vc.astype(f32)], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u = wu[..., :D].astype(dt), wu[..., D:]
    last = G[..., -1:, :]
    # W and the decayed queries meet the carried state in ONE product.
    wq = jnp.concatenate([w, (qc.astype(f32) * decayed).astype(dt)],
                         axis=-2)                              # [.., 2C, D]
    k_out = (kc.astype(f32) * jnp.exp(last - G)).astype(dt)
    keep = jnp.exp(last[..., 0, :])[..., None]                 # [.., D, 1]
    return wq, u, qk.astype(dt), k_out, keep


def _chunk_stage_operands(q, k, v, g, beta, chunk, sub, interpret):
    """What `hvd_kda_wy` reads, from the mixer's arrays: q, k, v [B, L, H d]
    (a head's slab is a block of columns, a chunk or a sub-block a run of
    its rows: every kernel reads this one form), the cumulative decays G
    likewise (f32; XLA's product), beta [B, H, L / C, C] (re-laid: 1 MB) and
    the own blocks' two squares [B, L / sub, H, sub, sub] f32 from
    `hvd_kda_scores` (with `hvd_kda_scores_bwd` behind it), which the
    cell's configuration asks for by name (`program_must_contain`)."""
    B, L, H, D = k.shape
    nc, f32 = L // chunk, jnp.float32
    q, k, v = (t.reshape(B, L, -1) for t in (q, k, v))
    G = chunk_cumsum(g.astype(f32).reshape(B, nc, chunk, -1)).reshape(
        B, L, -1)
    pq, pk = _own_kernels(q, k, G,
                          own_plan(L // sub, sub, D, interpret or None),
                          interpret, H, sub)
    return (q, k, v, G,
            beta.astype(f32).transpose(0, 2, 1).reshape(B, H, nc, chunk),
            pq, pk)


def _chunk_stage_kernels(q, k, v, g, beta, chunk, sub, block, interpret):
    """The chunk stage through its kernels (`chunk_plan`), the scan's
    operands as its kernels read them: (W over Q e^G [nc, B, H, 2C, D], U
    f32, the lower q-k scores, K e^(G_last - G), all chunks leading, and
    e^G_last [B, H, nc, D] f32, a chunk a row)."""
    return _wy_kernels(
        *_chunk_stage_operands(q, k, v, g, beta, chunk, sub, interpret),
        chunk, sub, block, interpret)


# Heads a grid step of the scan's two kernels takes side by side. A chunk's
# products wait on each other (S -> W S -> V' -> S'); the heads are
# independent and one's products fill another's wait.
SCAN_HEADS = 8


def _scan_jnp(wq, u, qk, k_out, keep):
    """The chunks tied in jnp, a `lax.scan` over them (a call `chunk_plan`
    does not take; the kernels' oracle): W over Q e^G [nc, B, H, 2C, D], U
    [nc, B, H, C, Dv] f32, the lower q-k scores [nc, B, H, C, C],
    K e^(G_last - G) [nc, B, H, C, D], e^G_last [nc, B, H, D, 1] f32 -> (o
    [B, L, H, Dv] f32, the final state [B, H, D, Dv] f32, the largest |S| a
    chunk ends in)."""
    nc, B, H, C, Dv = u.shape
    f32, dt = jnp.float32, wq.dtype

    def carry(state, step):
        S, largest = state
        wq_c, u_c, qk_c, k_c, keep_c = step
        Sd = S.astype(dt)
        both = jnp.einsum("bhtd,bhdv->bhtv", wq_c, Sd,
                          preferred_element_type=f32)
        new = u_c - both[..., :C, :]
        newd = new.astype(dt)
        o = both[..., C:, :] + jnp.einsum(
            "bhts,bhsv->bhtv", qk_c, newd, preferred_element_type=f32)
        S = keep_c * S + jnp.einsum("bhsd,bhsv->bhdv", k_c, newd,
                                    preferred_element_type=f32)
        return (S, jnp.maximum(largest, jnp.max(jnp.abs(
            lax.stop_gradient(S))))), o

    (final, largest), o = lax.scan(
        carry, (jnp.zeros((B, H, k_out.shape[-1], Dv), f32),
                jnp.zeros((), f32)), (wq, u, qk, k_out, keep))
    # [nc, B, H, C, Dv] -> [B, L, H, Dv]
    return o.transpose(1, 0, 3, 2, 4).reshape(B, nc * C, H, Dv), final, \
        largest


def _scan_kernel(wq_ref, u_ref, qk_ref, ko_ref, keep_ref, o_ref, s_ref,
                 top_ref, *saved, chunk):
    """`hvd_kda_scan`: chunk c = the grid's last index of `heads` heads, the
    state S [heads, D, Dv] f32 resident in VMEM over a head's chunks (the
    final state's block, zeroed at c = 0, written out when the heads
    change). A head's W over Q e^G [2C, D], U [C, Dv] f32, lower q-k scores
    [C, C], K e^(G_last - G) [C, D] and the row c of e^G_last [nc, D] ->
    the chunk's rows of o [C, heads, Dv] f32 as the mixer reads them, the
    largest |S| a chunk ends in (eight rows a head: reduced outside) and,
    for the backward rule, S at the chunk's START. The heads' bodies are
    independent straight-line code: one's products fill another's wait."""
    C, narrow = chunk, wq_ref.dtype
    heads, D, Dv = s_ref.shape
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        top_ref[...] = jnp.zeros_like(top_ref)

    for h in range(heads):
        S = s_ref[h]
        if saved:
            saved[0][h] = S
        both = _narrow(wq_ref[h], S.astype(narrow))              # [2C, Dv]
        newd = (u_ref[h] - both[:C]).astype(narrow)
        o_ref[:, h, :] = both[C:] + _narrow(qk_ref[h], newd)
        S = _column(keep_ref[h, pl.ds(c, 1), :]) * S \
            + _narrow(ko_ref[h], newd, ((0,), (0,)))
        s_ref[h] = S
        top_ref[h] = jnp.maximum(top_ref[h], jnp.max(
            jnp.abs(S).reshape(D // 8, 8, Dv), axis=0))


def _scan_bwd_kernel(wq_ref, u_ref, qk_ref, ko_ref, keep_ref, s_ref, do_ref,
                     dfinal_ref, dwq_ref, du_ref, dqk_ref, dko_ref,
                     dkeep_ref, ds_ref, *, chunk):
    """`hvd_kda_scan_bwd`: the chunks in reverse, the state's cotangent dS
    [heads, D, Dv] f32 in VMEM scratch (the final state's cotangent at the
    last chunk). From a chunk's rows of do, the S it started from (saved)
    and the five operands, V' again by one product:

        dV'   = qk^T do + K_out dS        dqk = do V'^T    dK_out = V' dS^T
        dkeep = sum_v dS (.) S            dU  = dV'
        d(W over Q e^G) = [-dV' ; do] S^T
        dS    = keep (.) dS + (W over Q e^G)^T [-dV' ; do]

    Operands of a product rounded to the model's dtype, f32 accumulation."""
    C, narrow = chunk, wq_ref.dtype
    heads, D, Dv = ds_ref.shape
    step = pl.program_id(2)
    c = keep_ref.shape[1] - 1 - step
    across, down = ((1,), (1,)), ((0,), (0,))

    @pl.when(step == 0)
    def _():
        ds_ref[...] = dfinal_ref[...]

    for h in range(heads):
        S, dS = s_ref[h], ds_ref[h]
        Sd, dSd = S.astype(narrow), dS.astype(narrow)
        wq, qk, ko = wq_ref[h], qk_ref[h], ko_ref[h]
        newd = (u_ref[h] - _narrow(wq[:C], Sd)).astype(narrow)
        do = do_ref[:, h, :]
        dod = do.astype(narrow)
        dnew = _narrow(qk, dod, down) + _narrow(ko, dSd)         # [C, Dv]
        du_ref[h] = dnew
        dqk_ref[h] = _narrow(dod, newd, across).astype(dqk_ref.dtype)
        dko_ref[h] = _narrow(newd, dSd, across).astype(dko_ref.dtype)
        at = (h, pl.ds(c, 1), slice(None))
        dkeep_ref[at] = _row(jnp.sum(dS * S, axis=1, keepdims=True))
        dboth = jnp.concatenate([-dnew, do], axis=0).astype(narrow)
        dwq_ref[h] = _narrow(dboth, Sd, across).astype(dwq_ref.dtype)
        ds_ref[h] = _column(keep_ref[at]) * dS + _narrow(wq, dboth, down)


@functools.partial(jax.jit, static_argnames=("heads", "save", "interpret"))
def _pallas_scan(wq, u, qk, k_out, keep, cot, saved, heads, save, interpret):
    """The scan's kernels on the chunk stage's results as `hvd_kda_wy` writes
    them (`_chunk_stage_kernels`), `heads` heads a grid step. With `cot`
    None the forward one -> (o [B, L, H, Dv] f32, the final state [B, H, D,
    Dv] f32, the largest |S| a chunk ends in [B, H, 8, Dv]) and, where
    `save`, S at each chunk's start [nc, B, H, D, Dv] f32; with `cot` = (do
    [B, L, H, Dv] f32, the final state's cotangent) and `saved` the backward
    one -> the five operands' cotangents. o and do are the mixer's 4-D
    arrays as the TPU lays them, tiled by (heads, Dv): a block is [C, heads,
    Dv] with a head a sublane of each token's tile (`heads` a multiple of 8,
    or all), written and read by sublane. As [B, L, H Dv], a head a block of
    columns, the kernels' time is the same and XLA re-lays the array around
    the mixer's gated head norm: 675.0 ms a step for 651.5 in the
    Kimi-Linear cell (my chip runs, PR 61)."""
    nc, B, H, C, Dv = u.shape
    D = k_out.shape[-1]
    f32 = jnp.float32
    leading = lambda t, d: pl.BlockSpec(  # noqa: E731
        (None, None, heads, t, d), lambda b, j, c: (c, b, j, 0, 0))
    a_head = lambda t, d: pl.BlockSpec(  # noqa: E731
        (None, heads, t, d), lambda b, j, c: (b, j, 0, 0))
    rows = pl.BlockSpec((None, C, heads, Dv), lambda b, j, c: (b, c, j, 0))
    operands = [leading(2 * C, D), leading(C, Dv), leading(C, C),
                leading(C, D), a_head(nc, D)]
    # A head's blocks of the backward kernel are 0.3 MB in two pipeline
    # buffers each, e^G_last and its cotangent whole a head 0.25 MB more.
    how = dict(grid=(B, H // heads, nc), interpret=interpret,
               compiler_params=pltpu.CompilerParams(
                   dimension_semantics=("parallel", "parallel",
                                        "arbitrary"),
                   vmem_limit_bytes=min(16 + 3 * heads, 96) << 20))
    states = jax.ShapeDtypeStruct((nc, B, H, D, Dv), f32)
    if cot is None:
        return pl.pallas_call(
            functools.partial(_scan_kernel, chunk=C),
            name=profile.KDA_SCAN, in_specs=operands,
            out_specs=[rows, a_head(D, Dv), a_head(8, Dv)]
            + [leading(D, Dv)] * save,
            out_shape=[jax.ShapeDtypeStruct((B, nc * C, H, Dv), f32),
                       jax.ShapeDtypeStruct((B, H, D, Dv), f32),
                       jax.ShapeDtypeStruct((B, H, 8, Dv), f32)]
            + [states] * save, **how)(wq, u, qk, k_out, keep)
    # the chunks in reverse: grid step c is chunk nc - 1 - c
    back = lambda spec: pl.BlockSpec(  # noqa: E731
        spec.block_shape, lambda b, j, c: spec.index_map(b, j, nc - 1 - c))
    operands = [back(spec) for spec in operands]
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=C),
        name=profile.KDA_SCAN_BWD,
        in_specs=operands + [back(leading(D, Dv)), back(rows),
                             a_head(D, Dv)],
        out_specs=operands,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (wq, u, qk, k_out, keep)],
        scratch_shapes=[pltpu.VMEM((heads, D, Dv), f32)],
        **how)(wq, u, qk, k_out, keep, saved, *cot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(wq, u, qk, k_out, keep, heads, interpret):
    return tuple(_pallas_scan(wq, u, qk, k_out, keep, None, None, heads,
                              False, interpret))


def _scan_kernels_fwd(wq, u, qk, k_out, keep, heads, interpret):
    *out, saved = _pallas_scan(wq, u, qk, k_out, keep, None, None, heads,
                               True, interpret)
    return tuple(out), ((wq, u, qk, k_out, keep), saved)


def _scan_kernels_bwd(heads, interpret, res, cot):
    operands, saved = res
    # the largest |S| is a counter: its cotangent is no part of the rule
    return tuple(_pallas_scan(*operands, tuple(cot[:2]), saved, heads, False,
                              interpret))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def scan_heads(H):
    """The heads a grid step of `hvd_kda_scan` / `hvd_kda_scan_bwd` takes:
    `SCAN_HEADS` (a tile's eight sublanes of o [B, L, H, Dv]) where that
    divides the heads, else all of them."""
    return SCAN_HEADS if H % SCAN_HEADS == 0 else H


def kda_chunked(q, k, v, g, beta, chunk=64, sub=16, interpret=None):
    """q, k [B, L, H, D] (k of norm 1 a head; q carries its scale), v
    [B, L, H, Dv]; g [B, L, H, D] f32, <= 0 (the log of the decay); beta
    [B, L, H] f32; L a multiple of `chunk`, `chunk` of `sub`; `interpret`:
    `own_block_scores`'s. Returns (o [B, L, H, Dv] f32, the final state
    [B, H, D, Dv] f32, the largest |S| a chunk ends in, an f32 scalar: a
    counter, no part of a program that does not read it)."""
    B, L, H, D = k.shape
    Dv = v.shape[-1]
    if L % chunk or chunk % sub:
        raise ValueError("kda_chunked: length %d is no multiple of the chunk "
                         "%d, or the chunk of its sub-block %d"
                         % (L, chunk, sub))
    block = chunk_plan(B, L, H, D, Dv, chunk, sub, interpret)
    with jax.named_scope(profile.KDA_CHUNK):
        if block is None:
            steps = _chunk_stage_jnp(q, k, v, g, beta, chunk, sub, interpret)
        else:
            steps = _chunk_stage_kernels(q, k, v, g, beta, chunk, sub, block,
                                         bool(interpret))
    with jax.named_scope(profile.KDA_CARRY):
        if block is None:
            return _scan_jnp(*(jnp.moveaxis(t, 2, 0) for t in steps))
        o, final, top = _scan_kernels(*steps, scan_heads(H), bool(interpret))
        return o, final, lax.stop_gradient(jnp.max(top))
