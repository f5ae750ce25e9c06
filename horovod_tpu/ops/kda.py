"""Kimi Delta Attention's recurrence in its chunked form (Kimi Linear,
arXiv:2510.26692: a gated delta rule whose decay is a vector a head and
token): jnp around two Pallas kernels.

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

A, the solve, W and U are made for all chunks at once; the L / C chunks are
tied by a `lax.scan` whose body is the last three lines. Never a loop over
the tokens, never an [L, L] array.

The decayed scores do not factor into one product: exp(G_t) exp(-G_s) has a
factor that overflows (64 tokens at alpha = 1e-3 are e^442). They are made
by GLA's secondary chunking (arXiv:2312.06635 s4.3) in sub-blocks of `sub`
tokens: a block of rows against the tokens BEFORE it through the decay at
the block's first token r (exp(G_t - G_r) on the row, exp(G_r - G_s) on the
key: both exponents <= 0, one product), and a block against itself term by
term (the exponent masked to -inf above the diagonal before the
exponential). No exponential ever takes a positive sum. The term-by-term
part is two Pallas kernels under one rule, `hvd_kda_scores` and
`hvd_kda_scores_bwd` (`own_block_scores`): in jnp its [sub, sub, D] terms
are 2 GiB a layer at 8192 tokens x 32 heads and the backward pass writes
them out several times over; a kernel holds a few sub-blocks' terms in
registers, a column of the scores at a time, and writes [sub, sub]. On a
backend that is no TPU the same numbers come from jnp (`interpret=True`
runs the kernels in Pallas' interpreter).

Cumulative decays, the term-by-term blocks, the solve and the carried state
are f32; the operands of the products are rounded to the dtype of `v` (bf16
in training) and accumulate in f32, as `ssd_scan`'s. Everything before the
scan lies under the scope `hvd_kda_chunk` (the two kernels too), the scan
under `hvd_kda_carry`.
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


def _own_scores_kernel(q_ref, k_ref, g_ref, pq_ref, pk_ref, *, sub, group):
    """`hvd_kda_scores`: a block of sub-blocks, `group` at a time; a column
    u of a sub-block's [sub, sub] scores from one [sub, D] product reduced
    over the lanes."""
    f32 = jnp.float32

    def some(i, _):
        at = pl.ds(i * group, group)
        q, k, G = (q_ref[at].astype(f32), k_ref[at].astype(f32), g_ref[at])
        cols = lax.broadcasted_iota(jnp.int32, (group, sub, sub), 2)
        pq = pk = jnp.zeros((group, sub, sub), f32)
        for u in range(sub):
            e = _decay_to(G, u) * k[:, u:u + 1, :]
            pq = jnp.where(cols == u, jnp.sum(q * e, -1, keepdims=True), pq)
            pk = jnp.where(cols == u, jnp.sum(k * e, -1, keepdims=True), pk)
        pq_ref[at] = pq
        pk_ref[at] = pk

    lax.fori_loop(0, q_ref.shape[0] // group, some, None)


def _own_scores_bwd_kernel(q_ref, k_ref, g_ref, dpq_ref, dpk_ref,
                           dq_ref, dk_ref, dg_ref, *, sub, group):
    """`hvd_kda_scores_bwd`: with e_u = exp(G_t - G_u) k_u and a_u, b_u the
    column u of the two cotangents, dq = sum_u a_u e_u; the rows' part of dk
    = sum_u b_u e_u; the keys' part of dk, row u, = sum_t (a_u q + b_u k)
    exp(G_t - G_u); dG = q dq + k (rows' part) - k (keys' part)."""
    f32 = jnp.float32

    def some(i, _):
        at = pl.ds(i * group, group)
        q, k, G = (q_ref[at].astype(f32), k_ref[at].astype(f32), g_ref[at])
        dpq, dpk = dpq_ref[at], dpk_ref[at]
        rows = lax.broadcasted_iota(jnp.int32, G.shape, 1)
        dq = dk_rows = dk_keys = jnp.zeros(G.shape, f32)
        for u in range(sub):
            decay = _decay_to(G, u)
            e = decay * k[:, u:u + 1, :]
            a, b = dpq[:, :, u:u + 1], dpk[:, :, u:u + 1]
            dq = dq + a * e
            dk_rows = dk_rows + b * e
            dk_keys = jnp.where(rows == u, jnp.sum(
                (a * q + b * k) * decay, axis=1, keepdims=True), dk_keys)
        dq_ref[at] = dq.astype(dq_ref.dtype)
        dk_ref[at] = (dk_rows + dk_keys).astype(dk_ref.dtype)
        dg_ref[at] = q * dq + k * (dk_rows - dk_keys)

    lax.fori_loop(0, q_ref.shape[0] // group, some, None)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas_own(q, k, G, cot, block, interpret):
    """The forward kernel on q, k [N, sub, D], G [N, sub, D] f32 -> (pq, pk)
    [N, sub, sub] f32; with `cot` = (dpq, dpk) the backward kernel -> (dq,
    dk, dG)."""
    N, sub, D = G.shape
    wide = pl.BlockSpec((block, sub, D), lambda i: (i, 0, 0))
    square = pl.BlockSpec((block, sub, sub), lambda i: (i, 0, 0))
    how = dict(grid=(N // block,), interpret=interpret,
               compiler_params=pltpu.CompilerParams(
                   dimension_semantics=("parallel",)))
    group = GROUP if block % GROUP == 0 else 1
    if cot is None:
        return pl.pallas_call(
            functools.partial(_own_scores_kernel, sub=sub, group=group),
            name=profile.KDA_SCORES, in_specs=[wide] * 3,
            out_specs=[square] * 2,
            out_shape=[jax.ShapeDtypeStruct((N, sub, sub), jnp.float32)] * 2,
            **how)(q, k, G)
    return pl.pallas_call(
        functools.partial(_own_scores_bwd_kernel, sub=sub, group=group),
        name=profile.KDA_SCORES_BWD, in_specs=[wide] * 3 + [square] * 2,
        out_specs=[wide] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(G.shape, jnp.float32)],
        **how)(q, k, G, *cot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _own_kernels(q, k, G, block, interpret):
    return tuple(_pallas_own(q, k, G, None, block, interpret))


def _own_kernels_fwd(q, k, G, block, interpret):
    return _own_kernels(q, k, G, block, interpret), (q, k, G)


def _own_kernels_bwd(block, interpret, res, cot):
    return tuple(_pallas_own(*res, tuple(cot), block, interpret))


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
    nc, f32, dt = L // chunk, jnp.float32, v.dtype

    def by_chunk(t):  # [B, L, H, d] -> [B, H, nc, C, d]
        return t.reshape(B, nc, chunk, H, -1).transpose(0, 3, 1, 2, 4)

    with jax.named_scope(profile.KDA_CHUNK):
        qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
        G = chunk_cumsum(by_chunk(g.astype(f32)))
        bc = by_chunk(beta.astype(f32)[..., None])             # [.., C, 1]
        scores = decayed_scores(qc, kc, G, sub, interpret)
        qk, kk = scores[..., 0, :, :], scores[..., 1, :, :]
        rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        unit = jnp.where(rows > cols, bc * kk, 0.0) \
            + (rows == cols).astype(f32)                       # I + A
        decayed = jnp.exp(G)
        wu = lax.linalg.triangular_solve(
            unit, bc * jnp.concatenate(
                [kc.astype(f32) * decayed, vc.astype(f32)], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
        w, u = wu[..., :D].astype(dt), wu[..., D:]
        last = G[..., -1:, :]
        # W and the decayed queries meet the carried state in ONE product.
        wq = jnp.concatenate([w, (qc.astype(f32) * decayed).astype(dt)],
                             axis=-2)                          # [.., 2C, D]
        k_out = (kc.astype(f32) * jnp.exp(last - G)).astype(dt)
        keep = jnp.exp(last[..., 0, :])[..., None]             # [.., D, 1]
        qk = qk.astype(dt)

    def carry(state, step):
        S, largest = state
        wq_c, u_c, qk_c, k_c, keep_c = step
        Sd = S.astype(dt)
        both = jnp.einsum("bhtd,bhdv->bhtv", wq_c, Sd,
                          preferred_element_type=f32)
        new = u_c - both[..., :chunk, :]
        newd = new.astype(dt)
        o = both[..., chunk:, :] + jnp.einsum(
            "bhts,bhsv->bhtv", qk_c, newd, preferred_element_type=f32)
        S = keep_c * S + jnp.einsum("bhsd,bhsv->bhdv", k_c, newd,
                                    preferred_element_type=f32)
        return (S, jnp.maximum(largest, jnp.max(jnp.abs(
            lax.stop_gradient(S))))), o

    with jax.named_scope(profile.KDA_CARRY):
        (final, largest), o = lax.scan(
            carry, (jnp.zeros((B, H, D, Dv), f32), jnp.zeros((), f32)),
            tuple(jnp.moveaxis(t, 2, 0)
                  for t in (wq, u, qk, k_out, keep)))
        # [nc, B, H, C, Dv] -> [B, L, H, Dv]
        o = o.transpose(1, 0, 3, 2, 4).reshape(B, L, H, Dv)
    return o, final, largest
