"""The per-token statistic of a hyper-connection and its projection, from
ONE read of the streams (Pallas, TPU): what `models/transformer.py::hc_maps`
derives from a full pass over X [n, ..., T, C] (n residual streams, T tokens
a sequence, C wide; mHC, arXiv:2512.24880),

    sumsq[t]   = sum over the n * C values of token t of x^2          f32
    proj[t, k] = sum_i X[i, t, :] . phi[i*C:(i+1)*C, k]               f32

with phi [n*C, K], K = 2n + n^2 columns. The RMS norm's factor is a scalar a
token, so it comes out of the product: `hc_maps` forms
`rsqrt(sumsq / (n C) + eps) * proj`, and the normalised streams are never
made.

Why kernels (my chip runs, PR 35; PERF.md §6): XLA formed the sum as a
reduction over the stream (major) and the lane (minor) dimension at once
with a transposed copy of X written beside it for the projection, and for
phi's gradient X^T dproj it wrote X transposed once more (0.36 ms) before a
product of 0.17. Two kernels, each one read of X at the memory bandwidth
(0.16 ms for 117 MB), a tile of tokens a grid step:

- `hvd_hc_stat`: a tile's [n, rows, C] block is read once; its squares are
  added lane-wise into an f32 [rows, 128] accumulator over the C / 128
  column groups (VPU adds only) and reduced across the lanes once at the
  end; the same block goes through the MXU against phi (X's dtype, f32
  accumulation). The one output is [..., T, 128-multiple] f32: the K
  columns of the projection and, in the next column, the sum of squares,
  so that every store is lane-dense.
- `hvd_hc_stat_dphi`, in the backward rule: X^T dproj summed over the
  tiles into an f32 [n, C, 128-multiple] block that stays in VMEM.

X's own gradient, `2 dsumsq X + dproj phi^T`, is jnp: XLA fuses it into
the sum of X's other cotangents, which reads and writes the streams anyway.

`hc_plan` says which path a call takes, from the shapes alone; the kernels
run what it returns. Where C is no multiple of 128 or no tile fits the
VMEM budget, and on a backend that is no TPU, the same results come from
jnp (per-stream sums over the minor dimension, added afterwards; plain
products), unless `interpret=True` asks for the kernels in Pallas'
interpreter (the tests do).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile

# Tokens of a tile, the block one grid step reads: its squares are summed
# in one [128, 128] f32 accumulator, 16 of the 64 vector registers. On the
# v5e at [4, 4096, 3584] bf16 (my chip run, PR 35; examples/hc_stat_sweep.py;
# ms an evaluation): 128 tokens 0.185, 256 0.189, 512 0.199 (a larger first
# tile is a longer fetch that nothing hides); 117 MB at the bandwidth: 0.143.
BLOCK_ROWS = 128
# What a kernel's blocks may take of VMEM: X's tile, phi or its gradient
# and the tile of the projection or its cotangent, each held twice (the
# pipeline's two buffers).
VMEM_BUDGET_BYTES = 48 << 20
_VMEM_LIMIT_BYTES = 64 << 20   # of the v5e's 128 MiB
_LANES = 128


def _columns(K):
    """Width of the kernels' narrow operands: the K columns of the
    projection, one more for the sum of squares, rounded up to whole
    lanes."""
    return -(-(K + 1) // _LANES) * _LANES


def _block_bytes(n, rows, C, K, itemsize):
    """What holds the blocks of either kernel: X's tile, and the two narrow
    blocks counted in f32 (phi's gradient and the forward's output are;
    phi and the cotangent's tile are in X's dtype)."""
    return (2 * n * rows * C * itemsize
            + 2 * (n * C + rows) * _columns(K) * 4)


def hc_plan(n, T, C, K, dtype=jnp.bfloat16, hc_remat=False,
            block_remat=False):
    """How `hc_stat` runs a call on X [n, ..., T, C] in `dtype` against phi
    [n*C, K] (T: the tokens of the last axis before C, a sequence's; the
    axes before it are grid axes, a tile never crosses them), and how often
    a training step makes it for one connection
    (`hvd.profile.hc_plan`; the op runs what this returns, so it needs no
    chip):

        {"path": "kernel" (where a TPU runs it) or "jnp",
         "rows": a tile's tokens, "steps": grid steps a sequence takes,
         "vmem_bytes": what either kernel's blocks take of VMEM at most,
         "passes": full passes over X one evaluation makes,
         "evaluations": evaluations a training step makes}

    `rows` is the largest divisor of T up to `BLOCK_ROWS` that is a whole
    number of the dtype's sublane tiles and whose blocks fit
    `VMEM_BUDGET_BYTES`; with none, or where C is no multiple of 128, the
    path is "jnp": a pass for the sums and one for the projection. The
    backward's kernel walks the same tiles.
    `hc_remat`, `block_remat`: whether the connection is recomputed in the
    backward pass, and whether its block's forward is run again there
    (`models/transformer.py`). Both recomputations keep the statistic and
    the projection by name, so a step evaluates them once whatever is
    run again; without a recomputation the backward pass reads what the
    forward kept anyway.
    """
    del hc_remat, block_remat  # once a step either way: see above
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    fits = [r for r in range(sublanes, min(T, BLOCK_ROWS) + 1, sublanes)
            if T % r == 0 and _block_bytes(n, r, C, K, itemsize)
            <= VMEM_BUDGET_BYTES]
    if C % _LANES or not fits:
        return {"path": "jnp", "rows": T, "steps": 0, "vmem_bytes": 0,
                "passes": 2, "evaluations": 1}
    return {"path": "kernel", "rows": fits[-1], "steps": T // fits[-1],
            "vmem_bytes": _block_bytes(n, fits[-1], C, K, itemsize),
            "passes": 1, "evaluations": 1}


def _stat_kernel(x_ref, phi_ref, out_ref, *, K):
    n, rows, C = x_ref.shape
    proj = jnp.zeros(out_ref.shape, jnp.float32)
    acc = jnp.zeros((rows, _LANES), jnp.float32)
    for i in range(n):
        proj += lax.dot_general(x_ref[i], phi_ref[i],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        for c in range(0, C, _LANES):
            x = x_ref[i, :, pl.ds(c, _LANES)].astype(jnp.float32)
            acc += x * x
    # phi's columns past K are zeros, so is the projection there: the sum
    # of squares takes the first of them.
    lane = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == K, jnp.sum(acc, axis=1, keepdims=True),
                             proj)


def _pallas_stat(X, phi, plan, interpret):
    """X [n, B, T, C], phi [n, C, K] in X's dtype -> [B, T, _columns(K)]
    f32. The streams are taken as they are, four-dimensional: a reshape to
    [n, B T, C] made XLA write them a second time for the kernel."""
    n, B, T, C = X.shape
    K = phi.shape[2]
    cols, rows = _columns(K), plan["rows"]
    phi = jnp.pad(phi, ((0, 0), (0, 0), (0, cols - K)))
    return pl.pallas_call(
        functools.partial(_stat_kernel, K=K),
        name=profile.HC_STAT,
        grid=(B, plan["steps"]),
        in_specs=[pl.BlockSpec((n, None, rows, C),
                               lambda b, t: (0, b, t, 0)),
                  pl.BlockSpec((n, C, cols), lambda b, t: (0, 0, 0))],
        out_specs=pl.BlockSpec((None, rows, cols), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(X, phi)


def _kernel_plan(X, K, interpret):
    """`hc_plan` of a call on X [n, B, T, C] where the kernels run it, None
    where jnp does."""
    n, _, T, C = X.shape
    plan = hc_plan(n, T, C, K, X.dtype)
    if plan["path"] == "jnp" or (interpret is None
                                 and jax.default_backend() != "tpu"):
        return None
    return plan


def _forward(X, phi, interpret):
    n, C = X.shape[0], X.shape[-1]
    K = phi.shape[1]
    phi = phi.reshape(n, C, K).astype(X.dtype)
    plan = _kernel_plan(X, K, interpret)
    if plan is None:
        xf = X.astype(jnp.float32)
        sumsq = sum(jnp.sum(xf[i] * xf[i], axis=-1) for i in range(n))
        proj = jnp.einsum("nbtc,nck->btk", X, phi,
                          preferred_element_type=jnp.float32)
        return sumsq, proj
    out = _pallas_stat(X, phi, plan, bool(interpret))
    return out[..., K], out[..., :K]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _stat(X, phi, interpret):
    return _forward(X, phi, interpret)


def _stat_fwd(X, phi, interpret):
    return _forward(X, phi, interpret), (X, phi)


def _dphi_kernel(x_ref, g_ref, out_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    for i in range(x_ref.shape[0]):
        out_ref[i] += lax.dot_general(x_ref[i], g_ref[...],
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)


def _pallas_dphi(X, d_proj, plan, interpret):
    """X [n, B, T, C], d_proj [B, T, K] f32 -> X^T d_proj [n, C, K] f32,
    from one read of the streams: XLA's own form first writes them
    transposed. The cotangent enters the MXU in X's dtype, as the TPU's
    default precision takes an f32 operand anyway."""
    n, B, T, C = X.shape
    K = d_proj.shape[2]
    cols, rows = _columns(K), plan["rows"]
    g = jnp.pad(d_proj, ((0, 0), (0, 0), (0, cols - K))).astype(X.dtype)
    out = pl.pallas_call(
        _dphi_kernel,
        name=profile.HC_STAT_DPHI,
        grid=(B, plan["steps"]),
        in_specs=[pl.BlockSpec((n, None, rows, C),
                               lambda b, t: (0, b, t, 0)),
                  pl.BlockSpec((None, rows, cols), lambda b, t: (b, t, 0))],
        out_specs=pl.BlockSpec((n, C, cols), lambda b, t: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, C, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(X, g)
    return out[..., :K]


def _stat_bwd(interpret, res, g):
    """dX = 2 dsumsq X + dproj phi^T in jnp, the cotangents f32 into the
    products as autodiff of the einsum leaves them; dphi = X^T dproj by
    the kernel where the forward took its own."""
    X, phi = res
    n, C = X.shape[0], X.shape[-1]
    d_sumsq, d_proj = g
    phi_x = phi.reshape(n, C, -1).astype(X.dtype)
    d_x = lax.dot_general(d_proj, phi_x, (((2,), (2,)), ((), ())),
                          preferred_element_type=jnp.float32)  # [B, T, n, C]
    d_x = (jnp.moveaxis(d_x, 2, 0)
           + 2.0 * d_sumsq[None, ..., None] * X.astype(jnp.float32))
    plan = _kernel_plan(X, d_proj.shape[2], interpret)
    if plan is None:
        d_phi = lax.dot_general(X, d_proj, (((1, 2), (0, 1)), ((), ())),
                                preferred_element_type=jnp.float32)
    else:
        d_phi = _pallas_dphi(X, d_proj, plan, bool(interpret))
    return d_x.astype(X.dtype), d_phi.reshape(phi.shape).astype(phi.dtype)


_stat.defvjp(_stat_fwd, _stat_bwd)


def hc_stat(X, phi, interpret=None):
    """(sumsq [..., T] f32, proj [..., T, K] f32) of the streams X
    [n, ..., T, C] and phi [n*C, K] (vec(X) stream-major): each token's sum
    of squares over its n * C values, and its projection onto phi's
    columns, the products in X's dtype with f32 accumulation.

    `interpret`: None takes the kernels on a TPU where `hc_plan` finds a
    tile and jnp elsewhere; True runs them in Pallas' interpreter."""
    lead = X.shape[1:-1]
    sumsq, proj = _stat(X.reshape(X.shape[:1] + (-1,) + X.shape[-2:]), phi,
                        interpret)
    return sumsq.reshape(lead), proj.reshape(lead + proj.shape[-1:])
