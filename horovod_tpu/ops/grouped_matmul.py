"""Grouped matmul over contiguous ragged groups of rows (Pallas, TPU): the
experts of a dropless mixture-of-experts layer (`parallel/expert.py`).

    out[r] = lhs[r] @ rhs[g(r)]      lhs [M, K], rhs [G, K, N], out [M, N]

where the rows of group g are the `group_sizes[g]` rows after those of the
groups before it (an empty group is legal; rows past the sum belong to no
group and their output is undefined). Three kernels, named for the profiler
(`hvd.profile`): the forward product, the gradient of the rows (the same
kernel with the matrices transposed) and the gradient of the matrices.

How the raggedness is met (the design of megablox's `gmm`, jax
`experimental/pallas/ops/tpu/megablox`, written anew here): the rows are cut
into tiles of `BLOCK_ROWS`; a tile that a group boundary crosses is VISITED
once for each group in it, and a mask keeps each visit to its own rows. The
list of visits (group, tile) is built from the group sizes by a few vector
operations outside the kernel and handed to it as prefetched scalars, which
the block index maps read; its length is static (tiles + groups), the visits
past the real ones repeat the last one and do nothing.

What this kernel does that neither megablox nor `lax.ragged_dot` does
(PERF.md §6, PR 26, has the chip's numbers for all three): it reads the
matrices in the dtype the parameters are kept in (f32) and rounds a block to
the rows' dtype (bf16) in VMEM, and it writes the matrices' gradient in f32
straight from its f32 accumulator. So a train step holds no bf16 copy of the
expert weights and no bf16 gradient of them, and the four passes over
device memory that make and convert those are gone. A matrix block is the
whole [K, N] of a group where that fits (`_cols_block`), fetched once per
group: consecutive visits of one group leave the block index unchanged.

On a backend that is no TPU the product is `lax.ragged_dot`, unless
`interpret=True` asks for the kernels in Pallas' interpreter (the tests do).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile

# Rows of a tile, and of the parts of a tile: a visit computes only the parts
# that hold a row of its group (the MXU's 128 rows at least; the matrices'
# gradient adds every part's product into an 8 MB block, so its parts are
# larger). Of the sizes tried on the v5e at 32768 rows in 64 groups (PERF.md
# §6, PR 26) these were the fastest: larger tiles mean fewer visits.
BLOCK_ROWS = 1024
SUB_ROWS = 128
SUB_ROWS_DRHS = 256
# A matrix block [K, cols] in the parameters' dtype may take this much; it is
# held twice (the pipeline's two buffers) beside the row and result blocks.
_MATRIX_BLOCK_BYTES = 8 << 20
_VMEM_LIMIT_BYTES = 64 << 20   # of the v5e's 128 MiB


def _cols_block(k, n, itemsize):
    """The widest block of columns [k, cols] within `_MATRIX_BLOCK_BYTES`:
    all `n`, or the largest multiple of 128 that divides `n`."""
    if k * n * itemsize <= _MATRIX_BLOCK_BYTES:
        return n
    cols = (_MATRIX_BLOCK_BYTES // (k * itemsize)) // 128 * 128
    while cols >= 128 and n % cols:
        cols -= 128
    if cols < 128:
        raise ValueError("grouped_matmul: no block of [%d, %d] matrices "
                         "fits %d bytes" % (k, n, _MATRIX_BLOCK_BYTES))
    return cols


def visits(group_sizes, rows, block, visit_empty=False):
    """The visits of `rows` rows (a multiple of `block`) in tiles of
    `block`, for groups of `group_sizes` [G]: (starts [G + 1]: the row each
    group starts at, and the end of the last; group [V] and tile [V] of
    visit v, ordered by group, then tile; total [1]: how many are real).
    V = rows / block + G. With `visit_empty` an empty group gets one visit
    (of a tile in which it has no row), so that a kernel can zero what it
    owns."""
    return _visits(_groups_tiles(group_sizes, rows, block), visit_empty)


def _groups_tiles(group_sizes, rows, block):
    """What both forms of `visits` share: (sizes, ends, each group's first
    and last tile, the tiles)."""
    tiles = rows // block
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = jnp.minimum((ends - sizes) // block, tiles - 1)
    last = jnp.minimum((ends - 1) // block, tiles - 1)
    return sizes, ends, first, last, tiles


def _visits(groups, visit_empty):
    sizes, ends, first, last, tiles = groups
    G = sizes.shape[0]
    V = tiles + G
    count = jnp.where(sizes > 0, last - first + 1, int(visit_empty))
    upto = jnp.cumsum(count)
    total = upto[-1:]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    group = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1),
                        G - 1).astype(jnp.int32)
    tile = first[group] + v - (upto[group] - count[group])
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return starts, group, tile.astype(jnp.int32), total.astype(jnp.int32)


def _for_own_sub_blocks(starts_ref, group, tile, block, sub, cols, body):
    """Calls `body(rows, mine)` for each `sub`-row part of `tile` that holds
    a row of `group`: `rows` the part's slice of the tile, `mine`
    [sub, cols] bool its rows that are the group's. The other parts cost
    nothing, so a visit's work follows the group's rows in the tile and
    not the tile."""
    lo, hi = starts_ref[group], starts_ref[group + 1]
    for part in range(block // sub):
        row0 = tile * block + part * sub

        @pl.when((row0 < hi) & (row0 + sub > lo))
        def _part():
            row = row0 + lax.broadcasted_iota(jnp.int32, (sub, cols), 0)
            body(pl.ds(part * sub, sub), (row >= lo) & (row < hi))


def _opens_group(group_ref, v):
    """Whether visit `v` is its group's first in this sweep of the visits."""
    return (v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group_ref[v])


def _gmm_kernel(starts_ref, group_ref, tile_ref, total_ref, lhs_ref, rhs_ref,
                out_ref, matrix_ref, *, block, sub, transpose_rhs):
    v = pl.program_id(1)
    real = v < total_ref[0]

    @pl.when(real & _opens_group(group_ref, v))
    def _round_the_matrix():
        # Once a group: its visits follow each other and keep the block.
        matrix_ref[...] = rhs_ref[...].astype(matrix_ref.dtype)

    @pl.when(real)
    def _visit():
        def part(rows, mine):
            product = lax.dot_general(
                lhs_ref[rows, :], matrix_ref[...],
                (((1,), (1 if transpose_rhs else 0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # The tile's other rows are another visit's, before or after
            # this one; what the block held before its first visit is
            # never kept, every row of a group being some visit's own.
            out_ref[rows, :] = jnp.where(mine, product.astype(out_ref.dtype),
                                         out_ref[rows, :])

        _for_own_sub_blocks(starts_ref, group_ref[v], tile_ref[v], block,
                            sub, out_ref.shape[1], part)


def _whole_tiles(rows, block):
    return -(-rows // block) * block


# The kernels' calls are jitted, as `ops/moe_rows.py`'s are: the nine calls
# of a gated layer (three products, and of each the rows' and the matrices'
# gradient) are six distinct ones, traced and lowered once for all the
# layers of a model, and each call site's scope path still reaches its
# call's `op_name`. Only the call is inside, with its pad and slice: the
# visits are vector work that XLA fuses and times, and an instruction it
# forms inside a shared function carries no call site's scopes. The tiles'
# rows are arguments: what a trace depends on is in its key.
@functools.partial(jax.jit, static_argnames=("transpose_rhs", "block", "sub",
                                             "interpret"))
def _gmm(lhs, rhs, meta, transpose_rhs, block, sub, interpret):
    """lhs [M, K] x rhs [G, K, N] -> [M, N] in lhs.dtype, the forward
    kernel; with `transpose_rhs` rhs is [G, N, K] and the kernel is the
    rows' gradient. Tiles of `block` rows in parts of `sub`; `meta`:
    `visits` of the rows in whole tiles."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rows = _whole_tiles(M, block)
    if rows != M:
        lhs = jnp.pad(lhs, ((0, rows - M), (0, 0)))
    cols = _cols_block(K, N, rhs.dtype.itemsize)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, cols, K),
                                lambda n, v, s, g, t, c: (g[v], n, 0))
    else:
        rhs_spec = pl.BlockSpec((None, K, cols),
                                lambda n, v, s, g, t, c: (g[v], 0, n))
    kernel = functools.partial(_gmm_kernel, block=block, sub=sub,
                               transpose_rhs=transpose_rhs)
    how = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // cols, meta[1].shape[0]),
            in_specs=[pl.BlockSpec((block, K),
                                   lambda n, v, s, g, t, c: (t[v], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((block, cols),
                                   lambda n, v, s, g, t, c: (t[v], n)),
            scratch_shapes=[pltpu.VMEM(
                (cols, K) if transpose_rhs else (K, cols), lhs.dtype)]),
        out_shape=jax.ShapeDtypeStruct((rows, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret)
    # One kernel body under two names: the profiler tells the forward
    # product from the rows' gradient by them.
    if transpose_rhs:
        call = pl.pallas_call(kernel, name=profile.MOE_GMM_DLHS, **how)
    else:
        call = pl.pallas_call(kernel, name=profile.MOE_GMM, **how)
    return call(*meta, lhs, rhs)[:M]


def _drhs_kernel(starts_ref, group_ref, tile_ref, total_ref, lhs_ref, g_ref,
                 out_ref, *, block, sub):
    v = pl.program_id(1)
    real = v < total_ref[0]

    @pl.when(real & _opens_group(group_ref, v))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(real)
    def _visit():
        def part(rows, mine):
            g = jnp.where(mine, g_ref[rows, :], jnp.zeros((), g_ref.dtype))
            out_ref[...] += lax.dot_general(
                lhs_ref[rows, :], g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _for_own_sub_blocks(starts_ref, group_ref[v], tile_ref[v], block,
                            sub, g_ref.shape[1], part)


@functools.partial(jax.jit, static_argnames=("block", "sub", "interpret"))
def _drhs(lhs, g, meta, block, sub, interpret):
    """The matrices' gradient: lhs [M, K], g [M, N] -> [G, K, N] f32, group
    g's the product of its rows of lhs, transposed, and of g. Tiles of
    `block` rows in parts of `sub`; `meta`: `visits` of the rows in whole
    tiles, the empty groups visited too."""
    M, K = lhs.shape
    N = g.shape[1]
    G = meta[0].shape[0] - 1  # the groups' starts, and the last one's end
    rows = _whole_tiles(M, block)
    if rows != M:
        lhs = jnp.pad(lhs, ((0, rows - M), (0, 0)))
        g = jnp.pad(g, ((0, rows - M), (0, 0)))
    cols = _cols_block(K, N, 4)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, block=block, sub=sub),
        name=profile.MOE_GMM_DRHS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // cols, meta[1].shape[0]),
            in_specs=[pl.BlockSpec((block, K),
                                   lambda n, v, s, g, t, c: (t[v], 0)),
                      pl.BlockSpec((block, cols),
                                   lambda n, v, s, g, t, c: (t[v], n))],
            out_specs=pl.BlockSpec((None, K, cols),
                                   lambda n, v, s, g, t, c: (g[v], 0, n))),
        out_shape=jax.ShapeDtypeStruct((G, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*meta, lhs, g)


def layer_visits(group_sizes, rows, interpret=None):
    """What the kernels behind `grouped_matmul` are told of `rows` rows in
    groups of `group_sizes`: (`visits` as the products and the rows'
    gradients take them, `visits` with the empty groups visited as the
    matrices' gradients do), in whole tiles of `BLOCK_ROWS`; None where the
    call is `lax.ragged_dot`'s. A routed layer has three products over the
    same rows and of each two gradients: it forms this ONCE and hands it to
    each (`meta=`), where every call formed its own anew, twenty small
    instructions traced 84 times for the SDAR cell's step. They stay out of
    the jitted calls (the comment above `_gmm`)."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    groups = _groups_tiles(group_sizes, _whole_tiles(rows, BLOCK_ROWS),
                           BLOCK_ROWS)
    return _visits(groups, False), _visits(groups, True)


def product(lhs, rhs, meta, interpret):
    """lhs [M, K] x rhs [G, K, N] -> [M, N], meta `layer_visits` of the M
    rows: the forward kernel alone (`hvd_moe_gmm`), for a caller that
    brings its own rule (`ops/moe_act.py`)."""
    return _gmm(lhs, rhs, meta[0], False, BLOCK_ROWS, SUB_ROWS, interpret)


def rows_gradient(g, rhs, meta, interpret):
    """g [M, N], rhs [G, K, N], meta `layer_visits` of the M rows ->
    [M, K]: the gradient by the rows of `grouped_matmul(lhs, rhs, ...)`
    from its result's cotangent (`hvd_moe_gmm_dlhs`)."""
    return _gmm(g, rhs, meta[0], True, BLOCK_ROWS, SUB_ROWS, interpret)


def matrices_gradient(lhs, g, meta, dtype, interpret):
    """lhs [M, K], g [M, N] -> [G, K, N] in `dtype`: the gradient by the
    matrices (`hvd_moe_gmm_drhs`). A row of `lhs` that belongs to no group
    is multiplied by zero where a part (`SUB_ROWS_DRHS`) also holds a row
    of the last group: it has to be finite there."""
    return _drhs(lhs, g, meta[1], BLOCK_ROWS, SUB_ROWS_DRHS,
                 interpret).astype(dtype)


def tile_sizes():
    """(`BLOCK_ROWS`, `SUB_ROWS`, `SUB_ROWS_DRHS`) as they stand: what a
    jitted function that calls the three above depends on beside its
    operands, for its key (the tests shrink them)."""
    return BLOCK_ROWS, SUB_ROWS, SUB_ROWS_DRHS


# A rule's backward is ONE jitted function, as its kernels' calls are: the
# layers of a model trace it once, and each layer binds one call where it
# bound one a kernel. `sizes` is `tile_sizes()`, read by the caller.
@functools.partial(jax.jit, static_argnames=("sizes", "interpret"))
def _gradients(lhs, rhs, meta, g, sizes, interpret):
    del sizes
    return (rows_gradient(g, rhs, meta, interpret),
            matrices_gradient(lhs, g, meta, rhs.dtype, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, meta, interpret):
    return product(lhs, rhs, meta, interpret)


def _grouped_fwd(lhs, rhs, meta, interpret):
    return product(lhs, rhs, meta, interpret), (lhs, rhs, meta)


def _grouped_bwd(interpret, res, g):
    lhs, rhs, meta = res
    return _gradients(lhs, rhs, meta, g, tile_sizes(), interpret) + (None,)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None, meta=None):
    """lhs [M, K] whose rows lie in contiguous groups of `group_sizes` [G]
    int32 (summing to M at most) times rhs [G, K, N], each group with its
    own matrix: [M, N] in lhs.dtype. The matrices may be kept in another
    dtype (f32 parameters under bf16 rows): they are rounded to lhs.dtype
    block by block, products accumulate in f32, and their gradient comes
    back in rhs.dtype.

    `interpret`: None takes the kernels on a TPU and `lax.ragged_dot`
    elsewhere; True runs the kernels in Pallas' interpreter. `meta`:
    `layer_visits(group_sizes, M, interpret)` where the caller has formed
    it for several calls over the same rows; formed here otherwise."""
    if interpret is None and jax.default_backend() != "tpu":
        return lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                              group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
    if meta is None:
        meta = layer_visits(group_sizes, lhs.shape[0], interpret)
    return _grouped(lhs, rhs, meta, bool(interpret))
