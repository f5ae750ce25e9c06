"""The rows of a routed layer that is told how many of them are live, moved
by that count (Pallas, TPU): the dispatch and the combine of the dropless
local path of `parallel/expert.py::moe_ffn`.

Such a layer sorts k*T assignments, turns the run of the experts it holds to
the front of a k*T-row buffer and multiplies `n_live` rows: a number the
router decides each step where the layer holds a part of the experts
(``held=``: an eighth of the buffer in the benchmark's cell `xing29b_1chip`),
and k*T itself where it holds them all (`olmoe1b7_1chip`). XLA's gathers,
selects and sums run over the buffer's static shape, at a third of the
memory's bandwidth; the grouped matmuls between them visit the live tiles
alone (`ops/grouped_matmul.py::visits`). These two kernels take the count as
a prefetched scalar and do the same for the shuffle around them:

- `hvd_moe_rows` (`rows_out`): ``out[s] = scale[s] * src[idx[s]]`` for
  ``s < n_live``; the last live tile is written WHOLE, zeros past the count,
  and the tiles behind it are never written. The zeros are there for the
  grouped matmul of the matrices' gradient, which multiplies every row of a
  part (`SUB_ROWS_DRHS`) that holds a row of the last group, by zero, so
  that a dead row there has to be finite: a tile is a whole number of such
  parts. Beside it, where asked, ``dots[s] = other[s] . src[idx[s]]`` in
  f32 from the same fetched rows (the weights' gradient).
- `hvd_moe_sum` (`rows_sum`): ``y[t] = sum of scale[s] * src[s]`` over the
  live rows s whose token is t, in f32, rounded once; `src` one array or
  the sum of several (the cotangents of the rows' uses, added here over the
  live tiles and not by XLA over all of them). Rows from `n_live` on are
  selected away before anything is added: a dead row is ZERO, never garbage
  times a zero weight.

How a row is moved. The buffer is the side that streams: a grid step takes
one tile of it through the pipeline, and a tile behind the last live one
repeats that one's block index, so nothing is fetched or written for it
(the idiom of `grouped_matmul.visits`). The [T, D] side (the tokens: the
source of `rows_out`, the sum of `rows_sum`) is resident in VMEM in f32, a
block of columns at a time (the grid's outer axis), and a scalar loop over
the tile's live rows copies or adds one row at a time between the two,
by a load and a store at a dynamic sublane. Not by one DMA a row from HBM,
as paged attention fetches its pages: Mosaic refuses a one-row slice of a
tiled operand in HBM ("Slice shape along dimension 0 must be aligned to
tiling (8)"), and a row of a bf16 array is half of each word of its tile.

`dispatch` and `combine` are the two differentiable ops `moe_ffn` calls;
each one's transpose is the other kernel. Where the layer's sort carried the
weights with the order (`combine`'s `carried`: a layer that holds a part of
the experts, `order_plan`), no [k*T] vector is gathered on either side of
the kernels: the weights arrive sorted, and their gradient goes back into
the assignments' order by a sort on the permutation (a gather of k*T scalars
costs the v5e 0.45 ms, a sort of them a tenth: `examples/moe_order_sweep.py`).
`rows_plan` says which path a call takes; the kernels run what it returns.
Where the width is no multiple of 128 or no tile divides the shapes, and on
a backend that is no TPU, the same results come from jnp (over all k*T rows,
as before), unless `interpret=True` asks for the kernels in Pallas'
interpreter (the tests do).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile
from horovod_tpu.ops import grouped_matmul

# Rows of a tile of the k*T-row buffer, and what the resident side's block
# of columns may take of VMEM ([T, cols]: the operand's block held twice by
# the pipeline, and its f32 twin). `examples/moe_rows_sweep.py` times the
# candidates on the chip (PERF.md §6, PR 37).
TILE_ROWS = 1024
RESIDENT_BYTES = 48 << 20
# Rows a pass of the scalar loop moves (unrolled: their loads and stores
# overlap); a tile is a whole number of passes.
UNROLL_ROWS = 8
_VMEM_LIMIT_BYTES = 64 << 20   # of the v5e's 128 MiB
_LANES = 128


def _tiles(T, k, D, dtype):
    """(rows of a buffer tile, columns of a block) for x [T, D] with k
    choices a token, or None where the kernels do not take the shape: a
    width that is no multiple of 128, a buffer that is no whole number of
    tiles, a tile that is no whole number of `SUB_ROWS_DRHS` parts, of
    sublane tiles and of the loop's passes, or a token side of which not
    even 128 columns fit `RESIDENT_BYTES`."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = min(TILE_ROWS, k * T)
    parts = (grouped_matmul.SUB_ROWS_DRHS, 32 // itemsize, UNROLL_ROWS)
    if D % _LANES or (k * T) % rows or any(rows % p for p in parts) \
            or T % (32 // itemsize):
        return None
    fits = [c for c in range(_LANES, D + 1, _LANES)
            if D % c == 0 and T * c * (2 * itemsize + 4) <= RESIDENT_BYTES]
    return (rows, fits[-1]) if fits else None


def _vmem_bytes(T, rows, cols, itemsize):
    """What a kernel's blocks take of VMEM at most: the resident side (its
    block twice and the f32 twin), the buffer's tiles (`rows_out` with the
    weights' gradient: the result, the other operand and the f32 partial
    sums, each twice), the tile's f32 twin and the scales."""
    return (T * cols * (2 * itemsize + 4)
            + rows * cols * (4 * itemsize + 4)
            + 2 * rows * _LANES * 4 * 2)


def order_plan(experts, held):
    """(how `moe_ffn` forms the sorted order of its rows, the bins it counts
    over): ("count", count) where the layer is told it holds `count` of its
    `experts` (``held=(first, count)``) and they are not all of them, so that
    a part of the k * T assignments is dead: `parallel/expert.held_order`,
    ONE sort that carries the weights, the dead assignments behind the
    `count` held bins. Else ("argsort", 0): `sort_assignments`' two argsorts
    over all `experts` bins (every expert held, the capacity path)."""
    if held is not None and experts is not None and held[1] < experts:
        return "count", held[1]
    return "argsort", 0


def rows_plan(T, k, D, dtype=jnp.bfloat16, experts=None, held=None):
    """How the dispatch and the combine of a dropless local routed layer
    move their rows, x [T, D] in `dtype` with k choices a token
    (`hvd.profile.moe_rows_plan`; the ops run what this returns, where a
    TPU runs them):

        {"path": "kernel" or "jnp",
         "tile_rows": rows of a tile of the buffer,
         "block_cols": columns of the token side resident at a time,
         "buffer_rows": k * T, whatever is live,
         "vmem_bytes": what a kernel's blocks take of VMEM at most,
         "calls_a_layer": {"forward": 2, "backward": 2} kernel calls,
         "order": "count" or "argsort",
         "bins": the bins counted over (0 under "argsort")}

    The path is "kernel" where the shapes fit (`_tiles`) and the backend is
    a TPU, whatever the layer holds of the experts (the count it tells the
    kernels is k * T where it holds them all); else "jnp": gathers, selects
    and sums over all k * T rows. The order (`order_plan`; whatever the
    backend) is "count" where the layer routes over `experts` and is told
    it holds ``held=(first, count)`` of them with count < experts, else
    (and with neither given) "argsort"."""
    tiles = _kernel_tiles(T, k, D, dtype, None)
    rows, cols = tiles or (0, 0)
    calls = 2 if tiles else 0
    order, bins = order_plan(experts, held)
    return {"path": "kernel" if tiles else "jnp", "tile_rows": rows,
            "block_cols": cols, "buffer_rows": k * T,
            "vmem_bytes": _vmem_bytes(T, rows, cols,
                                      jnp.dtype(dtype).itemsize)
            if tiles else 0,
            "calls_a_layer": {"forward": calls, "backward": calls},
            "order": order, "bins": bins}


def _kernel_tiles(T, k, D, dtype, interpret):
    """`_tiles` where the kernels run the call, None where jnp does."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    return _tiles(T, k, D, dtype)


def _last_live_tile(n_ref, rows):
    """The tile that holds row n_live - 1; the first where none is live, so
    that the grid's one block is written (zeros) and not what VMEM held."""
    return jnp.maximum(n_ref[0] - 1, 0) // rows


def _live_rows_of_tile(i, n_ref, rows):
    """(the first row of tile i, how many of its rows are live, [rows, 1]
    bool: which)."""
    base = i * rows
    row = base + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return base, jnp.clip(n_ref[0] - base, 0, rows), row < n_ref[0]


def _for_live_rows(live, move):
    """`move(r)` for the tile's rows r below `live`, `UNROLL_ROWS` a pass: the
    last pass may run past `live`, inside the tile, and what it moves the
    caller's select drops."""
    def one_pass(p, carry):
        for u in range(UNROLL_ROWS):
            move(p * UNROLL_ROWS + u)
        return carry

    lax.fori_loop(0, pl.cdiv(live, UNROLL_ROWS), one_pass, 0)


def _tile_spec(shape, index):
    """A block `shape` of the buffer's side at `index(tile, c)`, for the
    grid step of column block c and tile i: `tile` is i, or the last live
    tile where i lies behind it, so that nothing is fetched or written for
    a tile with no live row."""
    return pl.BlockSpec(shape, lambda c, i, idx, n: index(
        jnp.minimum(i, _last_live_tile(n, shape[-2])), c))


def _wide(rows, cols):
    """Tile i of column block c of a [k*T, D] array."""
    return _tile_spec((rows, cols), lambda tile, c: (tile, c))


def _scales(rows):
    """Tile i of the [k*T, 1] scales."""
    return _tile_spec((rows, 1), lambda tile, c: (tile, 0))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# out[s] = scale[s] * src[idx[s]], s < n_live
# --------------------------------------------------------------------------

def _rows_kernel(idx_ref, n_ref, src_ref, *refs, rows, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    src32, got32 = refs
    i = pl.program_id(1)  # read here: the interpreter has none in a branch

    @pl.when(i == 0)
    def _new_columns():
        src32[...] = src_ref[...].astype(jnp.float32)

    @pl.when(i <= _last_live_tile(n_ref, rows))
    def _live_tile():
        base, live, mine = _live_rows_of_tile(i, n_ref, rows)

        def move(r):
            got32[pl.ds(r, 1), :] = src32[pl.ds(idx_ref[base + r], 1), :]

        _for_live_rows(live, move)
        got = got32[...]
        if dotted:
            product = jnp.where(
                mine, got * other_ref[...].astype(jnp.float32), 0.0)
            dots_ref[...] = sum(product[:, c:c + _LANES]
                                for c in range(0, product.shape[1], _LANES))
        if scaled:
            got = scale_ref[...] * got
        out_ref[...] = jnp.where(mine, got, 0.0).astype(out_ref.dtype)


# The kernels' calls are jitted: the routed layers of a model share one
# trace and one lowering of each form of a kernel (a `pl.pallas_call` costs
# a quarter of a second of a step's lowering, each time it is lowered), and
# the call site's scope path still reaches each call's `op_name`.
@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _pallas_rows(src, idx, n_live, scale, other, tiles, interpret):
    rows, cols = tiles
    (T, D), S = src.shape, idx.shape[0]
    in_specs = [pl.BlockSpec((T, cols), lambda c, i, idx, n: (0, c))]
    args = [src]
    if scale is not None:
        in_specs.append(_scales(rows))
        args.append(scale.astype(jnp.float32).reshape(S, 1))
    out_specs = _wide(rows, cols)
    out_shape = jax.ShapeDtypeStruct((S, D), src.dtype)
    if other is not None:
        in_specs.append(_wide(rows, cols))
        args.append(other)
        # A column block's share of each row's product, folded onto 128
        # lanes: summed over the blocks and the lanes outside.
        out_specs = [out_specs, _tile_spec(
            (None, rows, _LANES), lambda tile, c: (c, tile, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (D // cols, S, _LANES), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_rows_kernel, rows=rows, scaled=scale is not None,
                          dotted=other is not None),
        name=profile.MOE_ROWS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(D // cols, S // rows),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((T, cols), jnp.float32),
                            pltpu.VMEM((rows, cols), jnp.float32)]),
        out_shape=out_shape, compiler_params=_PARAMS, interpret=interpret,
    )(idx.astype(jnp.int32), n_live.astype(jnp.int32).reshape(1), *args)


def rows_out(src, idx, n_live, scale=None, other=None, interpret=None):
    """src [T, D], idx [S] int32 (S a multiple of T), n_live (int32 scalar,
    S at most), scale [S] f32 or None -> out [S, D] in src.dtype: row s is
    ``scale[s] * src[idx[s]]`` (the product in f32, rounded once) for
    ``s < n_live``. What lies behind: zeros to the end of the last live
    tile (of `rows_plan`'s `tile_rows`) and undefined past it where the
    kernel runs, zeros everywhere from jnp. With `other` [S, D] the result
    is (out, dots [S] f32): ``dots[s] = other[s] . src[idx[s]]`` (products
    and sum in f32) for ``s < n_live``, else 0; rows of `other` from
    `n_live` on are selected away. `interpret`: None takes the kernel on a
    TPU where the shapes fit and jnp elsewhere; True runs it in Pallas'
    interpreter."""
    (T, D), S = src.shape, idx.shape[0]
    tiles = _kernel_tiles(T, S // T, D, src.dtype, interpret)
    mine = jnp.arange(S, dtype=jnp.int32) < n_live
    if tiles is None:
        got = src[idx].astype(jnp.float32)
        out = got if scale is None else scale[:, None] * got
        out = jnp.where(mine[:, None], out, 0.0).astype(src.dtype)
        if other is None:
            return out
        return out, jnp.sum(jnp.where(
            mine[:, None], got * other.astype(jnp.float32), 0.0), axis=1)
    got = _pallas_rows(src, idx, n_live, scale, other, tiles,
                       bool(interpret))
    if other is None:
        return got
    return got[0], jnp.where(mine, jnp.sum(got[1], axis=(0, 2)), 0.0)


# --------------------------------------------------------------------------
# y[t] = the sum of scale[s] * src[s] over the live rows s of token t
# --------------------------------------------------------------------------

def _sum_kernel(tok_ref, n_ref, *refs, rows, sources, scaled):
    refs = list(refs)
    src_refs = [refs.pop(0) for _ in range(sources)]
    scale_ref = refs.pop(0) if scaled else None
    out_ref, sum32, src32 = refs
    i = pl.program_id(1)  # read here: the interpreter has none in a branch

    @pl.when(i == 0)
    def _new_columns():
        sum32[...] = jnp.zeros_like(sum32)

    @pl.when(i <= _last_live_tile(n_ref, rows))
    def _live_tile():
        base, live, mine = _live_rows_of_tile(i, n_ref, rows)
        got = sum(ref[...].astype(jnp.float32) for ref in src_refs)
        if scaled:
            got = scale_ref[...] * got
        src32[...] = jnp.where(mine, got, 0.0)

        def move(r):
            at = pl.ds(tok_ref[base + r], 1)
            sum32[at, :] = sum32[at, :] + src32[pl.ds(r, 1), :]

        _for_live_rows(live, move)

    @pl.when(i == pl.num_programs(1) - 1)
    def _round():
        out_ref[...] = sum32[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("T", "tiles", "interpret"))
def _pallas_sum(srcs, tok, n_live, scale, T, tiles, interpret):
    rows, cols = tiles
    S, D = srcs[0].shape
    in_specs = [_wide(rows, cols)] * len(srcs)
    args = list(srcs)
    if scale is not None:
        in_specs.append(_scales(rows))
        args.append(scale.astype(jnp.float32).reshape(S, 1))
    return pl.pallas_call(
        functools.partial(_sum_kernel, rows=rows, sources=len(srcs),
                          scaled=scale is not None),
        name=profile.MOE_SUM,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(D // cols, S // rows),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((T, cols), lambda c, i, tok, n: (0, c)),
            scratch_shapes=[pltpu.VMEM((T, cols), jnp.float32),
                            pltpu.VMEM((rows, cols), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, D), srcs[0].dtype),
        compiler_params=_PARAMS, interpret=interpret,
    )(tok.astype(jnp.int32), n_live.astype(jnp.int32).reshape(1), *args)


def rows_sum(srcs, order, inv, n_live, k, weights=None, interpret=None,
             scale=None):
    """srcs: arrays [S, D] in sorted order whose SUM's rows are meant (one,
    or the cotangents of the copies `dispatch` handed out), order [S]
    (`order[s]` = j*T + t: the assignment at sorted position s), inv [k*T]
    (its inverse), n_live (int32 scalar), weights [k, T] f32 or None (ones)
    -> [T, D] in the sources' dtype:
    ``y[t] = sum_j weights[j, t] * src[inv[j*T + t]]`` over the choices j
    whose row is live (``inv[j*T + t] < n_live``), summed in f32 and rounded
    once (the kernel adds the sources, then a token's rows in sorted order;
    jnp rounds the sources' sum and adds in the order of j). S is k*T, or a
    whole number of T under it where no more rows can be live (`moe_ffn`'s
    cut): the token count is `inv`'s, never derived from the rows. Rows from
    `n_live` on are selected away. `scale` [S]: the weights in sorted order
    where the caller's sort carried them (else gathered here by `order`).
    `interpret`: as `rows_out`'s."""
    S, D = srcs[0].shape
    T = inv.shape[0] // k
    tiles = _kernel_tiles(T, S // T, D, srcs[0].dtype, interpret)
    if tiles is None:
        src = srcs[0] if len(srcs) == 1 else sum(
            a.astype(jnp.float32) for a in srcs).astype(srcs[0].dtype)
        pos = inv.reshape(k, T)
        live = pos < n_live
        # A position past a cut buffer is dead: the gather clamps it.
        rows = jnp.where(live[..., None], src[pos], 0)
        w = live.astype(jnp.float32) if weights is None \
            else jnp.where(live, weights, 0.0)
        return jnp.einsum("ktd,kt->td", rows, w,
                          preferred_element_type=jnp.float32
                          ).astype(src.dtype)
    if scale is None and weights is not None:
        scale = weights.reshape(-1)[order]
    return _pallas_sum(tuple(srcs), order % T, n_live, scale, T, tiles,
                       bool(interpret))


# --------------------------------------------------------------------------
# The two differentiable ops of `moe_ffn`
# --------------------------------------------------------------------------

def _dispatched(x, order, n_live, copies, interpret):
    return (rows_out(x, order % x.shape[0], n_live,
                     interpret=interpret),) * copies


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def dispatch(x, order, inv, n_live, k, copies=1, interpret=None):
    """x [T, D] -> `copies` times the same [S, D], S the length of `order`
    (k*T, or the front of the sorted order that can be live): row s is the
    token of the assignment at sorted position s (`order[s]` = j*T + t) for
    ``s < n_live``; behind them as `rows_out` leaves it. `inv` [k*T]: the
    sorted position of assignment a. One copy for each use of the rows (a
    gated expert's two first matmuls): the transpose is ONE `rows_sum` of
    the copies' cotangents over the live rows, where autodiff would first
    add them over all k*T."""
    del inv, k
    return _dispatched(x, order, n_live, copies, interpret)


def _dispatch_fwd(x, order, inv, n_live, k, copies, interpret):
    return (_dispatched(x, order, n_live, copies, interpret),
            (order, inv, n_live))


def _dispatch_bwd(k, copies, interpret, res, g):
    order, inv, n_live = res
    return (rows_sum(g, order, inv, n_live, k, interpret=interpret),
            None, None, None)


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combined(ys, weights, order, inv, n_live, interpret, carried):
    return rows_sum((ys,), order, inv, n_live, weights.shape[0], weights,
                    interpret=interpret,
                    scale=None if carried is None else carried[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine(ys, weights, order, inv, n_live, interpret=None, carried=None):
    """ys [S, D] in sorted order (S and `order` as `dispatch`'s), weights
    [k, T] f32 -> y [T, D] in ys.dtype: each token's weighted sum over its
    choices whose row is live (`rows_sum`). Transposed as one `rows_out`:
    the cotangent's row of each live position times its weight, and its
    product with that position's row of `ys`, the weights' gradient. The
    rows in assignment order are made in neither direction.

    `carried`: what the caller's sort carried with the order, where it
    carried anything (`parallel/expert.held_order`): (the weights in sorted
    order [S], the assignment at each of ALL k*T sorted positions, a
    permutation [k*T] whose front is `order`). The weights are then gathered
    in neither direction: forward they are given, and their gradient is put
    back into the assignments' order by a sort on that permutation. No
    gradient flows through `carried`."""
    return _combined(ys, weights, order, inv, n_live, interpret, carried)


def _combine_fwd(ys, weights, order, inv, n_live, interpret, carried):
    return (_combined(ys, weights, order, inv, n_live, interpret, carried),
            (ys, weights, order, inv, n_live, carried))


def _combine_bwd(interpret, res, dy):
    ys, weights, order, inv, n_live, carried = res
    T = weights.shape[1]
    tok = order % T
    scale = weights.reshape(-1)[order] if carried is None else carried[0]
    d_ys, dots = rows_out(dy, tok, n_live, scale=scale, other=ys,
                          interpret=interpret)
    if carried is None:
        d_w = jnp.where(inv < n_live, dots[inv], 0.0)
    else:
        # Sorted by the assignment each position holds, `dots` is in the
        # assignments' order (a cut buffer's are the front of them: nothing
        # behind the cut is live). A dead slot's gradient is zero by its
        # counted position, as above, whatever the kernel left.
        every = carried[1]
        dots = jnp.pad(dots, (0, every.shape[0] - dots.shape[0]))
        d_w = lax.sort((every, dots), num_keys=1, is_stable=False)[1]
        d_w = jnp.where(inv < n_live, d_w, 0.0)
    return (d_ys, d_w.reshape(weights.shape).astype(weights.dtype),
            None, None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)
