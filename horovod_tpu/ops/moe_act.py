"""The activation between the grouped matmuls of a routed layer that is told
how many of its rows are live (Pallas, TPU): what the held experts of
`parallel/expert.py::moe_ffn` do to a row between their first matmuls and
their last.

    a = act(g) * h      (gated: `g` the gate's matmul, `h` the up projection's)
    a = act(h)          (no gate)

over [rows, F] buffers of which the router decides each step how many rows
carry anything (``held=``: an eighth of 65,536 in the benchmark's cell
`sdar30b_1chip`, a twentieth of 32,768 in `nemo3s120b_1chip`). The grouped
matmuls on both sides visit the live tiles alone (`grouped_matmul.visits`),
the dispatch and the combine around them too (`ops/moe_rows.py`); an XLA
fusion between them runs over the buffer's static shape. These two kernels
take the count as a prefetched scalar, as those do:

- `hvd_moe_act`: `a` of the live rows, in f32 from the operands as they are
  and rounded once. The last live tile is written WHOLE, zeros from the
  count on, selected and not multiplied (a grouped matmul leaves a dead row
  as it found VMEM); the tiles behind are never fetched or written.
- `hvd_moe_act_bwd`: ``(dg, dh)`` (or ``dh``) from ``(g, h, da)``, the
  activation's derivative by `jax.vjp` of the callable inside the body, and
  `a` once more beside them; the same tiles, the same zeros. `a` is the LEFT
  operand of the last matrices' gradient, which multiplies every row of a
  part (`SUB_ROWS_DRHS`) that holds a row of the last group, by zero
  (`_drhs_kernel`): a dead row there has to be finite, and a tile is a whole
  number of such parts.

The grid walks the buffer's tiles a block of columns at a time, and a tile
behind the last live one repeats that one's block index, so nothing is
fetched or written for it (the idiom of `moe_rows._tile_spec`).

`activated_matmul` is the differentiable op `moe_ffn` calls: the activation
and the last grouped matmul under ONE rule, whose residuals are `g` and `h`
(and the matrices), what autodiff of the plain expression needs of the
activation; `a` is formed again by the backward's kernel where XLA, holding
the plain expression, kept it or formed it again as its memory allowed: an
opaque call's result would otherwise stay (two [32768, 2688] arrays more at
`nemo3s120b_1chip`'s peak). `act_plan` says which path a call takes. Where
the width is no multiple of 128 or no tile divides the rows, and on a backend
that is no TPU, the result is the plain expression's (over all rows, XLA's
fusion), unless `interpret=True` asks for the kernels in Pallas' interpreter
(the tests do).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile
from horovod_tpu.ops import grouped_matmul
from horovod_tpu.ops.moe_rows import _live_rows_of_tile

# Rows of a tile of the buffer, and what one operand's block [rows, cols] may
# take in its own dtype (the backward of a gated expert holds six such blocks
# twice, and their f32 twins while it computes).
# `examples/moe_rows_sweep.py` times the candidates on the chip.
TILE_ROWS = 1024
BLOCK_BYTES = 2 << 20
_VMEM_LIMIT_BYTES = 64 << 20   # of the v5e's 128 MiB
_LANES = 128


def _last_live_tile(n_ref, rows):
    """`moe_rows._last_live_tile`, by a division that truncates: nothing here
    is negative, and an integer's floor division costs Mosaic two `sign`s to
    lower each of the eleven times a call's specs and body ask for it (a
    quarter of a second of a step's start for the two kernels)."""
    return lax.div(jnp.maximum(n_ref[0] - 1, 0), jnp.int32(rows))


def _tiles(rows, F, dtype):
    """(rows of a tile, columns of a block) for [rows, F] buffers, or None
    where the kernels do not take the shape: a width that is no multiple of
    128, a buffer that is no whole number of tiles, a tile that is no whole
    number of `SUB_ROWS_DRHS` parts and of sublane tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    tile = min(TILE_ROWS, rows)
    if F % _LANES or rows % tile or tile % (32 // itemsize) \
            or tile % grouped_matmul.SUB_ROWS_DRHS:
        return None
    fits = [c for c in range(_LANES, F + 1, _LANES)
            if F % c == 0 and tile * c * itemsize <= BLOCK_BYTES]
    return tile, max(fits, default=_LANES)


def _kernel_tiles(rows, F, dtype, interpret):
    """`_tiles` where the kernels run the call, None where XLA does."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    return _tiles(rows, F, dtype)


def act_plan(rows, F, dtype=jnp.bfloat16, gated=True, held=True):
    """How the activation between a dropless local routed layer's grouped
    matmuls runs, [rows, F] buffers in `dtype` (`hvd.profile.moe_act_plan`;
    the op runs what this returns, where a TPU runs it):

        {"path": "kernel" or "xla",
         "tile_rows": rows of a tile of the buffer,
         "block_cols": columns of a grid step's block,
         "buffer_rows": rows, whatever is live,
         "grid_steps": steps a call issues (a dead tile's costs no traffic),
         "vmem_bytes": what the backward's blocks take of VMEM at most,
         "calls_a_layer": {"forward": 1, "backward": 1} kernel calls}

    The path is "kernel" where the layer holds a part of the experts
    (`held`: the count is the router's, the rest of the buffer dead), the
    shapes fit (`_tiles`) and the backend is a TPU; else "xla": the plain
    expression over all rows (every expert held: nothing is dead, and a
    fusion at the memory's bandwidth is not beaten)."""
    tiles = _kernel_tiles(rows, F, dtype, None) if held else None
    tile, cols = tiles or (0, 0)
    itemsize = jnp.dtype(dtype).itemsize
    blocks = 6 if gated else 4
    calls = 1 if tiles else 0
    return {"path": "kernel" if tiles else "xla", "tile_rows": tile,
            "block_cols": cols, "buffer_rows": rows,
            "grid_steps": (F // cols) * (rows // tile) if tiles else 0,
            "vmem_bytes": blocks * tile * cols * (2 * itemsize + 4),
            "calls_a_layer": {"forward": calls, "backward": calls}}


def _act_kernel(n_ref, *refs, act, rows, gated, backward):
    """One tile: `a` from (g, h) or from h; backward (dg, dh, a) from
    (g, h, da) or (dh, a) from (h, da). f32 inside, rounded once, zeros
    from the count on."""
    operands = gated + 1 + backward
    ins, outs = refs[:operands], refs[operands:]
    i = pl.program_id(1)  # read here: the interpreter has none in a branch

    @pl.when(i <= _last_live_tile(n_ref, rows))
    def _live_tile():
        mine = _live_rows_of_tile(i, n_ref, rows)[2]
        vals = [ref[...].astype(jnp.float32) for ref in ins]
        if not backward:
            got = (act(vals[0]) * vals[1] if gated else act(vals[0]),)
        elif gated:
            g, h, da = vals
            a_g, vjp = jax.vjp(act, g)
            got = (vjp(da * h)[0], da * a_g, a_g * h)
        else:
            h, da = vals
            a, vjp = jax.vjp(act, h)
            got = (vjp(da)[0], a)
        for ref, val in zip(outs, got):
            ref[...] = jnp.where(mine, val, 0.0).astype(ref.dtype)


# The calls are jitted, as `ops/moe_rows.py`'s are: the routed layers of a
# model share one trace and one lowering of each, and the call site's scope
# path still reaches each call's `op_name`. `act` is part of the key.
@functools.partial(jax.jit, static_argnames=("act", "tiles", "interpret"))
def _pallas_act(act, g, h, n_live, da, tiles, interpret):
    """(a,) [rows, F] from (g, h) (g None: no gate), or with `da` the
    gradients and `a` once more: (dg, dh, a) / (dh, a)."""
    rows, cols = tiles
    S, F = h.shape
    ins = [x for x in (g, h, da) if x is not None]
    gated, backward = g is not None, da is not None
    # Tile i of column block c, or the last live tile where i lies behind
    # it: nothing is fetched or written for a tile with no live row.
    spec = pl.BlockSpec((rows, cols), lambda c, i, n: (
        jnp.minimum(i, _last_live_tile(n, rows)), c))
    results = gated + 2 if backward else 1
    kernel = functools.partial(_act_kernel, act=act, rows=rows, gated=gated,
                               backward=backward)
    how = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(F // cols, S // rows),
            in_specs=[spec] * len(ins), out_specs=[spec] * results),
        out_shape=[jax.ShapeDtypeStruct((S, F), h.dtype)] * results,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret)
    # One kernel body under two names: the profiler tells the forward from
    # the backward by them.
    if backward:
        call = pl.pallas_call(kernel, name=profile.MOE_ACT_BWD, **how)
    else:
        call = pl.pallas_call(kernel, name=profile.MOE_ACT, **how)
    return call(n_live.astype(jnp.int32).reshape(1), *ins)


# The rule's two halves are ONE jitted function each, as the kernels' calls
# inside them are: every layer of a model binds one call forward and one
# backward, traced once for all of them. Only kernels' calls are inside
# (what XLA would fuse and time under a shared function carries no call
# site's scopes). `how`: (`_tiles`, `grouped_matmul.tile_sizes()`), all
# that the traces depend on beside their operands.
@functools.partial(jax.jit, static_argnames=("act", "how", "interpret"))
def _forward(act, g, h, n_live, w_out, meta, how, interpret):
    a, = _pallas_act(act, g, h, n_live, None, how[0], interpret)
    return grouped_matmul.product(a, w_out, meta, interpret)


@functools.partial(jax.jit, static_argnames=("act", "how", "interpret"))
def _backward(act, g, h, n_live, w_out, meta, dy, how, interpret):
    """(dg or None, dh, d_w_out)."""
    da = grouped_matmul.rows_gradient(dy, w_out, meta, interpret)
    *dg, dh, a = _pallas_act(act, g, h, n_live, da, how[0], interpret)
    d_w = grouped_matmul.matrices_gradient(a, dy, meta, w_out.dtype,
                                           interpret)
    return dg[0] if dg else None, dh, d_w


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 6, 7))
def _activated_matmul(act, g, h, n_live, w_out, meta, how, interpret):
    return _forward(act, g, h, n_live, w_out, meta, how, interpret)


def _activated_matmul_fwd(act, g, h, n_live, w_out, meta, how, interpret):
    return (_forward(act, g, h, n_live, w_out, meta, how, interpret),
            (g, h, n_live, w_out, meta))


def _activated_matmul_bwd(act, how, interpret, res, dy):
    g, h, n_live, w_out, meta = res
    dg, dh, d_w = _backward(act, g, h, n_live, w_out, meta, dy, how,
                            interpret)
    return dg, dh, None, d_w, None


_activated_matmul.defvjp(_activated_matmul_fwd, _activated_matmul_bwd)


def activated_matmul(act, h, n_live, w_out, group_sizes, gate=None,
                     interpret=None, meta=None):
    """h [rows, F], gate [rows, F] or None, n_live (int32 scalar: the rows
    of the groups, which lie at the front), `act` a callable of jnp, w_out
    [G, F, N], group_sizes [G] int32 -> [rows, N] in h.dtype:
    ``grouped_matmul(a, w_out, group_sizes)`` with
    ``a[s] = act(gate[s]) * h[s]`` (``act(h[s])`` without a gate). Where
    the kernels run, `a` is formed for ``s < n_live`` alone, in f32 and
    rounded once, with zeros to the end of the last live tile (of
    `act_plan`'s `tile_rows`) whatever the operands hold there, in the
    forward and again in the backward, which keeps `gate` and `h` and not
    `a`. From XLA the plain expression over all rows, in the operands'
    dtype. `interpret`: None takes the kernels on a TPU where the shapes
    fit and XLA (and `grouped_matmul`'s own choice) elsewhere; True runs
    them in Pallas' interpreter. `meta`: `grouped_matmul`'s."""
    tiles = _kernel_tiles(h.shape[0], h.shape[1], h.dtype, interpret)
    if tiles is None:
        return grouped_matmul.grouped_matmul(
            act(h) if gate is None else act(gate) * h, w_out, group_sizes,
            interpret, meta)
    if meta is None:
        meta = grouped_matmul.layer_visits(group_sizes, h.shape[0],
                                           interpret)
    return _activated_matmul(act, gate, h, n_live, w_out, meta,
                             (tiles, grouped_matmul.tile_sizes()),
                             bool(interpret))
