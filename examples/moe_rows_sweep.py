"""Tile sweep of a routed layer's dispatch and combine where the layer is
told its live-row count, against XLA's gathers over the whole buffer.

`ops.moe_rows` moves the rows of a k*T-row buffer by the count of those that
are live: the buffer's tiles stream (`TILE_ROWS` rows a grid step, the live
ones alone), the token side is resident in VMEM a block of columns at a time
(`RESIDENT_BYTES`), a scalar loop moves one row at a time between them. This
times, on the chip, the dispatch and the combine together WITHOUT the
experts between them (forward: x -> xs, (ys, weights) -> y; forward and
backward: + the three gradients from given cotangents), two ways: `xla`, the
ops' own jnp form, which is what runs where no tile takes the shape (a
gather and a select over all k*T rows each way, the weighted sum over the k
choices), and the kernels at each candidate. Two cases: Xing4.0's routed
layer on one rank of eight ([16384, 3584] bf16, the first 8 of 64 experts
held: an eighth live) and OLMoE's ([32768, 2048], all 64 held: every row
live, where there is nothing to skip and the question is one row move at a
time against XLA's gather).

With `--act`, the activation between the grouped matmuls instead
(`ops.moe_act`: ``act(g) * h`` or ``act(h)`` over the live tiles of [rows, F]
buffers, `TILE_ROWS` rows and `BLOCK_BYTES` of an operand a grid step)
against XLA's fusion over all rows, forward and backward, at the
three cells whose layers hold a part of their experts: SDAR's [65536, 768]
silu-gated, Nemotron's [32768, 2688] relu2 without a gate, Xing's
[16384, 1024] silu-gated, at a live share like each cell's.

Usage: python examples/moe_rows_sweep.py [--rows 256 512 1024 2048]
           [--resident-mib 12 24 48] [--unroll 8] [--iters 20]
       python examples/moe_rows_sweep.py --act [--rows 256 512 1024 2048]
           [--block-kib 512 1024 2048 4096] [--iters 20]
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.ops import moe_act as ma  # noqa: E402
from horovod_tpu.ops import moe_rows as mr  # noqa: E402
from horovod_tpu.parallel import expert  # noqa: E402

# (T, k, D, experts, held): the benchmark's two routed cells.
CASES = {"xing_eighth_live": (4096, 4, 3584, 64, 8),
         "olmoe_all_live": (4096, 8, 2048, 64, 64)}


# (rows, F, the activation, gated, live rows): the buffers between the
# grouped matmuls of the three cells whose layers hold a part of their experts.
ACT_CASES = {"sdar_silu_gated": (65536, 768, "silu", True, 9000),
             "nemo3_relu2": (32768, 2688, "relu2", False, 1700),
             "xing_silu_gated": (16384, 1024, "silu", True, 2048)}


CHAIN = 8  # --act: calls a timed program makes, one after the other


def ops(x, ys, weights, order, inv, n_live, k):
    return (mr.dispatch(x, order, inv, n_live, k)[0],
            mr.combine(ys, weights, order, inv, n_live))


def timed(step, args, iters):
    """ms a call, the best of three rounds of `iters` calls."""
    jax.block_until_ready(step(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters * 1e3)
    return min(times)


def sweep_act(args):
    """The activation: XLA's fusion over all rows, then the kernels at each
    candidate tile. A call is a tenth of a millisecond, under the host's
    cost of issuing one: each timed program runs `CHAIN` of them, every one
    on the results of the one before, behind a barrier XLA fuses nothing
    across."""
    bf16 = jnp.bfloat16
    for case, (rows, F, name, gated, live) in ACT_CASES.items():
        act = expert.ACTIVATIONS[name]
        g, h, da = (jax.random.normal(key, (rows, F), bf16)
                    for key in jax.random.split(jax.random.PRNGKey(0), 3))
        n_live = jnp.int32(live)

        def xla(g, h, da):
            """(a,), or with `da` (dg, dh, a) / (dh, a): autodiff of the
            plain expression."""
            fn = (lambda g, h: act(g) * h) if gated else (lambda g, h: act(h))
            if da is None:
                return (fn(g, h),)
            a, vjp = jax.vjp(fn, g, h)
            return vjp(da)[0 if gated else 1:] + (a,)

        def kernels(g, h, da):
            return tuple(ma._pallas_act(
                act, g if gated else None, h, n_live, da,
                ma._tiles(rows, F, bf16), False))

        def chained(form, backward):
            def f(g, h, da):
                for _ in range(CHAIN):
                    got = form(g, h, da if backward else None)
                    # the results are the next call's operands, no buffer
                    # twice among them
                    g, h, da = jax.lax.optimization_barrier(
                        (got[0], got[-1], g) if backward
                        else (h, got[0], da))
                return g, h, da
            return jax.jit(f)

        want = jax.jit(xla)(g, h, da)

        def report(form_name, form, **more):
            got = jax.jit(form)(g, h, da)
            off = [float(jnp.max(jnp.abs(
                a[:live].astype(jnp.float32) - b[:live].astype(jnp.float32))))
                for a, b in zip(got, want)]
            ms = [timed(chained(form, backward), (g, h, da), args.iters)
                  / CHAIN for backward in (False, True)]
            print(json.dumps({
                "case": case, "form": form_name, "live": live,
                "buffer_rows": rows, "width": F, **more,
                "live_rows_off_xla": [round(e, 5) for e in off],
                "forward_ms": round(ms[0], 4),
                "backward_ms": round(ms[1], 4)}), flush=True)

        report("xla", xla)
        seen = set()
        for ma.TILE_ROWS in args.rows:
            for kib in args.block_kib:
                ma.BLOCK_BYTES = kib << 10
                tiles = ma._tiles(rows, F, bf16)
                plan = ma.act_plan(rows, F, bf16, gated)
                if tiles is None or tiles in seen \
                        or plan["vmem_bytes"] > ma._VMEM_LIMIT_BYTES:
                    continue
                seen.add(tiles)
                jax.clear_caches()  # the kernels' calls are jitted
                try:
                    report("kernel", kernels, tile_rows=tiles[0],
                           block_cols=tiles[1])
                except jax.errors.JaxRuntimeError as e:  # VMEM, mostly
                    print(json.dumps({"case": case, "tile_rows": tiles[0],
                                      "block_cols": tiles[1],
                                      "refused": str(e)[:160]}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--act", action="store_true",
                    help="sweep the activation's kernels, not the rows'")
    ap.add_argument("--block-kib", nargs="+", type=int,
                    default=[512, 1024, 2048, 4096],
                    help="--act: KiB of one operand's block a grid step")
    ap.add_argument("--rows", nargs="+", type=int,
                    default=[256, 512, 1024, 2048])
    ap.add_argument("--resident-mib", nargs="+", type=int,
                    default=[12, 24, 48])
    ap.add_argument("--unroll", nargs="+", type=int, default=[mr.UNROLL_ROWS],
                    help="rows a pass of the scalar loop moves")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("moe_rows_sweep: a CPU timing is no device metric, and off "
                 "a TPU every row would be the jnp form (backend: %s)"
                 % jax.default_backend())
    print("device:", jax.devices()[0].device_kind)
    if args.act:
        return sweep_act(args)
    for case, (T, k, D, E, held) in CASES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        bf16 = jnp.bfloat16
        x = jax.random.normal(keys[0], (T, D), bf16)
        ys = jax.random.normal(keys[1], (k * T, D), bf16)
        g_xs = jax.random.normal(keys[2], (k * T, D), bf16)
        g_y = jax.random.normal(keys[3], (T, D), bf16)
        weights = jax.random.uniform(keys[4], (k, T), jnp.float32)
        chosen = jnp.argsort(jax.random.uniform(keys[5], (T, E)),
                             axis=1)[:, :k].astype(jnp.int32)
        _, order, inv, sizes = expert.sort_assignments(chosen, E)
        n_live = jnp.sum(sizes[:held])

        # No block of columns fits: the ops take their jnp form.
        mr.RESIDENT_BYTES = 0
        want = jax.jit(lambda *a: jax.vjp(
            lambda x, ys, w: ops(x, ys, w, *a[3:6], k), *a[:3])[1](a[6:]))(
                x, ys, weights, order, inv, n_live, g_xs, g_y)

        def report(form, **more):
            def forward(x, ys, w, order, inv, n):
                return ops(x, ys, w, order, inv, n, k)

            def both(x, ys, w, order, inv, n, g_xs, g_y):
                out, vjp = jax.vjp(
                    lambda x, ys, w: ops(x, ys, w, order, inv, n, k),
                    x, ys, w)
                return out, vjp((g_xs, g_y))

            operands = (x, ys, weights, order, inv, n_live)
            got = jax.jit(both)(*operands, g_xs, g_y)[1]
            live = jnp.arange(k * T) < n_live
            off = [float(jnp.max(jnp.abs(jnp.where(
                live[:, None] if a.shape[0] == k * T else True,
                a.astype(jnp.float32) - b.astype(jnp.float32), 0.0))))
                for a, b in zip(got, want)]
            print(json.dumps({
                "case": case, "form": form, "live": int(n_live),
                "buffer_rows": k * T, **more,
                "gradients_off_xla": [round(e, 5) for e in off],
                "forward_ms": round(timed(jax.jit(forward), operands,
                                          args.iters), 4),
                "forward_backward_ms": round(timed(
                    jax.jit(both), operands + (g_xs, g_y), args.iters), 4)}),
                flush=True)

        report("xla")
        seen = set()
        for rows in args.rows:
            for mib in args.resident_mib:
                for mr.UNROLL_ROWS in args.unroll:
                    mr.TILE_ROWS, mr.RESIDENT_BYTES = rows, mib << 20
                    tiles = mr._tiles(T, k, D, bf16)
                    if tiles is None or tiles + (mr.UNROLL_ROWS,) in seen:
                        continue
                    seen.add(tiles + (mr.UNROLL_ROWS,))
                    jax.clear_caches()  # the kernels' calls are jitted
                    report("kernel", tile_rows=tiles[0],
                           block_cols=tiles[1], unroll=mr.UNROLL_ROWS)


if __name__ == "__main__":
    main()
