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

Usage: python examples/moe_rows_sweep.py [--rows 256 512 1024 2048]
           [--resident-mib 12 24 48] [--unroll 8] [--iters 20]
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

from horovod_tpu.ops import moe_rows as mr  # noqa: E402
from horovod_tpu.parallel import expert  # noqa: E402

# (T, k, D, experts, held): the benchmark's two routed cells.
CASES = {"xing_eighth_live": (4096, 4, 3584, 64, 8),
         "olmoe_all_live": (4096, 8, 2048, 64, 64)}


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


def main():
    ap = argparse.ArgumentParser()
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
