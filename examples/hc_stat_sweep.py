"""Tile sweep of a hyper-connection's statistic and projection at a given
shape, against the two jnp forms.

`ops.hc_stat.hc_stat` reads the streams X [n, T, C] once for each token's
sum of squares and its projection onto phi's columns, a tile of `BLOCK_ROWS`
tokens a grid step (`hc_plan`). This times, on the chip, the normalised
projection `rsqrt(sumsq / nC + eps) * proj` alone (forward, and
value-and-gradient by X and phi) three ways: `plain`, as `hc_maps` wrote it
before the op (one reduction over the stream and the lane dimension, the
streams normalised, then projected); `jnp`, the op's own fallback
(per-stream sums over the minor dimension, the factor out of the product);
and the kernels (`hvd_hc_stat`; in the gradient also `hvd_hc_stat_dphi`) at
each candidate tile. The constants were read off it at Xing4.0's shape ([4,
4096, 3584] bf16 against 24 columns).

Each timed call evaluates `--copies` different X, so that a call lasts
long enough to hide its dispatch.

Usage: python examples/hc_stat_sweep.py [--n 4 --T 4096 --C 3584]
           [--rows 64 128 256 512] [--copies 4]
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.ops import hc_stat as hs  # noqa: E402

EPS = 1e-6


def plain(X, phi):
    n, C = X.shape[0], X.shape[-1]
    xf = X.astype(jnp.float32)
    inv = lax.rsqrt(jnp.sum(xf * xf, axis=(0, -1)) / (n * C) + EPS)
    xn = (xf * inv[None, ..., None]).astype(X.dtype)
    return jnp.einsum("n...c,nck->...k", xn,
                      phi.reshape(n, C, -1).astype(X.dtype),
                      preferred_element_type=jnp.float32)


def through_the_op(X, phi):
    sumsq, proj = hs.hc_stat(X, phi)
    return lax.rsqrt(sumsq / (X.shape[0] * X.shape[-1]) + EPS)[
        ..., None] * proj


def timed(fn, Xs, phi, grad, iters=20):
    """ms an evaluation, the best of three rounds of `iters` calls."""
    def value(Xs, phi):
        return sum(jnp.sum(jnp.sin(fn(X, phi))) for X in Xs)

    step = jax.jit(jax.grad(value, argnums=(0, 1)) if grad else value)
    jax.block_until_ready(step(Xs, phi))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(Xs, phi)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters / len(Xs) * 1e3)
    return min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--T", type=int, default=4096)
    ap.add_argument("--C", type=int, default=3584)
    ap.add_argument("--rows", nargs="+", type=int,
                    default=[64, 128, 256, 512])
    ap.add_argument("--copies", type=int, default=4)
    args = ap.parse_args()
    n, T, C = args.n, args.T, args.C
    K = 2 * n + n * n
    if jax.default_backend() != "tpu":
        sys.exit("hc_stat_sweep: a CPU timing is no device metric, and off "
                 "a TPU every row would be the jnp form (backend: %s)"
                 % jax.default_backend())
    print("device:", jax.devices()[0].device_kind)
    keys = jax.random.split(jax.random.PRNGKey(0), args.copies + 1)
    Xs = [jax.random.normal(k, (n, 1, T, C), jnp.bfloat16)
          for k in keys[1:]]
    phi = 0.02 * jax.random.normal(keys[0], (n * C, K), jnp.float32)

    def report(form, fn, **more):
        print(json.dumps({
            "form": form, **more,
            "forward_ms": round(timed(fn, Xs, phi, False), 4),
            "value_and_grad_ms": round(timed(fn, Xs, phi, True), 4)}),
            flush=True)

    report("plain", plain)
    budget = hs.VMEM_BUDGET_BYTES
    hs.VMEM_BUDGET_BYTES = 0    # no tile fits: the op's jnp form
    report("jnp", through_the_op, **hs.hc_plan(n, T, C, K, jnp.bfloat16))
    hs.VMEM_BUDGET_BYTES = budget
    for rows in args.rows:
        hs.BLOCK_ROWS = rows
        report("kernel", through_the_op,
               **hs.hc_plan(n, T, C, K, jnp.bfloat16))


if __name__ == "__main__":
    main()
