"""The chunked Kimi Delta Attention recurrence by part, timed on the chip.

`ops/kda.py::kda_chunked` at one benchmark layer's shape ([1, 8192, 32, 128],
bf16 operands, f32 decays; `benchmark/configs/kimilinear48b_a3b_w2304.json`):
the whole call forward and forward + backward; the two Pallas kernels of a
sub-block's own decayed scores (`own_block_scores`) at each `--blocks` x
`--groups` (sub-blocks a grid step, sub-blocks its loop holds at a time)
against the jnp form where that fits; the unit-lower-triangular solve alone.
Each kernel form is held to the jnp one on a few sub-blocks before it is
timed.

Usage: python examples/kda_sweep.py [--blocks 32 64 128] [--groups 1 2 4 8]
       [--iters 10] [--cpu]   (--cpu: tiny shapes, the interpreter, no times)
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

from horovod_tpu.ops import kda  # noqa: E402


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, nargs="*", default=[32, 64, 128])
    ap.add_argument("--groups", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("kda_sweep: needs a TPU (or --cpu for the forms "
                         "alone)")
    B, L, H, D = (1, 256, 2, 128) if args.cpu else (1, 8192, 32, 128)
    interpret = True if args.cpu else None
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = (unit(jax.random.normal(ks[0], (B, L, H, D))) * D ** -0.5).astype(
        jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (B, L, H, D))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, L, H, D)).astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, L, H, D), minval=-7.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    out = {"shape": [B, L, H, D]}

    def whole(*a):
        return kda.kda_chunked(*a, interpret=interpret)[0]

    def whole_grad(*a):
        return jax.grad(lambda *b: jnp.sum(jnp.square(whole(*b))),
                        argnums=(0, 1, 2, 3, 4))(*a)

    if not args.cpu:
        out["kda_chunked_fwd_ms"] = timed(jax.jit(whole),
                                          (q, k, v, g, beta), args.iters)
        out["kda_chunked_fwd_bwd_ms"] = timed(jax.jit(whole_grad),
                                              (q, k, v, g, beta), args.iters)
    # the own-block kernels alone, on the layer's sub-blocks
    sub, N = 16, B * H * L // 16
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(N, sub, D)  # noqa: E731
    G = jnp.cumsum(flat(g), axis=1)
    fq, fk = flat(q), flat(k)
    cot = tuple(jax.random.normal(kk, (N, sub, sub)) for kk in ks[5:7])
    want = kda._own_jnp(fq[:64], fk[:64], G[:64])
    want_grad = jax.grad(lambda a, b, c: sum(
        jnp.sum(r * t[:64]) for r, t in zip(kda._own_jnp(a, b, c), cot)),
        argnums=(0, 1, 2))(fq[:64].astype(jnp.float32),
                           fk[:64].astype(jnp.float32), G[:64])
    rows = []
    for block in args.blocks:
        for group in args.groups:
            if block % group or N % block:
                continue
            kda.BLOCK_SUBS, kda.GROUP = block, group
            jax.clear_caches()

            def fwd(a, b, c):
                return kda.own_block_scores(a, b, c, interpret)

            def bwd(a, b, c):
                return jax.grad(lambda *x: sum(
                    jnp.sum(r * t[:x[0].shape[0]])
                    for r, t in zip(fwd(*x), cot)), argnums=(0, 1, 2))(a, b, c)

            got = fwd(fq[:64], fk[:64], G[:64])
            got_grad = bwd(fq[:64], fk[:64], G[:64])
            err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
            gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                             / jnp.max(jnp.abs(b)))
                       for a, b in zip(got_grad, want_grad))
            row = {"block": block, "group": group, "fwd_err": err,
                   "bwd_rel_err": gerr}
            if not args.cpu:
                row["fwd_ms"] = timed(jax.jit(fwd), (fq, fk, G), args.iters)
                row["fwd_bwd_ms"] = timed(jax.jit(bwd), (fq, fk, G),
                                          args.iters)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out["own_block_scores"] = rows
    if not args.cpu:
        C = 64
        a = jnp.tril(jax.random.normal(ks[5], (B, H, L // C, C, C)), -1) \
            * 0.1 + jnp.eye(C)
        rhs = jax.random.normal(ks[6], (B, H, L // C, C, 2 * D))
        out["solve_ms"] = timed(jax.jit(lambda a, b: lax.linalg.triangular_solve(
            a, b, left_side=True, lower=True, unit_diagonal=True)),
            (a, rhs), args.iters)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
