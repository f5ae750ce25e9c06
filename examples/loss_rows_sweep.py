"""Rows-of-a-chunk sweep of the vocabulary loss at a given shape.

`chunked_softmax_cross_entropy` (ops/losses.py) takes as many of the
B * L rows a scan iteration as `LOGITS_BUDGET_BYTES` of f32 logits hold
(`loss_plan`). This times value-and-gradient of the loss alone, by
`hidden` and by the head, with the budget set to each candidate number
of rows in turn, and prints the plan beside each time. The constant was
read off it at the benchmark's two shapes ([2, 2048] and [1, 4096] at
D=2048, V=50304).

Usage: python examples/loss_rows_sweep.py [--D 2048 --V 50304]
           [--shapes 2x2048 1x4096] [--rows 512 1024 2048 4096]
           [--head float32 bfloat16]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from horovod_tpu.ops import losses


def timed(B, L, D, V, head, iters=20):
    """ms a call of value-and-gradient, bf16 hidden states."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(keys[0], (B, L, D), jnp.bfloat16)
    kernel = (jax.random.normal(keys[1], (D, V)) * 0.02).astype(head)
    targets = jax.random.randint(keys[2], (B, L), 0, V)

    # chunk=1: the caller asks for nothing, the budget alone decides.
    @jax.jit
    def step(hidden, kernel):
        return jax.value_and_grad(
            lambda h, k: losses.chunked_softmax_cross_entropy(
                h, k, targets, chunk=1), argnums=(0, 1))(hidden, kernel)

    jax.block_until_ready(step(hidden, kernel))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(hidden, kernel)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters * 1e3)
    return min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--D", type=int, default=2048)
    ap.add_argument("--V", type=int, default=50304)
    ap.add_argument("--shapes", nargs="+", default=["2x2048", "1x4096"])
    ap.add_argument("--rows", nargs="+", type=int,
                    default=[512, 1024, 2048, 4096])
    ap.add_argument("--head", nargs="+", default=["float32", "bfloat16"])
    args = ap.parse_args()
    print("device:", jax.devices()[0].device_kind)
    for shape in args.shapes:
        B, L = (int(x) for x in shape.split("x"))
        for head in args.head:
            for rows in args.rows:
                losses.LOGITS_BUDGET_BYTES = 4 * rows * args.V
                plan = losses.loss_plan(B, L, args.D, args.V, 1, jnp.bfloat16)
                ms = timed(B, L, args.D, args.V, jnp.dtype(head))
                print(json.dumps({"shape": shape, "head": head,
                                  "ms": round(ms, 3), **plan}), flush=True)


if __name__ == "__main__":
    main()
