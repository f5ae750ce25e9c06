"""Flash-attention kernel micro-benchmark (the PERF.md table).

Times forward and forward+backward with the lax.scan single-dispatch
recipe (one dispatch covers the loop, so per-call host overhead stays
out of a sub-millisecond kernel's timing), reporting ms/iter and
effective TFLOP/s from the causal count of the matmuls the kernels
execute: `hvd.profile.flash_plan` says whether the backward is one kernel
(`hvd_flash_bwd`, 5 matmuls a tile) or two (`hvd_flash_dq` +
`hvd_flash_dkv`, 7: s and dp are formed twice).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import profile
from horovod_tpu.ops import flash_attention


def timed(fn, args, iters=50):
    def body(carry, _):
        out = fn(*carry[:1]) if len(args) == 1 else fn(*carry)
        q = carry[0] + 1e-30 * out[0] if isinstance(out, tuple) \
            else carry[0] + 1e-30 * out
        return (q,) + carry[1:], ()

    def run(*args):
        carry, _ = lax.scan(body, args, None, length=iters)
        return jnp.sum(carry[0].astype(jnp.float32))

    jitted = jax.jit(run)
    float(jitted(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(*args))
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=4)
    ap.add_argument("--L", type=int, default=2048)
    ap.add_argument("--H", type=int, default=8)
    ap.add_argument("--D", type=int, default=128)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()
    B, L, H, D = args.B, args.L, args.H, args.D

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)

    # Causal-halved executed FLOPs: fwd = 2 matmuls, bwd = 5 as one
    # kernel or 7 as two.
    bwd_kernels = list(profile.flash_plan(B, H, L, D, dtype=q.dtype,
                                          backward=True))
    bwd_matmuls = 5 if bwd_kernels == [profile.FLASH_BWD] else 7
    fwd_flops = 2 * 2 * B * H * L * L * D / 2
    bwd_flops = bwd_matmuls * 2 * B * H * L * L * D / 2

    t_fwd = timed(lambda q: flash_attention(q, k, v, causal=True),
                  (q,), args.iters)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def fb(q, k, v):
        dq, dk, dv = grad(q, k, v)
        return dq + dk + dv, None

    t_fb = timed(lambda q, k, v: fb(q, k, v), (q, k, v), args.iters)

    print("B=%d L=%d H=%d D=%d causal:" % (B, L, H, D))
    print("  fwd:     %6.2f ms  %6.1f TFLOP/s" %
          (t_fwd * 1e3, fwd_flops / t_fwd / 1e12))
    print("  fwd+bwd: %6.2f ms  %6.1f TFLOP/s (backward: %s, %d matmuls a "
          "tile)" % (t_fb * 1e3, (fwd_flops + bwd_flops) / t_fb / 1e12,
                     " + ".join(bwd_kernels), bwd_matmuls))


if __name__ == "__main__":
    main()
