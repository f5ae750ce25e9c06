"""On-chip wall-clock comparison of the ring-attention backward paths.

Measures grad(ring_attention) on a 1-device mesh (one ring step, which
is exactly the per-step work that repeats n times on an n-chip ring)
for:

  * new: the FlashAttention-2-style second ring pass over saved lse
    (current `_ring_flash` VJP);
  * old: the round-2 recompute VJP — differentiate the blockwise jnp
    ring under jax.checkpoint (reconstructed here for comparison).

Timing recipe per PERF.md: iterations chained inside one lax.scan so a
single dispatch covers the loop, then one host read as the barrier.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel import ring_attention
from horovod_tpu.parallel.ring import _ring_jnp


def _old_remat_ring(q, k, v, axis_name, causal, scale):
    """Round-2 backward: recompute through the jnp ring under
    jax.checkpoint (per-step remat)."""
    f = jax.checkpoint(
        functools.partial(_ring_jnp, axis_name=axis_name, causal=causal,
                          scale=scale))
    return f(q, k, v)


def bench(fn, mesh, q, k, v, iters=20):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def scan_body(carry, _):
        q, k, v = carry
        dq, dk, dv = grad(q, k, v)
        # Feed gradients back in so scan iterations are data-dependent
        # (nothing can be hoisted or elided).
        return (q + 1e-30 * dq, k + 1e-30 * dk, v + 1e-30 * dv), ()

    def run(q, k, v):
        (q, k, v), _ = lax.scan(scan_body, (q, k, v), None, length=iters)
        return jnp.sum(q.astype(jnp.float32))

    sharded = jax.shard_map(run, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                            out_specs=P(), check_vma=False)
    jitted = jax.jit(sharded)
    float(jitted(q, k, v))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(q, k, v))
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1]


def main():
    B, L, H, D = 4, 2048, 8, 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    t_new = bench(lambda q, k, v: ring_attention(q, k, v, "sp"),
                  mesh, q, k, v)
    t_old = bench(
        lambda q, k, v: _old_remat_ring(q, k, v, "sp", True, D ** -0.5),
        mesh, q, k, v)
    print("B=%d L=%d H=%d D=%d fwd+bwd per iter:" % (B, L, H, D))
    print("  new (lse second ring pass): %.2f ms" % (t_new * 1e3))
    print("  old (jnp remat recompute):  %.2f ms" % (t_old * 1e3))
    print("  speedup: %.2fx" % (t_old / t_new))


if __name__ == "__main__":
    main()
