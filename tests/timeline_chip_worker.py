"""Observability worker: a live training loop with a mid-run straggler.

Every rank computes real model gradients with jax and allreduces them
through the HOST core (the plane the timeline instruments — on-chip XLA
collectives are compiled into the jit step and invisible to a host-side
tracer by design). A chip belongs to one process at a time, so on a TPU
host only local rank 0 may take it: every other rank pins itself to the
CPU backend before importing jax. (Under the test suite the launcher
env already pins every rank to the CPU.) One mid-run straggler step on
rank 1 crosses the stall-check threshold, so the coordinator's stall
inspector fires its warning DURING a live training loop. Reference
analogue: docs/timeline.rst:1-60 (capture a timeline from a real
training job)."""

import os
import sys
import time

if os.environ.get("HVD_TPU_LOCAL_RANK", os.environ.get("HVD_TPU_RANK",
                                                       "0")) != "0":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    r = int(os.environ.get("HVD_TPU_RANK", "0"))

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    backend = jax.default_backend()
    print("rank %d backend=%s" % (r, backend), flush=True)

    # Small-but-real model: 3-layer MLP classifier, grads jitted on
    # this rank's backend.
    rng = np.random.RandomState(0)
    params = [jnp.asarray(rng.randn(256, 256).astype(np.float32) * 0.05)
              for _ in range(3)]
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    y = jnp.asarray(rng.randn(64, 256).astype(np.float32))

    def loss_fn(ps):
        h = x
        for w in ps:
            h = jnp.tanh(h @ w)
        return jnp.mean((h - y) ** 2)

    grads_fn = jax.jit(jax.grad(loss_fn))

    lr = 0.1
    for step in range(6):
        grads = grads_fn(params)
        host_grads = [np.asarray(g, np.float32) for g in grads]
        if r == 1 and step == 3:
            # Straggle WELL past HVD_TPU_STALL_CHECK_TIME_SECONDS. Two
            # things must happen on the coordinator while rank 0
            # waits: the stalled CACHED tensor is invalidated and
            # renegotiated (the path whose fast-path drop once
            # livelocked this exact workload — controller.cc
            # invalid_in_queue gate), and the renegotiated tensor then
            # crosses the threshold again so the stall WARNING names
            # this rank.
            time.sleep(float(os.environ.get("HVD_TPU_TL_STRAGGLE",
                                            "7")))
        reduced = [hvd.allreduce(g, "grad.layer%d" % i)
                   for i, g in enumerate(host_grads)]
        params = [p - lr * jnp.asarray(g)
                  for p, g in zip(params, reduced)]

    final = float(loss_fn(params))
    print("rank %d final loss %.5f (backend=%s)" % (r, final, backend),
          flush=True)
    print("rank %d done" % r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
