"""Sharded-update A/B bench worker (bench.py --sharded-update): runs
HVD_TPU_BENCH_ITERS Adam steps over an HVD_TPU_BENCH_MB-MB flat f32
parameter buffer in one of two execution modes and reports one
`SHARDED_BENCH {...}` JSON line per rank:

  HVD_TPU_BENCH_SHARDED=0  replicated: allreduce the full gradient,
                           apply Adam to 100% of the parameters with
                           full-size moments on every rank
  HVD_TPU_BENCH_SHARDED=1  sharded (docs/ZERO.md): reduce-scatter the
                           gradient, Adam on this rank's 1/N shard
                           (1/N-size moments), allgather updated params

Reported: wall us/step, socket-layer data-ring bytes (the wire-parity
claim: reduce-scatter + allgather moves the same bytes the allreduce
did), optimizer-state bytes (the native opt_state_bytes gauge in
sharded mode — the N-fold memory claim), and executed reduce-scatter
count. With SHARDED_BENCH_CONV=1 rank 0's row also carries a 2-mode
convergence A/B through the real jax DistributedOptimizer wrappers
(max relative loss divergence, acceptance <= 1e-4). Both modes walk
the same deterministic trajectory; each row carries a params checksum
the bench driver cross-checks between modes, so a collective
regression fails the bench rather than biasing it."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common import ops  # noqa: E402
from horovod_tpu.common.ops import shard_partition  # noqa: E402

B1, B2, EPS, LR = 0.9, 0.999, 1e-8, 1e-3


def _adam(p, g, mu, nu, t):
    """Elementwise numpy Adam — identical math whether p/g/mu/nu are
    the full buffer (replicated) or one shard (sharded)."""
    mu = B1 * mu + (1.0 - B1) * g
    nu = B2 * nu + (1.0 - B2) * g * g
    mu_hat = mu / (1.0 - B1 ** t)
    nu_hat = nu / (1.0 - B2 ** t)
    return p - LR * mu_hat / (np.sqrt(nu_hat) + EPS), mu, nu


def _convergence(steps=40):
    """Replicated vs sharded DistributedOptimizer on the same tiny MLP
    regression (host plane, real collectives): returns the loss-curve
    stats; run on every rank (collective), reported by rank 0."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import jax as hvd_jax

    r, n = hvd.rank(), hvd.size()
    rng = np.random.RandomState(0)
    d_in, d_h, per = 24, 48, 16
    x = rng.randn(per * n, d_in).astype(np.float32)
    w_true = rng.randn(d_in, 1).astype(np.float32)
    y = np.tanh(x @ w_true).astype(np.float32)
    bx = jnp.asarray(x[r * per:(r + 1) * per])
    by = jnp.asarray(y[r * per:(r + 1) * per])

    def loss_fn(p):
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean((h @ p["w2"] - by) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def init_params():
        pr = np.random.RandomState(1)
        return {"w1": jnp.asarray(pr.randn(d_in, d_h).astype(np.float32)
                                  * 0.1),
                "w2": jnp.asarray(pr.randn(d_h, 1).astype(np.float32)
                                  * 0.1)}

    curves = {}
    for mode in ("replicated", "sharded"):
        opt = hvd_jax.DistributedOptimizer(  # hvd-lint: disable=missing-initial-broadcast
            optax.adam(5e-2), sharded_update=(mode == "sharded"),
            name_prefix="conv_%s" % mode)
        p = init_params()
        s = opt.init(p)
        losses = []
        for _ in range(steps):
            _, g = grad_fn(p)
            if mode == "sharded":
                u, s = opt.update(g, s, p)
            else:
                u, s = opt.update(g, s)  # hvd-lint: disable=verify-mixed-modes
            p = optax.apply_updates(p, u)
            # Global loss over the FULL batch (identical on every rank).
            h = np.tanh(x @ np.asarray(p["w1"]))
            losses.append(float(np.mean((h @ np.asarray(p["w2"]) - y)
                                        ** 2)))
        curves[mode] = losses

    ref = np.asarray(curves["replicated"])
    got = np.asarray(curves["sharded"])
    rel = np.abs(got - ref) / (np.abs(ref) + 1e-12)
    return {
        "steps": steps, "ranks": n,
        "replicated_final_loss": round(float(ref[-1]), 8),
        "sharded_final_loss": round(float(got[-1]), 8),
        "max_rel_loss_divergence": float(rel.max()),
        "tolerance": 1e-4,
        "loss_match": bool(rel.max() <= 1e-4),
    }


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    iters = int(os.environ.get("HVD_TPU_BENCH_ITERS", "10"))
    mb = float(os.environ.get("HVD_TPU_BENCH_MB", "4"))
    sharded = os.environ.get("HVD_TPU_BENCH_SHARDED", "0") == "1"
    elems = int(mb * 1024 * 1024 / 4)
    counts, offsets = shard_partition(elems, n)
    lo, hi = offsets[r], offsets[r] + counts[r]

    params = ((np.arange(elems, dtype=np.float32) % 1003) / 501.0) - 1.0
    if sharded:
        mu = np.zeros(counts[r], np.float32)
        nu = np.zeros(counts[r], np.float32)
        hvd.get_basics().opt_state_metrics(mu.nbytes + nu.nbytes)
    else:
        mu = np.zeros(elems, np.float32)
        nu = np.zeros(elems, np.float32)
        hvd.get_basics().opt_state_metrics(mu.nbytes + nu.nbytes)

    def step(i, t):
        nonlocal params, mu, nu
        # Deterministic rank-varying gradient whose mean every rank can
        # verify: base + mean(rank offsets).
        g_local = 0.01 * params + 0.001 * r
        if sharded:
            g = ops.reduce_scatter(g_local, "sb.grad", average=True)  # hvd-lint: disable=verify-kind-mismatch
            p_new, mu, nu = _adam(params[lo:hi], g, mu, nu, t)
            params = np.asarray(ops.allgather(
                np.ascontiguousarray(p_new), "sb.param_ag"))
        else:
            g = ops.allreduce(g_local, "sb.grad", average=True)  # hvd-lint: disable=name-attr-mismatch
            params, mu, nu = _adam(params, g, mu, nu, t)
        assert params.size == elems

    step(-1, 1)  # warmup: connections, negotiation, cache entries
    c0 = hvd.metrics()["counters"]
    t0 = time.perf_counter()
    for i in range(iters):
        step(i, i + 2)
    dt = time.perf_counter() - t0
    c1 = hvd.metrics()["counters"]
    snap = hvd.metrics()

    row = {
        "rank": r, "size": n, "sharded": sharded, "iters": iters,
        "payload_mb": mb,
        "us_per_step": round(dt / iters * 1e6, 1),
        "ring_bytes_sent": c1["net_ring_bytes_sent_total"] -
                           c0["net_ring_bytes_sent_total"],
        "ring_bytes_recv": c1["net_ring_bytes_recv_total"] -
                           c0["net_ring_bytes_recv_total"],
        "reduce_scatter_ops": c1["reduce_scatter_total"] -
                              c0["reduce_scatter_total"],
        "opt_state_bytes": int(snap["gauges"]["opt_state_bytes"]),
        "shard_elems": counts[r], "total_elems": elems,
        # Cross-mode trajectory check (the bench compares replicated vs
        # sharded): both modes must land on ~the same parameters.
        "params_sum": float(np.sum(params, dtype=np.float64)),
    }
    if r == 0 and os.environ.get("SHARDED_BENCH_CONV", "0") == "1":
        row["convergence"] = _convergence()
    elif os.environ.get("SHARDED_BENCH_CONV", "0") == "1":
        _convergence()  # collective: every rank must participate
    print("SHARDED_BENCH %s" % json.dumps(row), flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
