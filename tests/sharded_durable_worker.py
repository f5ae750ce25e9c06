"""Sharded-update x durable-checkpoint e2e worker (docs/ZERO.md):
deterministic training through ``DistributedOptimizer(
sharded_update=True)`` with elastic commits. The optimizer state lives
SHARDED (1/N of the Adam moments per rank); at every commit it is
materialized into its world-size-independent full form
(``sharded_state_full``) so it rides the rank-sharded durable
checkpoint writer and re-shards to ANY world size on restore
(``sharded_state_shard`` at generation entry).

Gradients are identical across ranks and quantized to a 1/1024 grid, so
the ring reduce-scatter's sum and the /N averaging are EXACT in f32 at
world sizes 1, 2 and 4 — the whole training trajectory is bitwise
world-size-independent, which is what lets the test assert a killed
2-rank run resumed at half (1) or double (4) size lands on
bitwise-identical parameters vs an uninterrupted run.

Prints the same start/commit/done CRC32C fingerprint lines as
durable_worker.py.

Knobs (env):
  DURABLE_TEST_TOTAL_STEPS  total optimization steps      (default 24)
  DURABLE_TEST_COMMIT_EVERY commit cadence in steps       (default 2)
  DURABLE_TEST_STEP_SLEEP   per-step sleep seconds        (default 0.1)
  DURABLE_TEST_CRASH_STEP   step at which crashers exit   (-1 = never)
  DURABLE_TEST_CRASH_WIDS   csv of worker ids that crash (generation 0
                            only)
  DURABLE_TEST_PID_DIR      write pid.<wid> files here
"""

import os
import sys
import time

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu import jax as hvd_jax
from horovod_tpu.elastic import durable

TOTAL_STEPS = int(os.environ.get("DURABLE_TEST_TOTAL_STEPS", "24"))
COMMIT_EVERY = int(os.environ.get("DURABLE_TEST_COMMIT_EVERY", "2"))
STEP_SLEEP = float(os.environ.get("DURABLE_TEST_STEP_SLEEP", "0.1"))
CRASH_STEP = int(os.environ.get("DURABLE_TEST_CRASH_STEP", "-1"))
CRASH_WIDS = set(
    w for w in os.environ.get("DURABLE_TEST_CRASH_WIDS", "").split(",")
    if w)
LR = 0.05
TARGET = 3.0
SHAPES = {"w": (19,), "b": (6,)}  # 25 elements: uneven at 2 and 4 ranks

WID = os.environ.get("HVD_TPU_WORKER_ID", "?")


def state_crc(state):
    """CRC32C over params + full-form optimizer moments + step —
    bitwise identity across restarts AND world sizes."""
    crc = 0
    for k in sorted(state.params):
        crc = durable.crc32c(
            np.ascontiguousarray(state.params[k]).tobytes(), crc)
    if state.opt_full:
        import jax
        for leaf in jax.tree_util.tree_leaves(state.opt_full["inner"]):
            crc = durable.crc32c(
                np.ascontiguousarray(np.asarray(leaf)).tobytes(), crc)
    return durable.crc32c(("step=%d" % state.step).encode(), crc)


def _quantized_grads(params):
    """2*(w - target) rounded to a 1/1024 grid: identical on every rank
    and EXACTLY summable/averagable at world sizes 1/2/4 in f32."""
    out = {}
    for k, v in params.items():
        g = 2.0 * (np.asarray(v, np.float32) - TARGET)
        out[k] = (np.round(g * 1024.0) / 1024.0).astype(np.float32)
    return out


@elastic.run
def train(state):
    import jax.numpy as jnp
    import optax

    opt = optax.adam(LR)
    sharded = hvd_jax.DistributedOptimizer(opt, sharded_update=True)  # hvd-lint: disable=missing-initial-broadcast
    params = {k: jnp.asarray(v) for k, v in state.params.items()}
    # Re-shard the world-independent full form for THIS rank and world
    # size — fresh start (main() seeds the full form of a fresh init,
    # so durable restore always sees a structure-matching state),
    # durable restore, and post-resize rollback all take the same path.
    s = hvd_jax.sharded_state_shard(state.opt_full)
    print("worker %s start step %d crc %08x size %d"
          % (WID, state.step, state_crc(state), hvd.size()), flush=True)
    while state.step < TOTAL_STEPS:
        gen = int(os.environ.get("HVD_TPU_GENERATION", "0") or 0)
        g = {k: jnp.asarray(v)
             for k, v in _quantized_grads(params).items()}
        updates, s = sharded.update(g, s, params)
        params = optax.apply_updates(params, updates)
        state.step += 1
        loss = float(sum(np.sum((np.asarray(v) - TARGET) ** 2)
                         for v in params.values()))
        print("worker %s gen %d step %d size %d loss %.6f"
              % (WID, gen, state.step, hvd.size(), loss), flush=True)
        if WID in CRASH_WIDS and gen == 0 and state.step == CRASH_STEP:
            print("worker %s crashing now" % WID, flush=True)
            os._exit(23)
        if state.step % COMMIT_EVERY == 0:
            state.params = {k: np.asarray(v, np.float32)
                            for k, v in params.items()}
            # Collective: every rank materializes the full optimizer
            # state so the commit snapshot re-shards at any world size.
            state.opt_full = hvd_jax.sharded_state_full(s)
            # Printed BEFORE the commit (same values): see
            # durable_worker.py.
            print("worker %s commit step %d crc %08x"
                  % (WID, state.step, state_crc(state)), flush=True)
            state.commit()
        time.sleep(STEP_SLEEP)
    state.params = {k: np.asarray(v, np.float32)
                    for k, v in params.items()}
    state.opt_full = hvd_jax.sharded_state_full(s)
    return float(sum(np.sum((v - TARGET) ** 2)
                     for v in state.params.values()))


def main():
    pid_dir = os.environ.get("DURABLE_TEST_PID_DIR")
    if pid_dir:
        with open(os.path.join(pid_dir, "pid.%s" % WID), "w") as f:
            f.write(str(os.getpid()))
    import jax.numpy as jnp
    import optax

    rng = np.random.RandomState(0)
    params = {k: (rng.randn(*shape) * 0.25).astype(np.float32)
              for k, shape in sorted(SHAPES.items())}
    # The WORLD-INDEPENDENT full form of a fresh Adam state (zero
    # moments over the full flat parameter vector): gives the elastic
    # state its final structure up front, so a durable restore's
    # structure match succeeds before hvd/jax world info exists.
    total = sum(int(np.prod(s)) for s in SHAPES.values())
    opt_full = {"inner": optax.adam(LR).init(
        jnp.zeros(total, jnp.float32)), "total": total,
        "world": -1, "rank": -1}
    state = elastic.ElasticState(params=params, opt_full=opt_full, step=0)
    final_loss = train(state)
    if final_loss is None:  # job finished before this worker could join
        print("worker %s superseded (job already complete)" % WID,
              flush=True)
        return 0
    print("worker %s done step %d crc %08x loss %.6f"
          % (WID, state.step, state_crc(state), final_loss), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
