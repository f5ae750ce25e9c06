"""Self-verifying multi-process jax.distributed bootstrap test: 2 ranks
initialize jax's distributed runtime from horovod_tpu topology, see each
other's devices as one global mesh, and run a cross-process psum."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import horovod_tpu.jax as hvd


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n >= 2

    hvd.init_distributed()
    hvd.init_distributed()  # idempotent: second call is a no-op

    import jax
    import jax.numpy as jnp

    assert jax.process_count() == n, jax.process_count()
    assert jax.process_index() == r, (jax.process_index(), r)
    local = jax.local_device_count()
    assert jax.device_count() == n * local, (jax.device_count(), n, local)
    if r == 0:
        print("PASS global_device_view (%d devices over %d processes)"
              % (jax.device_count(), n), flush=True)

    # Cross-process collective through the global runtime: every process
    # contributes its rank; psum must see them all.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    local_vals = [jnp.full((1,), float(r) + 1.0)
                  for _ in range(local)]
    arr = jax.make_array_from_single_device_arrays(
        (jax.device_count(),), sharding,
        [jax.device_put(v, d)
         for v, d in zip(local_vals, jax.local_devices())])

    @jax.jit
    def total(x):
        return jnp.sum(x)

    result = float(total(arr))
    expected = sum((rr + 1.0) * local for rr in range(n))
    assert abs(result - expected) < 1e-6, (result, expected)
    if r == 0:
        print("PASS cross_process_sum", flush=True)

    # FULL flagship train step over the multi-process global mesh: the
    # same make_train_step the single-process path uses, with the
    # gradient psum now crossing process boundaries (the DCN-plane
    # analogue of the reference's multi-host NCCL allreduce). Every
    # process supplies the identical global batch; jax slices each
    # process's addressable shards.
    import optax

    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    gmesh = data_parallel_mesh(devices=jax.devices())
    rngs = np.random.RandomState(0)
    w0 = jnp.asarray(rngs.randn(16, 8).astype(np.float32) * 0.1)

    def loss_fn(params, batch):
        logits = batch["x"] @ params
        return cross_entropy_loss(logits, batch["y"])

    opt = optax.sgd(0.1)
    step = make_train_step(loss_fn, opt, gmesh, donate=False)
    total_batch = 2 * jax.device_count()
    batch = {
        "x": jnp.asarray(rngs.randn(total_batch, 16).astype(np.float32)),
        "y": jnp.asarray(rngs.randint(0, 8, size=total_batch)),
    }
    params_p, opt_state, batch_p = step.place(w0, opt.init(w0), batch)
    losses = []
    for _ in range(3):
        params_p, opt_state, loss = step(params_p, opt_state, batch_p)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    # The replicated loss must agree across processes (allgather the
    # final loss through the host core to check).
    gathered = hvd.allgather(np.asarray([losses[-1]], np.float64),
                             name="jd_final_loss")
    assert np.allclose(np.asarray(gathered), losses[-1], atol=1e-9), \
        gathered
    if r == 0:
        print("PASS cross_process_train_step", flush=True)

    # FSDP over the same multi-process global mesh: params/state
    # sharded across PROCESS boundaries, GSPMD's gathers riding the
    # distributed runtime.
    from horovod_tpu.parallel import make_fsdp_train_step

    fparams = {"w": w0, "w2": jnp.asarray(
        rngs.randn(8, 16).astype(np.float32) * 0.1)}

    def floss(params, b):
        h = jnp.tanh(b["x"] @ params["w"])
        logits = h @ params["w2"]
        return cross_entropy_loss(logits, b["y"] % 16)

    fstep = make_fsdp_train_step(floss, opt, gmesh, donate=False,
                                 min_size=32)
    fp, fs, fb = fstep.place(fparams, batch=batch)
    flosses = []
    for _ in range(3):
        fp, fs, floss_v = fstep(fp, fs, fb)
        flosses.append(float(floss_v))
    assert flosses[-1] < flosses[0], flosses
    from jax.sharding import PartitionSpec as PS
    assert fp["w"].sharding.spec == PS("hvd"), fp["w"].sharding
    gathered_f = hvd.allgather(np.asarray([flosses[-1]], np.float64),
                               name="jd_fsdp_loss")
    assert np.allclose(np.asarray(gathered_f), flosses[-1], atol=1e-9)
    if r == 0:
        print("PASS cross_process_fsdp_step", flush=True)

    # Hierarchical (dp_cross x dp_local) train step over the global
    # mesh — the two-level ICI/DCN reduction the reference implements
    # as hierarchical NCCL allreduce (reference
    # horovod/common/ops/nccl_operations.cc:150-346: intra-node reduce,
    # inter-node allreduce, intra-node bcast). Here the mesh axes
    # encode the split (trailing axis = devices within a process) and
    # the program reduces in two explicit levels.
    from jax import lax
    from jax.sharding import PartitionSpec

    if local >= 2 and n >= 2:
        from horovod_tpu.parallel import hybrid_mesh

        hmesh = hybrid_mesh((n, local), ("dp_cross", "dp_local"),
                            devices=jax.devices())
        lr = 0.1
        N = n * local

        def hier_local(w, x, y):
            def lf(w):
                return cross_entropy_loss(x @ w, y)
            loss, g = jax.value_and_grad(lf)(w)
            # Level 1: reduce within the process (ICI analogue);
            # level 2: across processes (DCN analogue).
            g = lax.psum(g, "dp_local")
            g = lax.psum(g, "dp_cross")
            loss = lax.pmean(lax.pmean(loss, "dp_local"), "dp_cross")
            return w - lr * (g / N), loss

        hstep = jax.jit(jax.shard_map(
            hier_local, mesh=hmesh,
            in_specs=(PartitionSpec(),
                      PartitionSpec(("dp_cross", "dp_local")),
                      PartitionSpec(("dp_cross", "dp_local"))),
            out_specs=(PartitionSpec(), PartitionSpec()),
            check_vma=False))
        hw = w0
        hlosses = []
        for _ in range(3):
            hw, hloss = hstep(hw, batch["x"], batch["y"])
            hlosses.append(float(hloss))
        assert hlosses[-1] < hlosses[0], hlosses
        gathered_h = hvd.allgather(np.asarray([hlosses[-1]], np.float64),
                                   name="jd_hier_loss")
        assert np.allclose(np.asarray(gathered_h), hlosses[-1],
                           atol=1e-9), gathered_h
        if r == 0:
            print("PASS cross_process_hierarchical_step", flush=True)

    # Pipeline parallelism ACROSS process boundaries: pp stages on the
    # leading (cross-process) axis, dp on the per-process devices —
    # activations ppermute between processes every microbatch tick.
    if n >= 2:
        from horovod_tpu.parallel import hybrid_mesh, pipeline_apply

        ppmesh = hybrid_mesh((n, local), ("pp", "dp"),
                             devices=jax.devices())
        d, B_pp, M = 16, 4 * local * 2, 4
        rng2 = np.random.RandomState(7)
        stage_w = jnp.asarray(
            rng2.randn(n, 1, d, d).astype(np.float32) * (1.0 / d ** 0.5))
        xs = jnp.asarray(rng2.randn(B_pp, d).astype(np.float32))
        ys = jnp.asarray(rng2.randn(B_pp, d).astype(np.float32))
        lr = 0.2

        def stage_fn(sp, x):
            def layer(x, w):
                return jnp.tanh(x @ w), None
            return lax.scan(layer, x, sp)[0]

        def pp_local(stage_local, x, y):
            def local_loss(sl):
                sl0 = jax.tree_util.tree_map(lambda v: v[0], sl)
                x_mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
                out = pipeline_apply(stage_fn, sl0, x_mb, "pp")
                out = out.reshape(x.shape)
                # Pipeline grad contract (test_pipeline.py): local
                # loss scaled by 1/pp; staged grads then complete.
                return jnp.mean((out - y) ** 2) / lax.psum(1, "pp")
            loss, g = jax.value_and_grad(local_loss)(stage_local)
            # dp axis: plain data-parallel gradient average.
            g = jax.tree_util.tree_map(
                lambda v: lax.psum(v, "dp") / lax.psum(1, "dp"), g)
            loss = lax.pmean(lax.pmean(loss, "dp"), "pp") * n
            new = jax.tree_util.tree_map(lambda w, gv: w - lr * gv,
                                         stage_local, g)
            return new, loss

        pstep = jax.jit(jax.shard_map(
            pp_local, mesh=ppmesh,
            in_specs=(PartitionSpec("pp"), PartitionSpec("dp"),
                      PartitionSpec("dp")),
            out_specs=(PartitionSpec("pp"), PartitionSpec()),
            check_vma=False))
        sw = stage_w
        plosses = []
        for _ in range(4):
            sw, ploss = pstep(sw, xs, ys)
            plosses.append(float(ploss))
        assert plosses[-1] < plosses[0], plosses
        gathered_p = hvd.allgather(np.asarray([plosses[-1]], np.float64),
                                   name="jd_pp_loss")
        assert np.allclose(np.asarray(gathered_p), plosses[-1],
                           atol=1e-9), gathered_p
        if r == 0:
            print("PASS cross_process_pp_step", flush=True)

    jax.distributed.shutdown()
    print("rank %d: jax.distributed bootstrap tests passed" % r,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
