"""JAX binding tests: host-path collectives (size-1 short circuit), the
in-jit psum plane over a shard_map'd mesh, and the optax
DistributedOptimizer in both planes."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import horovod_tpu.jax as hvd


@pytest.fixture(scope="module", autouse=True)
def init_hvd():
    hvd.init()
    yield


def test_rank_size():
    assert hvd.size() == 1
    assert hvd.rank() == 0


def test_host_allreduce():
    x = jnp.arange(10, dtype=jnp.float32)
    out = hvd.allreduce(x, average=False)
    assert np.allclose(out, x)
    out = hvd.allreduce(x, average=True)
    assert np.allclose(out, x)


def test_host_allgather_broadcast():
    x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    assert np.allclose(hvd.allgather(x), x)
    assert np.allclose(hvd.broadcast(x, 0), x)


def test_shutdown_reinit_cycles():
    """The core must survive init/shutdown/init cycles in one process
    (VERDICT round-1 lifecycle obligation; exercised by spark task reuse
    and notebook workflows)."""
    import horovod_tpu as hvd_core
    for cycle in range(2):
        hvd_core.init()
        assert hvd_core.is_initialized()
        out = hvd.allreduce(jnp.ones(3), average=False,
                            name="cycle.%d" % cycle)
        assert np.allclose(out, 1.0)
        hvd_core.shutdown()
        assert not hvd_core.is_initialized()
    hvd_core.init()  # leave initialized for the rest of the module


def test_scalar_shape_roundtrip():
    """0-d tensors must come back 0-d (ascontiguousarray promotes them
    to (1,) internally; the caller's shape wins)."""
    out = hvd.allreduce(jnp.float32(2.0), average=False)
    assert out.shape == (), out.shape
    out = hvd.broadcast(jnp.int32(5), 0)
    assert out.shape == (), out.shape


def test_host_allgather_empty():
    # Zero rows is legal (reference allgatherv semantics); the zero-copy
    # view path must not choke on the core's null empty-buffer pointer.
    out = hvd.allgather(jnp.zeros((0, 4), jnp.float32))
    assert out.shape[0] == 0 and out.shape[1:] == (4,)


def test_compression_fp16_roundtrip():
    x = jnp.arange(8, dtype=jnp.float32)
    out = hvd.allreduce(x, average=False, compression=hvd.Compression.fp16)
    assert out.dtype == jnp.float32
    assert np.allclose(out, x, atol=1e-2)


def test_injit_psum_plane():
    devices = jax.devices("cpu")
    assert len(devices) == 8, "conftest should provide 8 virtual devices"
    mesh = Mesh(np.array(devices), (hvd.AXIS_NAME,))

    def step(x):
        return hvd.allreduce(x, average=True)

    f = shard_map(step, mesh=mesh, in_specs=P(hvd.AXIS_NAME),
                  out_specs=P(hvd.AXIS_NAME))
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    out = jax.jit(f)(x)
    # Average over the mapped axis: every row becomes the column mean
    # broadcast back to its shard.
    expected_mean = x.reshape(8, 2).mean(axis=0)
    assert np.allclose(out, jnp.tile(expected_mean, (8, 1)))


def test_injit_allgather():
    devices = jax.devices("cpu")
    mesh = Mesh(np.array(devices), (hvd.AXIS_NAME,))
    f = shard_map(lambda x: hvd.allgather(x), mesh=mesh,
                  in_specs=P(hvd.AXIS_NAME), out_specs=P(),
                  check_vma=False)
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = jax.jit(f)(x)
    assert out.shape == (8, 1)
    assert np.allclose(out.ravel(), np.arange(8))


def test_injit_broadcast_pytree():
    """In-jit broadcast accepts a pytree and broadcasts leaf-wise (the
    masked-psum rewrite must not regress the tree-accepting API)."""
    devices = jax.devices("cpu")
    mesh = Mesh(np.array(devices), (hvd.AXIS_NAME,))

    def step(rank_arr):
        tree = {"w": rank_arr, "b": rank_arr * 2.0}
        return hvd.broadcast(tree, root_rank=3)

    f = shard_map(step, mesh=mesh, in_specs=P(hvd.AXIS_NAME),
                  out_specs=P(hvd.AXIS_NAME))
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = jax.jit(f)(x)
    # Every shard receives rank 3's values.
    assert np.allclose(out["w"].ravel(), 3.0)
    assert np.allclose(out["b"].ravel(), 6.0)


def test_distributed_optimizer_host():
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones(4), "b": jnp.zeros(2)}
    state = opt.init(params)
    grads = {"w": jnp.full(4, 2.0), "b": jnp.ones(2)}
    updates, state = opt.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    assert np.allclose(new_params["w"], 1.0 - 0.1 * 2.0)
    assert np.allclose(new_params["b"], -0.1)


def test_distributed_optimizer_injit():
    devices = jax.devices("cpu")
    mesh = Mesh(np.array(devices), (hvd.AXIS_NAME,))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = jnp.ones(4)
    state = opt.init(params)

    def step(params, state, grads):
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    f = shard_map(step, mesh=mesh,
                  in_specs=(P(), P(), P(hvd.AXIS_NAME)),
                  out_specs=(P(), P()))
    # Per-device gradients 0..7 -> average 3.5.
    grads = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) * jnp.ones((8, 4))
    grads = grads.reshape(8, 4)
    new_params, _ = jax.jit(f)(params, state, grads)
    assert np.allclose(new_params, 1.0 - 0.1 * 3.5)


def test_broadcast_parameters():
    params = {"w": jnp.arange(4, dtype=jnp.float32),
              "b": jnp.ones(2, dtype=jnp.bfloat16)}
    out = hvd.broadcast_parameters(params, root_rank=0)
    assert out["b"].dtype == jnp.bfloat16
    assert np.allclose(out["w"], params["w"])


def test_metric_average():
    assert hvd.metric_average(3.5) == 3.5


def test_plain_jit_single_process_identity():
    """Collectives inside plain jit (no shard_map axis) in a single
    process are identity — must NOT raise unbound-axis NameError."""
    import jax
    import jax.numpy as jnp
    import horovod_tpu.jax as hvd_jax

    @jax.jit
    def step(x):
        a = hvd_jax.allreduce(x, average=True)
        b = hvd_jax.broadcast(x, 0)
        g = hvd_jax.allgather(x)
        return a, b, g

    x = jnp.arange(6.0)
    a, b, g = step(x)
    assert jnp.allclose(a, x)
    assert jnp.allclose(b, x)
    assert jnp.allclose(g, x)


def test_w2v_sparse_step_matches_dense_mesh():
    """The word2vec step's sparse (indices,values) allgather+scatter-add plane
    must produce bit-comparable tables to the dense psum path after
    multiple steps on a real 4-device mesh — pins the jax-plane
    IndexedSlices analogue end to end (duplicate ids accumulate, the
    cross-rank average matches, updates stay replicated)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from horovod_tpu.models.word2vec import w2v_make_step

    jax.config.update("jax_default_matmul_precision", "highest")
    n = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("dp",))
    V, D, B, K = 64, 16, 32, 8  # B/K divisible by n
    rng = np.random.RandomState(3)
    center = jnp.asarray(rng.randint(0, V, B).astype(np.int32))
    context = jnp.asarray(rng.randint(0, V, B).astype(np.int32))
    neg = jnp.asarray(rng.randint(0, V, K).astype(np.int32))

    def tables():
        r = np.random.RandomState(5)
        return (jnp.asarray(r.randn(V, D).astype(np.float32)),
                jnp.asarray(r.randn(V, D).astype(np.float32)),
                jnp.zeros((V,), jnp.float32))

    outs = {}
    for sparse in (True, False):
        # donate=False: donation is a memory optimization, not part
        # of the semantics under test.
        step = w2v_make_step(mesh, n, sparse, num_iters=3, donate=False)
        outs[sparse] = step(*tables(), center, context, neg)

    for a, b, nm in zip(outs[True], outs[False],
                        ("emb", "nce_w", "nce_b", "loss")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6, err_msg=nm)
