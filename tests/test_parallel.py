"""Sequence-parallel attention and DP train-step tests on the 8-device
virtual CPU mesh (the TPU-less analogue of the reference's 2-process
localhost distributed tests, SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# Numerical-equivalence tests compare two computation orders; pin matmuls
# to exact f32 so only the math (not backend matmul quantization) differs.
jax.config.update("jax_default_matmul_precision", "highest")


def _mesh(n, name):
    return Mesh(np.array(jax.devices("cpu")[:n]), (name,))


def _dense_reference(q, k, v, causal=True):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D ** -0.5
    if causal:
        L = s.shape[-1]
        mask = np.tril(np.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    from horovod_tpu.parallel import ring_attention
    n = 4
    B, L, H, D = 2, 32, 4, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_reference(q, k, v, causal)

    mesh = _mesh(n, "sp")
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_flash_path_values_and_grads(monkeypatch):
    """The TPU kernel ring path (forced via interpret mode on CPU):
    values AND gradients must match dense — pins the custom VJP that
    makes the Pallas path differentiable (a plain pallas_call is not)."""
    from horovod_tpu.parallel import ring_attention
    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n = 2
    B, L, H, D = 1, 256, 2, 16  # 128-per-shard, kernel path eligible
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_reference(q, k, v, causal=True)

    mesh = _mesh(n, "sp")

    def loss(q, k, v):
        out = ring_attention(q, k, v, "sp", causal=True)
        return out, jnp.sum(out.astype(jnp.float32) ** 2)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: (loss(q, k, v)[0],) + tuple(
            jax.grad(lambda q, k, v: loss(q, k, v)[1],
                     argnums=(0, 1, 2))(q, k, v)),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(None, "sp"),) * 4, check_vma=False))
    out, gq, gk, gv = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True) ** 2)

    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, exp in ((gq, dq), (gk, dk), (gv, dv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)


def test_zigzag_ring_matches_dense(monkeypatch):
    """schedule='zigzag' (causal load-balanced layout): values AND all
    three gradients must equal dense attention on the natural-order
    sequence, round-tripped through zigzag_shard/zigzag_unshard.
    Lq=1024/rank -> two 512-token chunks; with bq=256/bk=512 the q
    chunks span TWO blocks each (the per-block offset arrays carry
    real discontiguities) while each kv chunk is one block."""
    from horovod_tpu.parallel import (ring_attention, zigzag_shard,
                                      zigzag_unshard)
    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n = 4
    B, L, H, D = 1, 4096, 2, 16  # 1024/rank = 2 x 512-token chunks
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_reference(q, k, v, causal=True)

    qz, kz, vz, wz = (zigzag_shard(x, n) for x in (q, k, v, w))
    mesh = _mesh(n, "sp")

    def fwd_and_grads(q, k, v, w):
        def loss(q, k, v):
            out = ring_attention(q, k, v, "sp", causal=True,
                                 schedule="zigzag")
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    f = jax.jit(jax.shard_map(
        fwd_and_grads, mesh=mesh, in_specs=(P(None, "sp"),) * 4,
        out_specs=(P(None, "sp"),) * 4, check_vma=False))
    out, gq, gk, gv = f(qz, kz, vz, wz)

    np.testing.assert_allclose(
        np.asarray(zigzag_unshard(out, n)), np.asarray(expected),
        rtol=2e-5, atol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True) * w)

    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, exp, nm in ((gq, dq, "dq"), (gk, dk, "dk"), (gv, dv, "dv")):
        np.testing.assert_allclose(
            np.asarray(zigzag_unshard(got, n)), np.asarray(exp),
            rtol=2e-4, atol=2e-4, err_msg=nm)


def test_zigzag_shard_roundtrip_and_validation():
    """zigzag_shard/unshard invert each other; ring_attention rejects
    zigzag with non-causal or unaligned shards."""
    from horovod_tpu.parallel import (ring_attention, zigzag_shard,
                                      zigzag_unshard)
    x = jnp.arange(2 * 1024 * 3, dtype=jnp.float32).reshape(2, 1024, 3)
    for n in (2, 4):
        np.testing.assert_array_equal(
            np.asarray(zigzag_unshard(zigzag_shard(x, n), n)),
            np.asarray(x))
    q = jnp.zeros((1, 256, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        ring_attention(q, q, q, "sp", causal=False, schedule="zigzag")
    with pytest.raises(ValueError, match="256"):
        ring_attention(q[:, :128], q[:, :128], q[:, :128], "sp",
                       causal=True, schedule="zigzag")
    with pytest.raises(ValueError, match="unknown ring schedule"):
        ring_attention(q, q, q, "sp", schedule="stripey")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_backward_multiblock(monkeypatch, causal):
    """Multi-block shards (1024/shard -> num_qb=4, num_kb=2): the
    backward ring kernels' cross-block accumulate (kj>0 / qi>0
    load-accumulate-store) and the non-causal visible branch must
    produce dense-matching gradients, not just the single-block case."""
    from horovod_tpu.parallel import ring_attention
    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n = 2
    B, L, H, D = 1, 2048, 1, 16
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)

    mesh = _mesh(n, "sp")

    def loss(q, k, v):
        out = ring_attention(q, k, v, "sp", causal=causal)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(None, "sp"),) * 3, check_vma=False))
    gq, gk, gv = f(q, k, v)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, causal) ** 2)

    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, exp in ((gq, dq), (gk, dk), (gv, dv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)


def test_ulysses_attention_matches_dense():
    from horovod_tpu.parallel import ulysses_attention
    n = 4
    B, L, H, D = 2, 32, 8, 16  # H divisible by n
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_reference(q, k, v, causal=True)

    mesh = _mesh(n, "sp")
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_make_train_step_decreases_loss():
    import optax
    from horovod_tpu.models import MnistCNN
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    model = MnistCNN(dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (16, 28, 28, 1))
    y = jax.random.randint(rng, (16,), 0, 10)
    variables = model.init(rng, x[:1], train=False)
    params = variables["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"], train=True)
        return cross_entropy_loss(logits, batch["y"])

    mesh = data_parallel_mesh(devices=jax.devices("cpu"))
    opt = optax.sgd(0.05)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    params_p, opt_state = step.place(params, opt.init(params))
    batch = {"x": x, "y": y}

    losses = []
    for _ in range(5):
        params_p, opt_state, loss = step(params_p, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_train_step_gradients_are_averaged():
    """Each shard sees different data; the resulting params must be
    identical to a single-device run on the full batch (the defining
    property of synchronous data parallelism)."""
    import optax
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step

    w0 = jnp.ones((4,))
    x = jnp.arange(32.0).reshape(8, 4) / 32.0
    y = jnp.ones((8,))

    def loss_fn(params, batch):
        pred = batch["x"] @ params
        return jnp.mean((pred - batch["y"]) ** 2)

    opt = optax.sgd(0.1)
    mesh = data_parallel_mesh(devices=jax.devices("cpu"))
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    params_p, opt_state = step.place(w0, opt.init(w0))
    params_p, _, _ = step(params_p, opt_state, {"x": x, "y": y})

    g = jax.grad(loss_fn)(w0, {"x": x, "y": y})
    expected = w0 - 0.1 * g
    np.testing.assert_allclose(np.asarray(params_p), np.asarray(expected),
                               rtol=1e-6)


def test_hybrid_mesh_shapes():
    from horovod_tpu.parallel import hybrid_mesh, mesh_axis_size
    mesh = hybrid_mesh((-1, 4), ("dp", "sp"), devices=jax.devices("cpu"))
    assert mesh_axis_size(mesh, "dp") == 2
    assert mesh_axis_size(mesh, "sp") == 4


def test_train_step_gradient_accumulation():
    """accum_steps=k (the flagship analogue of torch's
    backward_passes_per_step): k scanned microbatches with one deferred
    allreduce+update must equal the single-pass step on the same global
    batch (exact for mean-reduction losses)."""
    import optax

    from horovod_tpu.parallel import data_parallel_mesh, make_train_step

    rng = np.random.RandomState(4)
    params = {"w": jnp.asarray(rng.randn(6, 3).astype(np.float32))}
    batch = {
        "x": jnp.asarray(rng.randn(32, 6).astype(np.float32)),
        "y": jnp.asarray(rng.randn(32, 3).astype(np.float32)),
    }

    def loss_fn(params, b):
        return jnp.mean((b["x"] @ params["w"] - b["y"]) ** 2)

    mesh = data_parallel_mesh(devices=jax.devices("cpu"))
    opt = optax.adam(1e-2)

    one = make_train_step(loss_fn, opt, mesh, donate=False)
    p1, s1, b1 = one.place(params, opt.init(params), batch)
    acc = make_train_step(loss_fn, opt, mesh, donate=False,
                          accum_steps=4)
    p2, s2, b2 = acc.place(params, opt.init(params), batch)

    for _ in range(2):
        p1, s1, loss1 = one(p1, s1, b1)
        p2, s2, loss2 = acc(p2, s2, b2)
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p1["w"]),
                               rtol=1e-5, atol=1e-6)


def test_train_step_accum_composes_with_zero1():
    """accum_steps and zero1 together: still equal to the plain step."""
    import optax

    from horovod_tpu.parallel import data_parallel_mesh, make_train_step

    rng = np.random.RandomState(5)
    params = {"w": jnp.asarray(rng.randn(6, 3).astype(np.float32))}
    batch = {
        "x": jnp.asarray(rng.randn(32, 6).astype(np.float32)),
        "y": jnp.asarray(rng.randn(32, 3).astype(np.float32)),
    }

    def loss_fn(params, b):
        return jnp.mean((b["x"] @ params["w"] - b["y"]) ** 2)

    mesh = data_parallel_mesh(devices=jax.devices("cpu"))
    opt = optax.adam(1e-2)
    one = make_train_step(loss_fn, opt, mesh, donate=False)
    p1, s1, b1 = one.place(params, opt.init(params), batch)
    z = make_train_step(loss_fn, opt, mesh, donate=False, zero1=True,
                        accum_steps=2)
    p2, s2, b2 = z.place(params, None, batch)
    for _ in range(2):
        p1, s1, loss1 = one(p1, s1, b1)
        p2, s2, loss2 = z(p2, s2, b2)
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p1["w"]),
                               rtol=1e-5, atol=1e-6)


def _positions(B, L):
    return jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))


def _dense_gqa_reference(q, k, v, causal=True, rotary=False):
    """Dense reference for q [B,L,H,D], k/v [B,L,G,D] on the whole
    sequence: with ``rotary``, q and k rotated by the model's `_rotary` at
    positions 0..L-1; kv repeated across head groups."""
    H, G = q.shape[2], k.shape[2]
    if rotary:
        from horovod_tpu.models.transformer import _rotary
        pos = _positions(*q.shape[:2])
        q, k = _rotary(q, pos), _rotary(k, pos)
    if H != G:
        k = jnp.repeat(k, H // G, axis=2)
        v = jnp.repeat(v, H // G, axis=2)
    return _dense_reference(q, k, v, causal)


def _rotated_ring(q, k, v, positions, **kw):
    """What the model does on a sequence shard: rotary outside the ring, by
    the shard's GLOBAL positions (an input, sharded as the tokens are)."""
    from horovod_tpu.models.transformer import _rotary
    from horovod_tpu.parallel import ring_attention
    return ring_attention(_rotary(q, positions), _rotary(k, positions), v,
                          "sp", causal=True, **kw)


def test_ring_gqa_jnp_path_matches_dense():
    """The jnp ring fallback with grouped kv heads, rotary outside it by
    global positions: the small G-head shards travel the ring and are
    repeated per step."""
    n = 4
    B, L, H, G, D = 2, 32, 4, 2, 16
    rng = np.random.RandomState(21)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    expected = _dense_gqa_reference(q, k, v, rotary=True)

    mesh = _mesh(n, "sp")
    f = jax.jit(jax.shard_map(
        _rotated_ring, mesh=mesh, in_specs=(P(None, "sp"),) * 4,
        out_specs=P(None, "sp"), check_vma=False))
    out = f(q, k, v, _positions(B, L))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_gqa_values_and_grads(monkeypatch):
    """Kernel ring path (interpret mode) with grouped kv heads, rotary
    outside it by global positions: values AND gradients (through the
    rotation) vs dense. Pins the grouped-rows ring layout."""
    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n = 2
    B, L, H, G, D = 1, 256, 4, 2, 16
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_gqa_reference(q, k, v, rotary=True)

    mesh = _mesh(n, "sp")

    def fwd_and_grads(q, k, v, w, positions):
        def loss(q, k, v):
            out = _rotated_ring(q, k, v, positions)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    f = jax.jit(jax.shard_map(
        fwd_and_grads, mesh=mesh, in_specs=(P(None, "sp"),) * 5,
        out_specs=(P(None, "sp"),) * 4, check_vma=False))
    out, gq, gk, gv = f(q, k, v, w, _positions(B, L))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_gqa_reference(q, k, v, rotary=True) * w)

    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, exp, nm in ((gq, dq, "dq"), (gk, dk, "dk"), (gv, dv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4, err_msg=nm)


def test_zigzag_mqa_matches_dense(monkeypatch):
    """zigzag schedule + MQA (G=1), rotary outside the ring: the positions
    are zigzag-sharded with the tokens, so each shard rotates by its two
    discontiguous chunks' global positions."""
    from horovod_tpu.parallel import zigzag_shard, zigzag_unshard
    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n = 4
    B, L, H, G, D = 1, 4096, 2, 1, 16
    rng = np.random.RandomState(29)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    expected = _dense_gqa_reference(q, k, v, rotary=True)

    qz, kz, vz, wz, pz = (zigzag_shard(x, n)
                          for x in (q, k, v, w, _positions(B, L)))
    mesh = _mesh(n, "sp")

    def fwd_and_grads(q, k, v, w, positions):
        def loss(q, k, v):
            out = _rotated_ring(q, k, v, positions, schedule="zigzag")
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    f = jax.jit(jax.shard_map(
        fwd_and_grads, mesh=mesh, in_specs=(P(None, "sp"),) * 5,
        out_specs=(P(None, "sp"),) * 4, check_vma=False))
    out, gq, gk, gv = f(qz, kz, vz, wz, pz)
    np.testing.assert_allclose(
        np.asarray(zigzag_unshard(out, n)), np.asarray(expected),
        rtol=2e-5, atol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_gqa_reference(q, k, v, rotary=True) * w)

    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, exp, nm in ((gq, dq, "dq"), (gk, dk, "dk"), (gv, dv, "dv")):
        np.testing.assert_allclose(
            np.asarray(zigzag_unshard(got, n)), np.asarray(exp),
            rtol=2e-4, atol=2e-4, err_msg=nm)


def test_ulysses_gqa_matches_dense():
    """Ulysses with grouped kv heads: q splits H over the axis, k/v
    split G; contiguous split keeps the query->kv head grouping."""
    from horovod_tpu.parallel import ulysses_attention
    n = 4
    B, L, H, G, D = 2, 32, 8, 4, 16
    rng = np.random.RandomState(31)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, G, D), jnp.float32)
    expected = _dense_gqa_reference(q, k, v, True)

    mesh = _mesh(n, "sp")
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


class _FakeMesh:
    """What `grad_overlap_options` reads of a mesh: the axis sizes and the
    devices' platforms (a TPU mesh cannot be built on the CPU backend)."""

    def __init__(self, platform, n, name="hvd"):
        self.shape = {name: n}
        self.devices = np.array([_FakeDevice(platform) for _ in range(n)])


@pytest.mark.parametrize("platform,n,engages", [
    ("tpu", 4, True), ("cpu", 4, False), ("tpu", 1, False)])
def test_grad_overlap_rule(platform, n, engages):
    """The step builder's rule: asynchronous gradient all-reduces are asked
    of the compiler where the axis has more than one device AND they are
    TPUs; otherwise the step is built as before — and on a CPU mesh of 4
    that step still compiles and averages the gradients."""
    import optax
    from horovod_tpu.parallel import (data_parallel_mesh,
                                      grad_overlap_options, make_train_step)

    options = grad_overlap_options(_FakeMesh(platform, n))
    assert bool(options) == engages
    if engages:
        assert options["xla_enable_async_all_reduce"] == "true"
        assert all(isinstance(v, str) for v in options.values())
        # A caller may edit its copy without changing the next step's.
        options.clear()
        assert grad_overlap_options(_FakeMesh(platform, n))
        return
    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:n])
    assert grad_overlap_options(mesh) == {}
    w0 = jnp.ones((4,))
    x = jnp.arange(32.0).reshape(8, 4) / 32.0
    y = jnp.ones((8,))

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params - batch["y"]) ** 2)

    opt = optax.sgd(0.1)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    params_p, opt_state = step.place(w0, opt.init(w0))
    params_p, _, _ = step(params_p, opt_state, {"x": x, "y": y})
    expected = w0 - 0.1 * jax.grad(loss_fn)(w0, {"x": x, "y": y})
    np.testing.assert_allclose(np.asarray(params_p), np.asarray(expected),
                               rtol=1e-6)


# Lines as libtpu 0.0.34 writes them (shortened): a synchronous all-reduce
# of two leaves, an `all-reduce-start`/`-done` pair, an
# `async-collective-start` fusion whose computation holds the all-reduce
# (its later step inside a compute fusion and its done must not count
# again), a computation nothing calls, and a collective outside the scope.
_HLO_SNIPPET = """\
HloModule jit_shard_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8192,2048]) -> (f32[8192,2048], f32[8192,2048], u32[]) {
  %param_0.1 = f32[8192,2048]{1,0:T(8,128)} parameter(0)
  %all-reduce.5 = f32[8192,2048]{1,0:T(8,128)} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
  ROOT %custom-call.1 = (f32[8192,2048]{1,0}, f32[8192,2048]{1,0:S(1)}, u32[]{:S(2)}) custom-call(%all-reduce.5), custom_call_target="x"
}

%async_collective_fusion.9 (param_0.2: f32[8192,2048], param_1.2: f32[16,16]) -> (f32[16,16], f32[8192,2048]) {
  %param_0.2 = f32[8192,2048]{1,0} parameter(0)
  %param_1.2 = f32[16,16]{1,0} parameter(1)
  %all-reduce.6 = f32[8192,2048]{1,0} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
  ROOT %tuple.3 = (f32[16,16]{1,0}, f32[8192,2048]{1,0}) tuple(%param_1.2, %all-reduce.6)
}

%fused_computation.8 (param_0.3: f32[8192,2048]) -> f32[8192,2048] {
  %param_0.3 = f32[8192,2048]{1,0} parameter(0)
  ROOT %all-reduce.7 = f32[8192,2048]{1,0} all-reduce(%param_0.3), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
}

%fused_computation.left_behind (param_0.4: f32[8192,2048]) -> f32[8192,2048] {
  %param_0.4 = f32[8192,2048]{1,0} parameter(0)
  ROOT %all-reduce.8 = f32[8192,2048]{1,0} all-reduce(%param_0.4), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
}

ENTRY %main.1_spmd (param.1: f32[2048,8192], param.2: f32[2048], param.3: f32[8192,2048], param.4: f32[512,512], param.5: f32[16,16]) -> f32[2048,8192] {
  %param.1 = f32[2048,8192]{1,0:T(8,128)} parameter(0)
  %param.2 = f32[2048]{0:T(1024)} parameter(1)
  %param.3 = f32[8192,2048]{1,0:T(8,128)} parameter(2)
  %param.4 = f32[512,512]{1,0:T(8,128)} parameter(3)
  %param.5 = f32[16,16]{1,0} parameter(4)
  %all-reduce.1 = (f32[2048,8192]{1,0:T(8,128)}, /*index=1*/f32[2048]{0:T(1024)}) all-reduce(%param.1, %param.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
  %all-reduce-start.1 = f32[512,512]{1,0:T(8,128)} all-reduce-start(%param.4), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
  %all-reduce-done.1 = f32[512,512]{1,0:T(8,128)} all-reduce-done(%all-reduce-start.1), metadata={op_name="jit(shard_step)/shard_map/hvd_grad_sync/psum"}
  %async-collective-start.2 = (f32[8192,2048]{1,0}, f32[8192,2048]{1,0:S(1)}, u32[]{:S(2)}) fusion(%param.3), kind=kCustom, output_to_operand_aliasing={{0}: (0, {})}, calls=%fused_computation.7
  %fusion.9 = (f32[16,16]{1,0}, f32[8192,2048]{1,0}) fusion(%param.3, %param.5), kind=kLoop, calls=%async_collective_fusion.9
  %async-collective-done.2 = f32[8192,2048]{1,0} fusion(%param.3), kind=kCustom, calls=%fused_computation.8
  %all-gather.1 = f32[4,16,16]{2,1,0} all-gather(%param.5), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(shard_step)/shard_map/hvd_param_gather/all_gather"}
  ROOT %get-tuple-element.1 = f32[2048,8192]{1,0:T(8,128)} get-tuple-element(%all-reduce.1), index=0
}
"""


def test_grad_collectives_reads_a_recorded_program():
    from horovod_tpu import profile

    got = profile.grad_collectives(_HLO_SNIPPET)
    assert got == {
        "sync": {"count": 1, "bytes": 4 * (2048 * 8192 + 2048)},
        "async": {"count": 2, "bytes": 4 * (512 * 512 + 8192 * 2048)}}
    assert profile.grad_collectives("") == {
        "sync": {"count": 0, "bytes": 0}, "async": {"count": 0, "bytes": 0}}
