"""The traffic-lean BatchNorm (`ops/batch_norm.py`), pinned against flax
BatchNorm on the CPU: values, statistics, gradients, sync BN over a mesh
axis and over the host plane, ghost BN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.batch_norm import (LeanBatchNorm, bn_remat_policy,
                                        lean_batch_norm_train)

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.mark.parametrize("norm", ["batch", "lean"])
def test_resnet_sync_bn_wiring(norm):
    """ResNet(bn_axis_name='dp'): training forward over a 4-way
    sharded batch produces the same outputs and running-stat updates
    as the unsharded model (sync BN sees the global batch either
    way). Covers the model-level wiring of both norm paths' axis_name
    plumb-through."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.models.resnet import ResNet, BottleneckBlock

    n = 4
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 16, 16, 3).astype(np.float32))
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("dp",))

    def build(axis):
        return ResNet(stage_sizes=[1], block_cls=BottleneckBlock,
                      num_classes=5, num_filters=8, dtype=jnp.float32,
                      norm=norm, bn_axis_name=axis)

    variables = build(None).init(jax.random.PRNGKey(0), x, train=False)
    y_ref, upd_ref = build(None).apply(
        variables, x, train=True, mutable=["batch_stats"])

    model = build("dp")

    def shard_fwd(xs):
        y, upd = model.apply(variables, xs, train=True,
                             mutable=["batch_stats"])
        return y, upd["batch_stats"]

    f = jax.jit(jax.shard_map(
        shard_fwd, mesh=mesh, in_specs=(P("dp"),),
        out_specs=(P("dp"), P(None)), check_vma=False))
    y_s, stats_s = f(x)

    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    ref_stats = upd_ref["batch_stats"]
    flat_s = jax.tree_util.tree_leaves_with_path(stats_s)
    flat_r = dict(jax.tree_util.tree_leaves_with_path(ref_stats))
    assert flat_s
    for path, leaf in flat_s:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_r[path]),
            rtol=1e-4, atol=1e-5, err_msg=str(path))


@pytest.mark.parametrize("norm", ["pallas", "bacth"])
def test_unknown_norm_is_refused_by_name(norm):
    """A `norm` the model does not know is refused with the ones there
    are, not handed to flax's BatchNorm in silence ("pallas" was a value
    until PR 60)."""
    from horovod_tpu.models.resnet import ResNet, BottleneckBlock

    model = ResNet(stage_sizes=[1], block_cls=BottleneckBlock, num_classes=5,
                   num_filters=8, dtype=jnp.float32, norm=norm)
    with pytest.raises(ValueError) as refused:
        model.init(jax.random.PRNGKey(0), jnp.ones((2, 16, 16, 3)),
                   train=False)
    for known in ("batch", "none", "group", "lean"):
        assert repr(known) in str(refused.value)
    assert repr(norm) in str(refused.value)


# --- round 10: the traffic-lean graph-level BN -----------------------------

@pytest.mark.parametrize("shape", [(512, 128), (392, 64), (96, 12),
                                   (6, 5, 7, 13)])
def test_lean_bn_matches_flax(shape):
    """Outputs, batch stats, and all three gradients of the lean
    custom-VJP path vs flax.linen.BatchNorm, 2-D and 4-D, odd shapes
    included (no power-of-two or lane constraints — the lean path is
    pure XLA)."""
    import flax.linen as nn

    C = shape[-1]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32)) * 2.0 + 0.5
    gamma = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(C).astype(np.float32))
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))

    bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                      epsilon=1e-5)
    stats0 = {"mean": jnp.zeros(C), "var": jnp.ones(C)}

    def flax_loss(x, gamma, beta):
        v = {"params": {"scale": gamma, "bias": beta},
             "batch_stats": stats0}
        y, _ = bn.apply(v, x, mutable=["batch_stats"])
        return jnp.sum(y * w), y

    def lean_loss(x, gamma, beta):
        y, mean, var = lean_batch_norm_train(x, gamma, beta, 1e-5)
        return jnp.sum(y * w), (y, mean, var)

    (l1, y1), g1 = jax.value_and_grad(flax_loss, argnums=(0, 1, 2),
                                      has_aux=True)(x, gamma, beta)
    (l2, (y2, mean, var)), g2 = jax.value_and_grad(
        lean_loss, argnums=(0, 1, 2), has_aux=True)(x, gamma, beta)

    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=2e-4, atol=2e-4)
    flat = np.asarray(x).reshape(-1, C)
    np.testing.assert_allclose(np.asarray(mean), flat.mean(0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), flat.var(0),
                               rtol=1e-4, atol=1e-5)
    for a, b, nm in zip(g2, g1, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=nm)


def test_lean_bn_fused_relu_matches_flax_plus_relu():
    """relu=True: y = max(bn(x), 0) with the backward mask recomputed
    from the pre-activation sign (never stored) must equal
    relu(flax_bn(x)) in value AND all three gradients."""
    import flax.linen as nn

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(8, 6, 6, 24).astype(np.float32))
    C = x.shape[-1]
    gamma = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(C).astype(np.float32))
    w = jnp.asarray(rng.randn(*x.shape).astype(np.float32))
    bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                      epsilon=1e-5)
    stats0 = {"mean": jnp.zeros(C), "var": jnp.ones(C)}

    def flax_loss(x, gamma, beta):
        v = {"params": {"scale": gamma, "bias": beta},
             "batch_stats": stats0}
        y, _ = bn.apply(v, x, mutable=["batch_stats"])
        return jnp.sum(jax.nn.relu(y) * w)

    def lean_loss(x, gamma, beta):
        y, _, _ = lean_batch_norm_train(x, gamma, beta, 1e-5, True)
        return jnp.sum(y * w)

    l1, g1 = jax.value_and_grad(flax_loss, argnums=(0, 1, 2))(
        x, gamma, beta)
    l2, g2 = jax.value_and_grad(lean_loss, argnums=(0, 1, 2))(
        x, gamma, beta)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    for a, b, nm in zip(g2, g1, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=nm)


def test_lean_ghost_bn_matches_per_group_flax():
    """groups=G (ghost BN): each virtual batch normalized independently
    must equal flax BN applied per slice — values, (G, C) stats, and
    gradients (dgamma/dbeta summed over groups)."""
    import flax.linen as nn

    G, M, C = 4, 32, 12
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(M, 5, C).astype(np.float32)) * 1.5
    gamma = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(C).astype(np.float32))
    w = jnp.asarray(rng.randn(*x.shape).astype(np.float32))
    bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                      epsilon=1e-5)
    stats0 = {"mean": jnp.zeros(C), "var": jnp.ones(C)}

    def ref_loss(x, gamma, beta):
        v = {"params": {"scale": gamma, "bias": beta},
             "batch_stats": stats0}
        ys = []
        for i in range(G):
            y, _ = bn.apply(v, x[i * (M // G):(i + 1) * (M // G)],
                            mutable=["batch_stats"])
            ys.append(y)
        return jnp.sum(jnp.concatenate(ys) * w)

    def ghost_loss(x, gamma, beta):
        y, mean, var = lean_batch_norm_train(x, gamma, beta, 1e-5,
                                             False, G)
        return jnp.sum(y * w), (mean, var)

    l1, g1 = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        x, gamma, beta)
    (l2, (mean, var)), g2 = jax.value_and_grad(
        lambda *a: ghost_loss(*a), argnums=(0, 1, 2),
        has_aux=True)(x, gamma, beta)
    assert mean.shape == (G, C) and var.shape == (G, C)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    for i in range(G):
        sl = np.asarray(x)[i * (M // G):(i + 1) * (M // G)].reshape(-1, C)
        np.testing.assert_allclose(np.asarray(mean)[i], sl.mean(0),
                                   rtol=1e-5, atol=1e-6)
    for a, b, nm in zip(g2, g1, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=nm)


def test_lean_module_train_eval_roundtrip_and_ghost():
    """LeanBatchNorm: training updates running stats like nn.BatchNorm
    (same variables dict — param names match), eval mode uses them
    identically, fuse_relu eval clamps, and virtual_batch_size updates
    running stats with the mean of the group statistics."""
    import flax.linen as nn

    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 4, 4, 16).astype(np.float32))

    ours_t = LeanBatchNorm(momentum=0.9, epsilon=1e-5)
    flax_t = nn.BatchNorm(use_running_average=False, momentum=0.9,
                          epsilon=1e-5)
    v0 = flax_t.init(jax.random.PRNGKey(0), x)
    y_f, upd_f = flax_t.apply(v0, x, mutable=["batch_stats"])
    y_o, upd_o = ours_t.apply(v0, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_o), np.asarray(y_f),
                               rtol=2e-4, atol=2e-4)
    for k in ("mean", "var"):
        np.testing.assert_allclose(
            np.asarray(upd_o["batch_stats"][k]),
            np.asarray(upd_f["batch_stats"][k]), rtol=1e-4, atol=1e-5)

    ours_e = LeanBatchNorm(use_running_average=True, epsilon=1e-5)
    flax_e = nn.BatchNorm(use_running_average=True, epsilon=1e-5)
    v1 = {"params": v0["params"], "batch_stats": upd_f["batch_stats"]}
    np.testing.assert_allclose(
        np.asarray(ours_e.apply(v1, x)),
        np.asarray(flax_e.apply(v1, x)), rtol=2e-4, atol=2e-4)
    # fuse_relu in eval mode clamps exactly like a separate relu.
    np.testing.assert_allclose(
        np.asarray(LeanBatchNorm(use_running_average=True,
                                 fuse_relu=True).apply(v1, x)),
        np.asarray(jax.nn.relu(flax_e.apply(v1, x))),
        rtol=2e-4, atol=2e-4)

    # Ghost running stats: mean over the per-group statistics.
    ghost = LeanBatchNorm(momentum=0.9, virtual_batch_size=2)
    _, upd_g = ghost.apply(v0, x, mutable=["batch_stats"])
    flat = np.asarray(x)
    means = np.stack([flat[i * 2:(i + 1) * 2].reshape(-1, 16).mean(0)
                      for i in range(4)])
    np.testing.assert_allclose(
        np.asarray(upd_g["batch_stats"]["mean"]),
        0.9 * 0.0 + 0.1 * means.mean(0), rtol=1e-4, atol=1e-5)

    # One ghost group is plain BN: the running statistics stay (C,)-shaped
    # and match flax's (a groups == 1 path once collapsed them to a
    # cross-channel scalar).
    one = LeanBatchNorm(momentum=0.9, epsilon=1e-5, virtual_batch_size=8)
    _, upd_1 = one.apply(v0, x, mutable=["batch_stats"])
    for k in ("mean", "var"):
        got = np.asarray(upd_1["batch_stats"][k])
        assert got.shape == (16,), got.shape
        np.testing.assert_allclose(
            got, np.asarray(upd_f["batch_stats"][k]), rtol=1e-4, atol=1e-5)

    # virtual_batch_size must divide the batch.
    with pytest.raises(ValueError):
        LeanBatchNorm(virtual_batch_size=3).apply(
            v0, x, mutable=["batch_stats"])


def test_lean_bn_remat_policy_grads_match():
    """bn_remat_policy: gradients through jax.checkpoint with the
    BN-scoped policy (normalize outputs recomputed, everything else
    saved) match the un-remat'd gradients exactly."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(6, 4, 4, 8).astype(np.float32))
    mod = LeanBatchNorm(momentum=0.9)
    import flax.linen as nn
    v0 = nn.BatchNorm(use_running_average=False).init(
        jax.random.PRNGKey(0), x)

    def f(x):
        y, _ = mod.apply(v0, x, mutable=["batch_stats"])
        return jnp.sum(y ** 2)

    g_plain = jax.grad(f)(x)
    g_remat = jax.grad(jax.checkpoint(f, policy=bn_remat_policy()))(x)
    np.testing.assert_allclose(np.asarray(g_remat), np.asarray(g_plain),
                               rtol=1e-5, atol=1e-6)


def test_lean_sync_bn_matches_global_batch():
    """axis_name (in-jit) sync for the lean path over a 4-way sharded
    batch equals plain lean BN over the concatenated batch under the
    canonical DP loss contract (cf. test_sync_bn_matches_global_batch
    for the Pallas path)."""
    from jax.sharding import Mesh, PartitionSpec as P

    n, M, C = 4, 64, 32
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(n * M, C).astype(np.float32)) * 1.5 + 0.3
    w = jnp.asarray(rng.randn(n * M, C).astype(np.float32))
    gamma = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(C).astype(np.float32))
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("dp",))

    def global_loss(x, gamma, beta):
        y, mean, var = lean_batch_norm_train(x, gamma, beta, 1e-5)
        return jnp.sum(y * w), (mean, var)

    (l_g, (mean_g, var_g)), g_g = jax.value_and_grad(
        global_loss, argnums=(0, 1, 2), has_aux=True)(x, gamma, beta)

    def sharded_loss(xs, gamma, beta, ws):
        y, mean, var = lean_batch_norm_train(
            xs, gamma, beta, 1e-5, False, 1, "dp")
        return jnp.sum(y * ws)

    fwd = jax.jit(jax.shard_map(
        lambda xs, gamma, beta: lean_batch_norm_train(
            xs, gamma, beta, 1e-5, False, 1, "dp"),
        mesh=mesh, in_specs=(P("dp"), P(), P()),
        out_specs=(P("dp"), P(None), P(None)), check_vma=False))
    y_s, mean_s, var_s = fwd(x, gamma, beta)

    grad = jax.jit(jax.shard_map(
        jax.grad(sharded_loss, argnums=(0, 1, 2)),
        mesh=mesh, in_specs=(P("dp"), P(), P(), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp")), check_vma=False))
    dx_s, dgamma_s, dbeta_s = grad(x, gamma, beta, w)

    np.testing.assert_allclose(np.asarray(mean_s), np.asarray(mean_g),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var_s), np.asarray(var_g),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(y_s * w)), float(l_g),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dx_s), np.asarray(g_g[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dgamma_s).reshape(n, C).sum(0), np.asarray(g_g[1]),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(dbeta_s).reshape(n, C).sum(0), np.asarray(g_g[2]),
        rtol=1e-4, atol=1e-4)


def _rename_bn(tree, a="BatchNorm", b="LeanBatchNorm"):
    if isinstance(tree, dict):
        return {k.replace(a, b) if k.startswith(a) else k:
                _rename_bn(v, a, b) for k, v in tree.items()}
    return tree


def test_lean_resnet_matches_stock_resnet():
    """ResNet(norm='lean') with flax-BN params transplanted (module
    class names differ; structure and call order do not) produces the
    same outputs, running-stat updates, and parameter gradients as the
    stock norm='batch' model — the model-level wiring proof, fused
    norm+relu pairs included."""
    from horovod_tpu.models.resnet import ResNet, BottleneckBlock

    def build(norm):
        return ResNet(stage_sizes=[1], block_cls=BottleneckBlock,
                      num_classes=5, num_filters=8, dtype=jnp.float32,
                      norm=norm)

    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(4, 16, 16, 3).astype(np.float32))
    v_b = build("batch").init(jax.random.PRNGKey(0), x, train=False)
    v_l = {"params": _rename_bn(v_b["params"]),
           "batch_stats": _rename_bn(v_b["batch_stats"])}
    v_l_check = build("lean").init(jax.random.PRNGKey(0), x, train=False)
    assert jax.tree_util.tree_structure(v_l["params"]) == \
        jax.tree_util.tree_structure(v_l_check["params"])

    y_b, upd_b = build("batch").apply(v_b, x, train=True,
                                      mutable=["batch_stats"])
    y_l, upd_l = build("lean").apply(v_l, x, train=True,
                                     mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_l), np.asarray(y_b),
                               rtol=2e-4, atol=2e-4)
    stats_l = dict(jax.tree_util.tree_leaves_with_path(
        _rename_bn(upd_l["batch_stats"], "LeanBatchNorm", "BatchNorm")))
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            upd_b["batch_stats"]):
        np.testing.assert_allclose(np.asarray(stats_l[path]),
                                   np.asarray(leaf), rtol=1e-4,
                                   atol=1e-5, err_msg=str(path))

    def loss(model, variables, params):
        vv = {"params": params, "batch_stats": variables["batch_stats"]}
        y, _ = model.apply(vv, x, train=True, mutable=["batch_stats"])
        return jnp.sum(y ** 2)

    g_b = jax.grad(lambda p: loss(build("batch"), v_b, p))(v_b["params"])
    g_l = jax.grad(lambda p: loss(build("lean"), v_l, p))(v_l["params"])
    g_l_cmp = dict(jax.tree_util.tree_leaves_with_path(
        _rename_bn(g_l, "LeanBatchNorm", "BatchNorm")))
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_b):
        np.testing.assert_allclose(np.asarray(g_l_cmp[path]),
                                   np.asarray(leaf), rtol=5e-3,
                                   atol=5e-3, err_msg=str(path))


def test_resnet_lean_variant_one_step():
    """ResNet50Lean end to end: one train step, finite loss and grads
    (`norm="lean"`; ROADMAP S7 decides it against stock on the chip)."""
    from horovod_tpu.models import ResNet50Lean

    model = ResNet50Lean(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)

    def loss_fn(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return jnp.mean(logits ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.e2e
def test_sync_bn_host_plane_2rank_bitwise(run_launcher):
    """2-rank e2e: lean BN with host-collective stats sync (plain jit,
    ordered io_callback plane). Stats equal the global batch AND are
    bitwise rank-identical; the backward's dx matches the global-batch
    reference."""
    result = run_launcher(2, "bn_sync_worker.py",
                          extra_env={"JAX_PLATFORMS": "cpu",
                                     "BN_SYNC_MODE": "world"},
                          timeout=600)
    assert result.returncode == 0, result.stdout + result.stderr
    for marker in ("PASS world_stats_global_and_bitwise",
                   "PASS world_backward_global_dx",
                   "PASS bn_sync_worker_done"):
        assert marker in result.stdout, (marker, result.stdout)


@pytest.mark.e2e
def test_sync_bn_group_scoped_2x2_mesh(run_launcher):
    """4-rank e2e under hvd.init(model_parallel=2): sync BN scoped to
    the batch group of the 2-D mesh (docs/GROUPS.md composition). Stats
    are bitwise identical WITHIN each batch group, equal that group's
    global batch, and DIFFER across groups."""
    result = run_launcher(4, "bn_sync_worker.py",
                          extra_env={"JAX_PLATFORMS": "cpu",
                                     "BN_SYNC_MODE": "mesh"},
                          timeout=600)
    assert result.returncode == 0, result.stdout + result.stderr
    for marker in ("PASS mesh_group_scoped_sync_bn",
                   "PASS bn_sync_worker_done"):
        assert marker in result.stdout, (marker, result.stdout)


def test_sync_batch_norm_stats_wrapper():
    """hvd.jax.sync_batch_norm_stats: the jax-wrapper plumbing under
    sync BN — partial (sum, sumsq) in, (mean, var, global_count) out.
    Single-process world: the host allreduce is identity, so the
    result must equal the local statistics exactly."""
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax

    hvd.init()
    rng = np.random.RandomState(11)
    x = rng.randn(64, 8).astype(np.float32)
    s = jnp.asarray(x.sum(0))
    ss = jnp.asarray((x * x).sum(0))
    mean, var, count = hvd_jax.sync_batch_norm_stats(s, ss, x.shape[0],
                                                     name="t_sync_bn")
    assert count == x.shape[0] * hvd.size()
    np.testing.assert_allclose(np.asarray(mean), x.mean(0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), x.var(0), rtol=1e-4,
                               atol=1e-5)
