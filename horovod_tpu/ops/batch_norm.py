"""BatchNorm for TPU inside XLA's fusion graph (`LeanBatchNorm`,
`lean_batch_norm_train`): one-pass statistics, a custom VJP that keeps
(x, mean, rstd) alone, sync BN over a mesh axis or a process group, ghost
BN. The section below says what it does and why.

The Pallas statistics kernels that stood here until PR 60 lost their one
chip measurement (v5e, ResNet-50 at batch 256: 1348 against stock XLA
BatchNorm's 2355 img/s; 53 kernel islands a direction cost ~80 ms a step
in copies at the fusion borders) and went with their option.
"""

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Traffic-lean BatchNorm (round 10): the graph-level answer to the round-4
# island tax. PERF.md's round-4 measurement proved Pallas stats kernels the
# wrong lever for deep conv nets on TPU (the ~11 ms stats win lost ~80 ms to
# fusion-boundary copies), so this path never leaves XLA's fusion graph and
# instead makes each activation pass TOUCH FEWER BYTES:
#
# * one-pass statistics: a single VARIADIC reduce emits (sum, sum-of-squares)
#   forward and (sum(dy), sum(dy*x_hat)) backward from ONE read of the
#   activation (XLA fuses the x*x / dy*x_hat producers into the reduce), vs
#   the per-quantity convert+reduce fusions the stock lowering builds;
# * a custom_vjp that saves only (x, mean, rstd) — x is the producing conv's
#   output and already live for ITS backward — and recomputes x_hat in the
#   backward, eliminating the stored-normalized-intermediate round trip
#   autodiff of the closed-form BN expression materializes (an extra f32
#   M x C residual per layer in a bf16 model);
# * optional fused ReLU (`relu=True`): y = max(bn(x), 0) in one epilogue,
#   with the backward MASK recomputed from the pre-activation sign
#   (x_hat * gamma + beta > 0) instead of saved.
#
# The same formulation carries the distributed plane: `axis_name=` psums the
# per-device partial sums over a mesh axis (in-jit sync BN), `group=` rides
# the HOST collectives with process-group scoping (docs/GROUPS.md — sync BN
# over the batch group of a 2-D mesh), and `groups=` splits the batch into
# ghost-BN virtual batches (arxiv 1705.08741; the large-per-chip-batch
# regularizer) — all through one (G, C)-shaped stats pipeline.
# ---------------------------------------------------------------------------


def onepass_stats(a, b, axis=0):
    """(sum(a), sum(b)) over `axis` as a pair of sibling reduce fusions,
    each a SINGLE fused read of its operand chain (the cast and the
    x*x / dy*x_hat producers fuse into the reduce), f32 accumulation.

    Measured pitfall, kept as the design note: a variadic tuple
    `lax.reduce((a, b), ...)` looks like "one pass" but XLA does NOT
    fuse elementwise producers into variadic reduces — the squared
    operand MATERIALIZED as a full f32 activation buffer (2R + 1W extra
    per stats pass, verified via per-instruction `cost_analysis`).
    Sibling single-operand reduces each take a fused producer chain, so
    the pair costs two reads and zero intermediate writes."""
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return (jnp.sum(a.astype(jnp.float32), axis=axes),
            jnp.sum(b.astype(jnp.float32), axis=axes))


def _lean_sync(pair, axis_name, group, name):
    """Cross-rank reduction of a (stats_a, stats_b) pair: psum over the
    in-jit mesh axis, or one host-plane allreduce (group-scoped, stable
    name) when `group` is set. Returns (pair, replica_count)."""
    a, b = pair
    n = 1
    if axis_name is not None:
        a, b = jax.lax.psum((a, b), axis_name)
        n = jax.lax.psum(1, axis_name)
    elif group is not None:
        import horovod_tpu.jax as hvd_jax
        from horovod_tpu import groups as _grp
        grp = None if group == "world" else group
        stacked = hvd_jax.allreduce(jnp.stack([a, b]), average=False,
                                    name=name, group=grp)
        a, b = stacked[0], stacked[1]
        n = _grp.group_size(grp)
    return (a, b), n


def _ghost_view(x, groups):
    """(x reshaped for ghost groups, reduce axes, per-channel-stat
    shape for broadcasting). The leading batch axis splits into
    (groups, N//groups); the reshape is a leading-dim split — a
    bitcast, never a layout change (collapsing to (M, C) measured as a
    REGRESSION: the 2-D view through the custom-VJP boundary forced
    layout copies into the neighboring conv backward fusions)."""
    if groups == 1:
        return x, tuple(range(x.ndim - 1)), (x.shape[-1],)
    xg = x.reshape((groups, x.shape[0] // groups) + x.shape[1:])
    return xg, tuple(range(1, xg.ndim - 1)), \
        (groups,) + (1,) * (x.ndim - 1) + (x.shape[-1],)


def _lean_fwd(x, gamma, beta, eps, relu, groups, axis_name, group,
              sync_name):
    C = x.shape[-1]
    dt = x.dtype
    xg, axes, bshape = _ghost_view(x, groups)
    count_local = xg.size // (groups * C)
    # f32 cast + square fuse into the reduce producer: ONE read of the
    # (possibly bf16) activation, f32 accumulation, BOTH reductions.
    xf = xg.astype(jnp.float32)
    s, ss = onepass_stats(xf, xf * xf, axis=axes)   # (C,) or (G, C)
    (s, ss), n = _lean_sync((s, ss), axis_name, group, sync_name)
    count = count_local * n
    mean = s / count
    var = jnp.maximum(ss / count - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    a = gamma * rstd                                 # f32, stat-shaped
    b = beta - mean * a
    # Normalize in the COMPUTE dtype (flax's convention: stats in f32,
    # apply in dtype) — a bf16 model's activation passes stay 2-byte.
    y = xg * a.reshape(bshape).astype(dt) + b.reshape(bshape).astype(dt)
    if relu:
        y = jnp.maximum(y, jnp.zeros((), dt))
    return (y.reshape(x.shape), mean, var), (x, gamma, beta, mean, rstd)


def _lean_bwd(eps, relu, groups, axis_name, group, sync_name, res, ct):
    gy, gmean, gvar = ct
    x, gamma, beta, mean, rstd = res
    C = x.shape[-1]
    dt = x.dtype
    xg, axes, bshape = _ghost_view(x, groups)
    count_local = xg.size // (groups * C)
    gyg = gy.reshape(xg.shape)
    mean_b = mean.reshape(bshape)
    rstd_b = rstd.reshape(bshape)
    # x_hat recomputed (never stored), in the compute dtype for the
    # elementwise chain; the f32 casts below fuse into the reduce.
    xhat = (xg - mean_b.astype(dt)) * rstd_b.astype(dt)
    if relu:
        # The forward's ReLU mask, recomputed from the pre-activation
        # sign (y_pre = x_hat * gamma + beta) — never stored.
        pre = xhat * gamma.astype(dt) + beta.astype(dt)
        gyg = jnp.where(pre > 0, gyg, jnp.zeros((), dt))
    # Both backward reductions from one fused read of (gy, x), f32
    # accumulation.
    gyf = gyg.astype(jnp.float32)
    dbeta, dgamma = onepass_stats(gyf, gyf * xhat.astype(jnp.float32),
                                  axis=axes)
    # dx needs the reductions over the FULL sync scope; the returned
    # dgamma/dbeta stay local — the training loop's gradient allreduce
    # completes them (matching autodiff of a psum-of-stats formulation).
    (dbeta_g, dgamma_g), n = _lean_sync(
        (dbeta, dgamma), axis_name, group,
        sync_name + ".bwd" if sync_name else sync_name)
    count = count_local * n
    a_b = (gamma * rstd_b).astype(dt)
    dx = a_b * (gyg - (dbeta_g.reshape(bshape) / count).astype(dt) -
                xhat * (dgamma_g.reshape(bshape) / count).astype(dt))
    # Direct mean/var cotangents (zero in training use — running stats
    # are not differentiated — and XLA folds the mul-by-zero-constant
    # away; kept exact so jax.grad through the returned stats is still
    # correct).
    gmean_b = jnp.asarray(gmean, jnp.float32).reshape(bshape)
    gvar_b = jnp.asarray(gvar, jnp.float32).reshape(bshape)
    dx = dx + (gmean_b / count).astype(dt) + \
        (gvar_b * (2.0 / count)).astype(dt) * (xg - mean_b.astype(dt))
    if groups > 1:
        dgamma = dgamma.sum(axis=0)
        dbeta = dbeta.sum(axis=0)
    return (dx.reshape(x.shape), dgamma, dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def lean_batch_norm_train(x, gamma, beta, eps=1e-5, relu=False,
                          groups=1, axis_name=None, group=None,
                          sync_name="lean_bn"):
    """Training-mode traffic-lean BN over a channels-last activation of
    any rank (stats over every leading axis): returns (y, mean, var)
    with batch statistics in f32 for the caller's running-stats update.

    Pure XLA on both passes (no kernel islands — the round-4 lesson)
    and no layout-changing views (x keeps its native NHWC shape through
    the custom-VJP boundary): one-pass variadic-reduce statistics,
    residuals limited to (x, mean, rstd), x_hat (and the ``relu=True``
    mask, from the pre-activation sign) recomputed in the backward.

    ``groups`` > 1 is ghost BN: the leading batch axis splits into
    `groups` virtual batches normalized independently (mean/var come
    back as (G, C)). ``axis_name`` syncs statistics over an in-jit mesh
    axis; ``group`` syncs through the HOST collectives scoped to a
    process group (docs/GROUPS.md; pass the string "world" for
    whole-world sync) under the stable collective name ``sync_name`` —
    both make the statistics global over the participating replicas
    (sync BN).
    """
    return _lean_fwd(x, gamma, beta, eps, relu, groups, axis_name,
                     group, sync_name)[0]


lean_batch_norm_train.defvjp(_lean_fwd, _lean_bwd)


def bn_remat_policy():
    """Checkpoint policy for BN-scoped rematerialization: saves every
    residual EXCEPT the normalize-pass outputs (tagged
    ``hvd_bn_norm`` by :class:`LeanBatchNorm`), so the normalized
    activations are recomputed in the backward instead of stored —
    ``nn.remat(Block, policy=bn_remat_policy())`` or
    ``ResNet(..., bn_remat=True)``."""
    return jax.checkpoint_policies.save_anything_except_these_names(
        "hvd_bn_norm")


try:
    import flax.linen as nn

    class LeanBatchNorm(nn.Module):
        """Drop-in for ``nn.BatchNorm`` (the subset the conv zoo uses)
        on the traffic-lean graph-level path: one-pass variadic-reduce
        statistics, custom-VJP residuals limited to (x, mean, rstd),
        x_hat (and the ``fuse_relu`` mask) recomputed in the backward —
        never leaving XLA's fusion graph (the round-4 island-tax
        lesson, PERF.md).

        ``virtual_batch_size`` enables ghost BN: the leading batch dim
        splits into ``N // virtual_batch_size`` groups normalized
        independently (running stats average the group statistics).
        ``axis_name`` is in-jit cross-replica sync BN (psum over the
        mesh axis); ``sync_group`` syncs through the HOST collectives
        scoped to a process group — e.g. ``hvd.batch_group()`` under a
        2-D mesh (docs/GROUPS.md), or the string "world". The host
        collective's name derives from the module path (rank-identical
        by construction) unless ``sync_name`` is set.

        Outputs are tagged ``hvd_bn_norm`` for
        :func:`bn_remat_policy`-scoped rematerialization."""
        use_running_average: bool = False
        momentum: float = 0.9
        epsilon: float = 1e-5
        dtype: Any = None
        param_dtype: Any = jnp.float32
        scale_init: Callable = nn.initializers.ones
        bias_init: Callable = nn.initializers.zeros
        axis_name: str = None        # in-jit sync BN (psum)
        sync_group: Any = None       # host-plane sync BN (docs/GROUPS.md)
        sync_name: str = None
        virtual_batch_size: int = None  # ghost BN
        fuse_relu: bool = False

        @nn.compact
        def __call__(self, x):
            from jax.ad_checkpoint import checkpoint_name

            C = x.shape[-1]
            scale = self.param("scale", self.scale_init, (C,),
                               self.param_dtype)
            bias = self.param("bias", self.bias_init, (C,),
                              self.param_dtype)
            ra_mean = self.variable("batch_stats", "mean",
                                    lambda: jnp.zeros(C, jnp.float32))
            ra_var = self.variable("batch_stats", "var",
                                   lambda: jnp.ones(C, jnp.float32))
            if self.use_running_average:
                a = scale * jax.lax.rsqrt(ra_var.value + self.epsilon)
                b = bias - ra_mean.value * a
                y = x.astype(jnp.float32) * a + b
                if self.fuse_relu:
                    y = jnp.maximum(y, 0.0)
                return y.astype(self.dtype or x.dtype)
            groups = 1
            if self.virtual_batch_size:
                N = x.shape[0]
                if N % self.virtual_batch_size:
                    raise ValueError(
                        "virtual_batch_size=%d does not divide the "
                        "batch %d" % (self.virtual_batch_size, N))
                groups = N // self.virtual_batch_size
            sync_name = self.sync_name or \
                "lean_bn/%s" % "/".join(self.scope.path)
            # x keeps its native shape through the op: a collapsed
            # (M, C) view through the custom-VJP boundary measured as
            # layout copies in the neighboring conv backward.
            y, mean, var = lean_batch_norm_train(
                x, scale, bias, self.epsilon,
                self.fuse_relu, groups, self.axis_name,
                self.sync_group, sync_name)
            if not self.is_initializing():
                m = self.momentum
                # Ghost groups contribute equally to the running stats
                # (mean-of-group-stats — the standard ghost-BN running
                # estimate).
                mean_u = mean if groups == 1 else mean.mean(axis=0)
                var_u = var if groups == 1 else var.mean(axis=0)
                ra_mean.value = m * ra_mean.value + (1 - m) * mean_u
                ra_var.value = m * ra_var.value + (1 - m) * var_u
            y = checkpoint_name(y, "hvd_bn_norm")
            return y.astype(self.dtype or x.dtype)
except ImportError:  # pragma: no cover - flax is baked into this env
    LeanBatchNorm = None
