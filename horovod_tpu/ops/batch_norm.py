"""Fused Pallas BatchNorm statistics for TPU.

Built to attack the PERF.md profile's biggest non-conv line
(`convert_reduce_fusion`, ~29 ms/step on ResNet-50 batch 256).
MEASURED OUTCOME (v5e, PERF.md "negative result" section): the stats
kernels beat XLA's reductions (~17.6 vs 29 ms/step) but the 53 Pallas
islands per direction cost ~80 ms/step in fusion-boundary copies/
reshapes/unfused masks — stock XLA BN wins for deep conv nets. Use
`PallasBatchNorm` where norm layers are few and wide; it is also the
package's sync-BN implementation (`axis_name`). Both reductions the
op needs —

* forward: per-channel sum and sum-of-squares of the activation, and
* backward: per-channel sum(dy) and sum(dy * x_hat)

— are computed by ONE Pallas kernel each: a single bf16 read of the
activation block, f32 accumulation in registers, both reductions of the
pair emitted together (XLA's lowering builds convert+reduce fusions per
reduction). The normalize / dx elementwise math stays in XLA on purpose:
there it fuses into neighboring producers/consumers (residual adds, ReLU
masks — the `multiply_add_fusion` lines), which a Pallas island cannot.

The reference delegates BN to cuDNN (no analogue source); this is the
TPU-native equivalent of its fused-BN dependence. Correctness is pinned
against `flax.linen.BatchNorm` in tests (interpret mode on CPU); on the
v5e it lost to XLA's fusions (1348 against 2355 img/s, r04 capture;
PERF.md section 6), and no benchmark cell runs it.

Layout contract: activations reshaped to (M, C), stats over axis 0.
M must be divisible by the block size (the caller picks the largest
power-of-two divisor within a VMEM byte budget; if that is < 8 rows the
plain XLA path is used — tiny inputs don't carry the bottleneck).
Narrow-channel layers (C <= 64, i.e. k*C stays within the 128-lane
register) are lane-packed: k rows fold into the lane dimension so every
VPU lane is live, with a (k, C) sum after the kernel. 64 < C < 128
cannot pack a whole row and keeps C lanes live.
"""

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu import profile

# ~16 MB VMEM/core; blocks are double-buffered (and the grad kernel
# reads two operands), so stay well under: 4 MB for the one-input
# stats pass, 2 MB per input for the two-input grad pass.
_STATS_BLOCK_BYTES = 4 * 1024 * 1024
_GRAD_BLOCK_BYTES = 2 * 1024 * 1024


def _pick_bm(M, C, itemsize, cap_bytes):
    """Largest power-of-two divisor of M whose (bm, C) block fits the
    byte budget. Blocks must be BIG: a 1024-row cap put the ResNet-50
    stem (M=3.2M) at ~3.1k sequential grid steps, and per-step overhead
    across 53 BN layers fwd+bwd cost more than the fused read saved
    (measured 189 vs 110 ms/step on v5e). At 4 MB the stem is 98
    steps."""
    # VMEM pads the lane dim to the next 128 multiple (C=64 -> 128,
    # C=288 -> 384), so budget by the padded width.
    padded_c = ((C + 127) // 128) * 128
    cap_rows = max(8, cap_bytes // (padded_c * itemsize))
    bm = 1
    while bm * 2 <= cap_rows and M % (bm * 2) == 0:
        bm *= 2
    return bm


def _pack_factor(M, C, itemsize, cap_bytes):
    """Lane packing: view (M, C) as (M/k, k*C) so narrow-channel layers
    (ResNet stem C=64) fill the VPU's 128 lanes; channel c lives at
    lanes c, C+c, ..., folded by a cheap (2, k, C) sum after the call.
    Only pack when the packed shape still yields a >=8-row block."""
    k = 1
    while C * (k * 2) <= 128 and M % (k * 2) == 0:
        k *= 2
    while k > 1 and _pick_bm(M // k, k * C, itemsize, cap_bytes) < 8:
        k //= 2
    return k


def _plan(shape, dtype, block_m, cap_bytes):
    """(k, Mp, Cp, bm) for a (M, C) reduction: pack factor, packed
    shape, block rows. An explicit block_m disables packing (tests pin
    block-size semantics on the unpacked layout)."""
    M, C = shape
    itemsize = jnp.dtype(dtype).itemsize
    k = 1 if block_m else _pack_factor(M, C, itemsize, cap_bytes)
    Mp, Cp = M // k, k * C
    bm = block_m or _pick_bm(Mp, Cp, itemsize, cap_bytes)
    return k, Mp, Cp, bm


def _fold(out, k, C):
    """Undo lane packing on a (2, k*C) kernel output."""
    return out.reshape(2, k, C).sum(axis=1) if k > 1 else out


def _stats_kernel(x_ref, out_ref):
    i = pl.program_id(0)
    xb = x_ref[...].astype(jnp.float32)
    blk = jnp.stack([jnp.sum(xb, axis=0), jnp.sum(xb * xb, axis=0)])

    @pl.when(i == 0)
    def _():
        out_ref[...] = blk

    @pl.when(i > 0)
    def _():
        out_ref[...] = out_ref[...] + blk


def batch_norm_stats(x2d, interpret=False, block_m=None):
    """Per-channel (sum, sum_of_squares) of a (M, C) array in one
    bf16-read f32-accumulate pass. Returns two (C,) f32 arrays."""
    M, C = x2d.shape
    k, Mp, Cp, bm = _plan(x2d.shape, x2d.dtype, block_m,
                          _STATS_BLOCK_BYTES)
    xp = x2d.reshape(Mp, Cp) if k > 1 else x2d
    out = pl.pallas_call(
        _stats_kernel,
        name=profile.BN_STATS,
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, Cp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, Cp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, Cp), jnp.float32),
        interpret=interpret,
    )(xp)
    out = _fold(out, k, C)
    return out[0], out[1]


def _grad_stats_kernel(dy_ref, x_ref, mean_ref, rstd_ref, out_ref):
    i = pl.program_id(0)
    dy = dy_ref[...].astype(jnp.float32)
    xb = x_ref[...].astype(jnp.float32)
    xhat = (xb - mean_ref[...]) * rstd_ref[...]
    blk = jnp.stack([jnp.sum(dy, axis=0), jnp.sum(dy * xhat, axis=0)])

    @pl.when(i == 0)
    def _():
        out_ref[...] = blk

    @pl.when(i > 0)
    def _():
        out_ref[...] = out_ref[...] + blk


def batch_norm_grad_stats(dy2d, x2d, mean, rstd, interpret=False,
                          block_m=None):
    """Per-channel (sum(dy), sum(dy * x_hat)) — i.e. (d_beta, d_gamma)
    — in one fused read of dy and x. mean/rstd are (C,) f32."""
    M, C = x2d.shape
    # Budget by the wider operand: the public API allows f32 dy with
    # bf16 x, and the dy block must fit the per-input budget too.
    wider = max((dy2d.dtype, x2d.dtype), key=lambda d: jnp.dtype(d).itemsize)
    k, Mp, Cp, bm = _plan(x2d.shape, wider, block_m, _GRAD_BLOCK_BYTES)
    dyp = dy2d.reshape(Mp, Cp) if k > 1 else dy2d
    xp = x2d.reshape(Mp, Cp) if k > 1 else x2d
    # Packed lane l holds channel l % C, so tile the per-channel stats.
    meanp = jnp.tile(mean, k) if k > 1 else mean
    rstdp = jnp.tile(rstd, k) if k > 1 else rstd
    out = pl.pallas_call(
        _grad_stats_kernel,
        name=profile.BN_GRAD_STATS,
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, Cp), lambda i: (i, 0)),
            pl.BlockSpec((bm, Cp), lambda i: (i, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((2, Cp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, Cp), jnp.float32),
        interpret=interpret,
    )(dyp, xp, meanp.reshape(1, Cp), rstdp.reshape(1, Cp))
    out = _fold(out, k, C)
    return out[0], out[1]


def _use_kernel(M):
    # The max(8, ...) floor in _pick_bm means the kernel-usable test
    # reduces to "M has a power-of-two divisor >= 8".
    return M % 8 == 0


def _stats(x2d, interpret):
    M, C = x2d.shape
    if interpret is not None and _use_kernel(M):
        s, ss = batch_norm_stats(x2d, interpret)
    else:
        xf = x2d.astype(jnp.float32)
        s, ss = jnp.sum(xf, axis=0), jnp.sum(xf * xf, axis=0)
    return s, ss


def _bn_train_fwd(x2d, gamma, beta, eps, interpret, axis_name=None):
    M, C = x2d.shape
    s, ss = _stats(x2d, interpret)
    if axis_name is not None:
        # Cross-replica (sync) BN: the kernels produce per-device
        # partial sums; one packed psum over the data axis makes the
        # statistics global. M_g = M * group size (equal shards).
        s, ss = jax.lax.psum((s, ss), axis_name)
        M = M * jax.lax.psum(1, axis_name)
    mean = s / M
    var = jnp.maximum(ss / M - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    a = gamma * rstd
    b = beta - mean * a
    # Normalize stays in XLA: it fuses with neighbors (residual/ReLU).
    y = (x2d.astype(jnp.float32) * a + b).astype(x2d.dtype)
    return (y, mean, var), (x2d, gamma, mean, rstd)


def _bn_train_bwd(eps, interpret, axis_name, res, cotangents):
    gy, gmean, gvar = cotangents
    x2d, gamma, mean, rstd = res
    M, C = x2d.shape
    gyf = gy.astype(jnp.float32) if gy.dtype != jnp.float32 else gy
    xf = x2d.astype(jnp.float32)
    xhat = (xf - mean) * rstd
    if interpret is not None and _use_kernel(M):
        dbeta, dgamma = batch_norm_grad_stats(gy, x2d, mean, rstd,
                                              interpret)
    else:
        dbeta = jnp.sum(gyf, axis=0)
        dgamma = jnp.sum(gyf * xhat, axis=0)
    if axis_name is not None:
        # dx needs the GLOBAL reductions over the sync group; the
        # returned dgamma/dbeta stay local — the training loop's
        # gradient allreduce completes them (matching what autodiff
        # of a psum-of-stats formulation yields).
        dbeta_g, dgamma_g = jax.lax.psum((dbeta, dgamma), axis_name)
        Mg = M * jax.lax.psum(1, axis_name)
    else:
        dbeta_g, dgamma_g, Mg = dbeta, dgamma, M
    dx = (gamma * rstd) * (gyf - dbeta_g / Mg - xhat * (dgamma_g / Mg))
    # Direct mean/var cotangent terms (zero in training use — running
    # stats aren't differentiated — and XLA folds the add-zeros away;
    # kept exact so jax.grad through mean/var is still correct).
    dx = dx + gmean / Mg + gvar * (2.0 / Mg) * (xf - mean)
    return dx.astype(x2d.dtype), dgamma, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_batch_norm_train(x2d, gamma, beta, eps=1e-5, interpret=False,
                           axis_name=None):
    """Training-mode BN over (M, C): returns (y, mean, var) with the
    Pallas stats kernels on both the forward and the VJP path. mean /
    var are f32 batch statistics for the caller's running-stats
    update. `axis_name` enables cross-replica (sync) BN: statistics
    are psummed over that mesh axis (kernels stay per-device; one
    packed psum each way rides the ICI)."""
    return _bn_train_fwd(x2d, gamma, beta, eps, interpret, axis_name)[0]


def _bn_train_vjp_fwd(x2d, gamma, beta, eps, interpret, axis_name):
    return _bn_train_fwd(x2d, gamma, beta, eps, interpret, axis_name)


fused_batch_norm_train.defvjp(_bn_train_vjp_fwd, _bn_train_bwd)


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

    class PallasBatchNorm(nn.Module):
        """Drop-in for `nn.BatchNorm` (the subset ResNet uses) with the
        fused Pallas statistics path in training mode. Eval mode (
        `use_running_average=True`) is pure elementwise math and stays
        in XLA entirely."""
        use_running_average: bool = False
        momentum: float = 0.9
        epsilon: float = 1e-5
        dtype: Any = None
        param_dtype: Any = jnp.float32
        scale_init: Callable = nn.initializers.ones
        bias_init: Callable = nn.initializers.zeros
        axis_name: str = None  # sync BN: psum stats over this mesh axis
        # Ghost BN (virtual batches normalized independently): routed
        # through the graph-level lean path — per-group stats would
        # multiply the kernel islands, the exact round-4 failure mode.
        virtual_batch_size: int = None
        interpret: bool = False

        @nn.compact
        def __call__(self, x):
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
                return (x.astype(jnp.float32) * a + b).astype(
                    self.dtype or x.dtype)
            x2d = x.reshape(-1, C)
            if self.virtual_batch_size:
                N = x.shape[0]
                if N % self.virtual_batch_size:
                    raise ValueError(
                        "virtual_batch_size=%d does not divide the "
                        "batch %d" % (self.virtual_batch_size, N))
                groups = N // self.virtual_batch_size
                y, mean, var = lean_batch_norm_train(
                    x2d, scale, bias, self.epsilon, False,
                    groups, self.axis_name,
                    None, "lean_bn/%s" % "/".join(self.scope.path))
                if groups > 1:  # (G, C) group stats -> (C,) running
                    mean, var = mean.mean(axis=0), var.mean(axis=0)
            else:
                interpret = self.interpret
                if jax.default_backend() != "tpu" and not interpret:
                    interpret = None  # plain-XLA fallback off-TPU
                y, mean, var = fused_batch_norm_train(
                    x2d, scale, bias, self.epsilon, interpret,
                    self.axis_name)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
            return y.reshape(x.shape).astype(self.dtype or x.dtype)

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
    PallasBatchNorm = None
    LeanBatchNorm = None
