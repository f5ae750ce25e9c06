"""ResNet v1.5 in flax — the flagship benchmark model.

Capability parity: the reference benchmarks ResNet-50 via
`examples/tensorflow2_synthetic_benchmark.py:24-37` (Keras applications
ResNet50) and `examples/pytorch_imagenet_resnet50.py`; its headline scaling
numbers are ResNet-101 (`docs/benchmarks.rst:13-14,43`).

TPU-first choices (not inherited from the reference):
* NHWC layout — the natural layout for TPU convolutions; XLA tiles the
  channel dim onto the 128-lane MXU minor dimension.
* bfloat16 compute / float32 params + batch-norm statistics: matmul/conv
  inputs are cast to bf16 (MXU native), accumulation and state stay f32.
* Static shapes everywhere; stride-2 projection shortcuts (v1.5: the 3x3
  conv carries the stride, matching the torchvision model the reference
  benchmarks).
"""

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import profile

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic two-conv residual block (ResNet-18/34)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)
    # Fused norm+activation factory (norm="lean"): the norm module
    # applies the ReLU itself so its backward recomputes the mask from
    # the pre-activation sign instead of storing it. None = norm then
    # act separately (every other norm path).
    norm_act: Optional[ModuleDef] = None

    def _norm_act(self, y):
        if self.norm_act is not None:
            return self.norm_act()(y)
        return self.act(self.norm()(y))

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self._norm_act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3(stride) -> 1x1 bottleneck (ResNet-50/101/152, v1.5)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)
    norm_act: Optional[ModuleDef] = None  # see ResNetBlock

    def _norm_act(self, y):
        if self.norm_act is not None:
            return self.norm_act()(y)
        return self.act(self.norm()(y))

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self._norm_act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self._norm_act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 over NHWC inputs.

    `norm="group"` swaps BatchNorm for GroupNorm(32) — the PERF.md
    roofline experiment: BN's cross-batch statistics force f32
    convert+reduce passes over every activation (the measured HBM
    bottleneck), while GN's within-sample stats stay in the compute
    dtype with f32 reduce accumulation only.

    `norm="lean"` is the round-10 traffic-lean graph-level BN
    (ops/batch_norm.LeanBatchNorm): one-pass variadic-reduce stats, a
    custom VJP that recomputes x_hat (and, for the norm+ReLU pairs, the
    ReLU mask) instead of storing them, never leaving XLA's fusion
    graph — the shape the round-4 island-tax measurement demanded."""
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    norm: str = "batch"
    # Cross-replica (sync) BN: psum batch statistics over this mesh
    # axis (the flax and the lean norm paths both support it). The
    # standard choice at small per-chip batch, where per-device BN
    # statistics get noisy.
    bn_axis_name: Optional[str] = None
    # Host-plane sync-BN scope (norm="lean"):
    # a hvd.ProcessGroup (e.g. hvd.batch_group() under a 2-D mesh) or
    # the string "world" — statistics ride the host collectives
    # group-scoped (docs/GROUPS.md).
    bn_sync_group: Any = None
    # Ghost BN (norm="lean"): virtual batch each normalization
    # group sees; None = the whole per-replica batch.
    bn_virtual_batch_size: Optional[int] = None
    # BN-scoped remat (norm="lean"): recompute the normalize-pass
    # outputs in the backward instead of saving them
    # (ops.batch_norm.bn_remat_policy applied per residual block).
    bn_remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        norm_act = None
        if self.norm == "none":
            # Normalizer-free roofline probe: measures the conv-only
            # ceiling (NF-ResNet-style models train like this with
            # weight standardization + scalers, which add no
            # activation-pass traffic).
            def norm(name=None, scale_init=None):
                return lambda y: y
        elif self.norm == "group":
            norm = partial(nn.GroupNorm, num_groups=32, epsilon=1e-5,
                           dtype=self.dtype, param_dtype=jnp.float32)
        elif self.norm == "lean":
            # Traffic-lean graph-level BN (round 10, ops/batch_norm.py).
            from horovod_tpu.ops.batch_norm import LeanBatchNorm
            norm = partial(LeanBatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32,
                           axis_name=self.bn_axis_name,
                           sync_group=self.bn_sync_group,
                           virtual_batch_size=self.bn_virtual_batch_size)
            # The norm+ReLU pairs fuse (backward mask recomputed from
            # the pre-activation sign); block-final norms and the
            # post-residual-add ReLUs stay separate.
            norm_act = partial(norm, fuse_relu=True)
        elif self.norm == "batch":
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32,
                           axis_name=self.bn_axis_name)
        else:
            raise ValueError("norm=%r: 'batch', 'none', 'group' or 'lean'"
                             % (self.norm,))
        act = nn.relu

        block_cls = self.block_cls
        if self.bn_remat:
            from horovod_tpu.ops.batch_norm import bn_remat_policy
            block_cls = nn.remat(block_cls, policy=bn_remat_policy())

        # The scopes are the profiler's names for the model's parts
        # (hvd.profile); flax's module names sit inside them.
        with jax.named_scope(profile.STEM):
            x = x.astype(self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
            if norm_act is not None:
                x = norm_act(name="bn_init")(x)
            else:
                x = act(norm(name="bn_init")(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            with jax.named_scope(profile.STAGES[i]):
                for j in range(block_size):
                    strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                    x = block_cls(self.num_filters * 2 ** i, conv=conv,
                                  norm=norm, act=act, strides=strides,
                                  norm_act=norm_act)(x)
        with jax.named_scope(profile.HEAD):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=self.dtype,
                         param_dtype=jnp.float32)(x)
            return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                    block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3],
                    block_cls=BottleneckBlock)
ResNet50GN = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                     block_cls=BottleneckBlock, norm="group")
ResNet50NF = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                     block_cls=BottleneckBlock, norm="none")
ResNet50Lean = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                       block_cls=BottleneckBlock, norm="lean")
ResNet101NF = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                      block_cls=BottleneckBlock, norm="none")
