"""VGG-16 and Inception V3 in flax — the reference's other headline
benchmark models (its published 512-GPU scaling table is Inception V3 /
ResNet-101 / VGG-16, `docs/benchmarks.rst:13-14`, README.rst:75).

Same TPU-first conventions as `resnet.py`: NHWC, bf16 compute with f32
params/statistics, static shapes.
"""

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp


class VGG16(nn.Module):
    """VGG-16 (configuration D): 13 conv + 3 FC layers."""
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, kernel_size=(3, 3), dtype=self.dtype,
                       param_dtype=jnp.float32)
        x = x.astype(self.dtype)
        for i, (filters, reps) in enumerate(
                [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]):
            for j in range(reps):
                x = nn.relu(conv(filters, name="conv%d_%d" % (i, j))(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        for j, width in enumerate([4096, 4096]):
            x = nn.relu(nn.Dense(width, dtype=self.dtype,
                                 param_dtype=jnp.float32,
                                 name="fc%d" % j)(x))
            x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


class _ConvBN(nn.Module):
    """Conv + BatchNorm + ReLU, the Inception building block."""
    filters: int
    kernel: tuple
    strides: tuple = (1, 1)
    padding: Any = "SAME"
    dtype: Any = jnp.bfloat16
    bn_axis_name: Optional[str] = None  # sync BN: psum stats over this mesh axis

    @nn.compact
    def __call__(self, x, train):
        x = nn.Conv(self.filters, self.kernel, self.strides,
                    padding=self.padding, use_bias=False,
                    dtype=self.dtype, param_dtype=jnp.float32)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-3, dtype=self.dtype,
                         param_dtype=jnp.float32,
                         axis_name=self.bn_axis_name)(x)
        return nn.relu(x)


def _avgpool3(x):
    return nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")


class InceptionV3(nn.Module):
    """Inception V3 (Szegedy et al. 2015), aux head omitted (the
    reference synthetic benchmarks train the main head only)."""
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    bn_axis_name: Optional[str] = None  # sync BN over this mesh axis

    @nn.compact
    def __call__(self, x, train: bool = True):
        cbn = partial(_ConvBN, dtype=self.dtype,
                      bn_axis_name=self.bn_axis_name)
        x = x.astype(self.dtype)
        # Stem: 299x299x3 -> 35x35x192
        x = cbn(32, (3, 3), (2, 2), "VALID")(x, train)
        x = cbn(32, (3, 3), padding="VALID")(x, train)
        x = cbn(64, (3, 3))(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = cbn(80, (1, 1), padding="VALID")(x, train)
        x = cbn(192, (3, 3), padding="VALID")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))

        def inception_a(x, pool_features):
            b1 = cbn(64, (1, 1))(x, train)
            b5 = cbn(48, (1, 1))(x, train)
            b5 = cbn(64, (5, 5))(b5, train)
            b3 = cbn(64, (1, 1))(x, train)
            b3 = cbn(96, (3, 3))(b3, train)
            b3 = cbn(96, (3, 3))(b3, train)
            bp = cbn(pool_features, (1, 1))(_avgpool3(x), train)
            return jnp.concatenate([b1, b5, b3, bp], axis=-1)

        def inception_b(x):  # grid 35 -> 17
            b3 = cbn(384, (3, 3), (2, 2), "VALID")(x, train)
            bd = cbn(64, (1, 1))(x, train)
            bd = cbn(96, (3, 3))(bd, train)
            bd = cbn(96, (3, 3), (2, 2), "VALID")(bd, train)
            bp = nn.max_pool(x, (3, 3), strides=(2, 2))
            return jnp.concatenate([b3, bd, bp], axis=-1)

        def inception_c(x, c7):
            b1 = cbn(192, (1, 1))(x, train)
            b7 = cbn(c7, (1, 1))(x, train)
            b7 = cbn(c7, (1, 7))(b7, train)
            b7 = cbn(192, (7, 1))(b7, train)
            bd = cbn(c7, (1, 1))(x, train)
            bd = cbn(c7, (7, 1))(bd, train)
            bd = cbn(c7, (1, 7))(bd, train)
            bd = cbn(c7, (7, 1))(bd, train)
            bd = cbn(192, (1, 7))(bd, train)
            bp = cbn(192, (1, 1))(_avgpool3(x), train)
            return jnp.concatenate([b1, b7, bd, bp], axis=-1)

        def inception_d(x):  # grid 17 -> 8
            b3 = cbn(192, (1, 1))(x, train)
            b3 = cbn(320, (3, 3), (2, 2), "VALID")(b3, train)
            b7 = cbn(192, (1, 1))(x, train)
            b7 = cbn(192, (1, 7))(b7, train)
            b7 = cbn(192, (7, 1))(b7, train)
            b7 = cbn(192, (3, 3), (2, 2), "VALID")(b7, train)
            bp = nn.max_pool(x, (3, 3), strides=(2, 2))
            return jnp.concatenate([b3, b7, bp], axis=-1)

        def inception_e(x):
            b1 = cbn(320, (1, 1))(x, train)
            b3 = cbn(384, (1, 1))(x, train)
            b3 = jnp.concatenate([cbn(384, (1, 3))(b3, train),
                                  cbn(384, (3, 1))(b3, train)], axis=-1)
            bd = cbn(448, (1, 1))(x, train)
            bd = cbn(384, (3, 3))(bd, train)
            bd = jnp.concatenate([cbn(384, (1, 3))(bd, train),
                                  cbn(384, (3, 1))(bd, train)], axis=-1)
            bp = cbn(192, (1, 1))(_avgpool3(x), train)
            return jnp.concatenate([b1, b3, bd, bp], axis=-1)

        x = inception_a(x, 32)
        x = inception_a(x, 64)
        x = inception_a(x, 64)
        x = inception_b(x)
        for c7 in (128, 160, 160, 192):
            x = inception_c(x, c7)
        x = inception_d(x)
        x = inception_e(x)
        x = inception_e(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)
