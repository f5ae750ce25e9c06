"""Plain reference for ResNet-50 v1.5 in training mode, on the parameter
tree `models.ResNet50` makes.

Straightforward `jax.numpy` / `lax.conv_general_dilated` in float32, NHWC,
written from the architecture (He et al., Table 1; stride on the 3x3
convolution) and not from the program's code. Call it under
`jax.default_matmul_precision("highest")`.

    stem:   7x7/2 conv (pad 3) - BN - ReLU - 3x3/2 max pool (pad 1)
    block:  1x1 conv - BN - ReLU - 3x3 conv (stride s) - BN - ReLU
            - 1x1 conv - BN ; shortcut = x, or 1x1/s conv - BN where the
            shape changes ; ReLU(shortcut + branch)
    head:   mean over H, W - dense - softmax cross entropy

BatchNorm uses the statistics of the batch it is given (training mode):
y = (x - mean) / sqrt(var + 1e-5) * scale + bias over N, H, W. Convolutions
without explicit padding pad as `SAME` does (the extra pixel goes after),
which is what the program's 3x3/2 convolutions do.
"""

import jax
import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")


def _conv(x, p, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, jnp.asarray(p["kernel"], jnp.float32), (stride, stride), padding,
        dimension_numbers=_DIMS)


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return ((x - mean) * lax.rsqrt(var + eps) * jnp.asarray(p["scale"])
            + jnp.asarray(p["bias"]))


def logits(params, images, stage_sizes=(3, 4, 6, 3)):
    """[N, classes] float32 logits of `images` [N, H, W, 3]."""
    x = jnp.asarray(images, jnp.float32)
    x = _conv(x, params["conv_init"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    index = 0
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            p = params["BottleneckBlock_%d" % index]
            stride = 2 if stage > 0 and block == 0 else 1
            y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]), p["BatchNorm_0"]))
            y = jax.nn.relu(_bn(_conv(y, p["Conv_1"], stride),
                                p["BatchNorm_1"]))
            y = _bn(_conv(y, p["Conv_2"]), p["BatchNorm_2"])
            if "conv_proj" in p:
                x = _bn(_conv(x, p["conv_proj"], stride), p["norm_proj"])
            x = jax.nn.relu(x + y)
            index += 1
    x = jnp.mean(x, axis=(1, 2))
    dense = params["Dense_0"]
    return x @ jnp.asarray(dense["kernel"], jnp.float32) + dense["bias"]


def loss(params, images, labels, stage_sizes=(3, 4, 6, 3)):
    """Mean softmax cross entropy, and the logits it came from."""
    z = logits(params, images, stage_sizes)
    logp = jax.nn.log_softmax(z)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1)), z
