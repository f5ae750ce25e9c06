"""The pass between the two projections of a double-gated short convolution
(`models/transformer.py::GatedShortConv`; LFM2, arXiv:2511.23404): on the
in-projection's three equal column blocks [B | G | z] of `proj` [.., L, 3 C]
and the taps `conv_kernel` [taps, C],

    u    = B * z                                   elementwise
    c[t] = sum_j conv_kernel[j] * u[t - (taps - 1 - j)]
                                depthwise, causal: tap taps - 1 meets the
                                current token, zeros before the sequence,
                                no activation, no bias
    y    = G * c                                   [.., L, C]

f32 from the widened input to the one rounding of y. It reads no position and
no tap reaches across the batch.

In jnp: XLA's fusions own it (`gate_plan` says so: the op has this one path
until a kernel is given it). A one-pass form moves `gate_plan`'s bytes, 8 a token and channel
forward (three blocks read, y written, in bf16) and 14 backward (the blocks
and y's cotangent read, the blocks' cotangent written); what XLA's passes
take against that on the v5e is in `PERF.md` s6 (PR 65), with
`examples/sconv_sweep.py`, which times this call alone.
"""

import jax.numpy as jnp


def gate_plan(B, L, C, taps, dtype=jnp.bfloat16):
    """How `gated_conv` runs a call on `proj` [B, L, 3 C] in `dtype` against
    [taps, C] taps (`hvd.profile.sconv_plan`; the op's one path today):

        {"path": "jnp",
         "bytes": {"forward": ..., "backward": ...} a one-pass form moves}

    The bytes are the least any implementation moves, whatever runs the
    call: the three column blocks read once and y written once (backward:
    the blocks and y's cotangent read, the blocks' cotangent written), the
    taps and their f32 gradient."""
    itemsize = jnp.dtype(dtype).itemsize
    block = B * L * C * itemsize
    weights = taps * C * 4
    return {"path": "jnp",
            "bytes": {"forward": 4 * block + weights,
                      "backward": 7 * block + 2 * weights}}


def gated_conv(proj, conv_kernel):
    """proj [B, L, 3 C] (the mixer's in-projection: B | G | z, C columns
    each), conv_kernel [taps, C] f32 -> y [B, L, C] in `proj`'s dtype: G
    times the causal depthwise convolution of B * z (module text); the two
    products and the tap sum in f32, one rounding."""
    taps, C = conv_kernel.shape
    B, L, width = proj.shape
    if width != 3 * C:
        raise ValueError("gated_conv: proj has %d columns, the taps are of "
                         "%d channels: three blocks of them" % (width, C))
    f32 = jnp.float32
    b, g, z = (proj[..., i * C:(i + 1) * C] for i in range(3))
    # Tap j reads the token taps - 1 - j behind: zeros before the sequence.
    # Padded as they are and widened a tap at a time (as `Mamba2`'s and
    # `ops/kda_conv.py::_qkv_jnp`'s shifted sums): no f32 copy of a block.
    pad = ((0, 0), (taps - 1, 0), (0, 0))
    bp, zp = jnp.pad(b, pad), jnp.pad(z, pad)
    c = sum(conv_kernel[j].astype(f32)
            * (bp[:, j:j + L].astype(f32) * zp[:, j:j + L].astype(f32))
            for j in range(taps))
    return (g.astype(f32) * c).astype(proj.dtype)
