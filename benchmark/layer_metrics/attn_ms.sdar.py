"""Device milliseconds per step in attention, forward and backward, every
block, mean over devices: everything under a block's `attn` half (the q, k,
v and output projections, the two per-head norms, rotary at positions that
repeat) and the mask-ruled flash kernels, as `mla_ms` reads latent
attention's. A part of `fwd_bwd_ms`. Source: device trace, self time by the
program's own scope (`sdar_reduce.py`)."""

from benchmark import sdar_reduce


def read(trace, context):
    return sdar_reduce.ms(trace, context, "attn")
