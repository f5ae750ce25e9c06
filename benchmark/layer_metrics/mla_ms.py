"""Device milliseconds per step in latent attention, forward and backward,
every block and the prediction module's, mean over devices: everything
under a block's `attn` half (the low-rank projections, their two norms,
rotary on the 64-wide slice, the output projection) and the flash kernels.
The hyper-connection around the branch is `hc_ms`'s. A part of `fwd_bwd_ms`.
Source: device trace, self time by the program's own scope
(`xing_reduce.py`)."""

from benchmark import xing_reduce


def read(trace, context):
    return xing_reduce.ms(trace, context, "mla")
