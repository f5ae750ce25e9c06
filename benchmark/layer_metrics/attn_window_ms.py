"""Device milliseconds per step in the attention half of the WINDOW layers,
forward and backward (and the forward again where a block is recomputed),
mean over devices: everything under the scope `hvd_attn_window` (the norm
before the attention, the q, k, v and output projections, the two per-head
norms, the kind's rotation, its flash kernels, the residual add):
a window layer's query sees itself and the 1023 keys before it
(`ops.BandMask`, the band-ruled kernels), on the plain rotation. A part
of `fwd_bwd_ms`. Source: device trace, self time by the program's own scope
(`mellum_reduce.py`); None for a program that names no such scope."""

from benchmark import mellum_reduce


def read(trace, context):
    return mellum_reduce.attn_ms(trace, context, "window")
