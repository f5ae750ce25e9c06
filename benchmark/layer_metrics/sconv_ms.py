"""Device milliseconds per step under `hvd_sconv`: the first branch of the
LFM2 cell's eight conv layers whole (the norm before the mixer, `in_proj`
and `out_proj`, the gated pass between them, the residual add), both
directions and the forward again where a block is recomputed.
`sconv_proj_ms` + `sconv_gate_ms` + the norm and the add; a part of
`fwd_bwd_ms`. Source: device trace, self time by the program's own scope
(`lfm2_reduce.py`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.ms(trace, context)
