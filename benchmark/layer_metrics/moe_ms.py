"""Device milliseconds per step in the routed feed-forward, forward and
backward, every layer, mean over devices: everything under the scope
`hvd_moe` (router, top-k, sort, gathers, the experts' grouped matmuls, the
gate, the weighted sum). `moe_gmm_ms` + `moe_shuffle_ms`; a part of
`fwd_bwd_ms`. Source: device trace, self time by the program's own scope
(`moe_reduce.py`)."""

from benchmark import moe_reduce


def read(trace, context):
    return moe_reduce.ms(trace, context, "moe")
