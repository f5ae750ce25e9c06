"""Device milliseconds per step in the Kimi Delta Attention layers'
attention half, forward and backward (a recomputed forward too), every such
layer, mean over devices: everything under the scope `hvd_kda` (the norm
before the mixer, its projections, convolutions, decay and gates, the
chunked recurrence, the output projection, the residual add). A part of
`fwd_bwd_ms`; `kda_proj_ms`, `kda_gate_ms`, `kda_chunk_ms` and `kda_carry_ms`
are parts of it (with the convolutions and what lies under the mixer's scope
alone, which the `INFO` line `kda_ms_a_step` shows). Source: device trace,
self time by the program's own scope (`kimi_reduce.py`); None for a program
that names no such scope."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda")
