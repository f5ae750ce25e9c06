"""`attn_window_ms` for the Laguna cell: everything under `hvd_attn_window`
in its six window layers (the norm before the attention, the 64-head q, the
8-head k and v, the gate and the output projections, the plain rotation of
the whole head at base 10000, the flash kernels at group 8 under
`ops.BandMask(512)`, the gate's product, the residual add), both directions
and the recomputed forwards (see `attn_window_ms.py`; `laguna_reduce.py`)."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.attn_ms(trace, context, "window")
