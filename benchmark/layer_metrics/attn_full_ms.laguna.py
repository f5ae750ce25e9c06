"""`attn_full_ms` for the Laguna cell: everything under `hvd_attn_full` in its
two full layers (the norm before the attention, the 48-head q, the 8-head k
and v, the gate and the output projections, YaRN's rotation of half of each
head at base 500000, the causal flash kernels at group 6, the gate's product,
the residual add), both directions and the recomputed forwards (see
`attn_full_ms.py`; `laguna_reduce.py`)."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.attn_ms(trace, context, "full")
