"""`attn_full_ms` for the LFM2 cell: everything under `hvd_attn_full` in its
two attention layers (the norm before the attention, the 32-head q, the
8-head k and v and the output projections at head width 64, the norm a head
of q and k, the rotation at base 1e6, the causal flash kernels at group 4,
the residual add), both directions and the recomputed forwards (see
`attn_full_ms.py`; `lfm2_reduce.py`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.attn_ms(trace, context, "full")
