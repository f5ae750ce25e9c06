"""`flash_ms` for the LFM2 cell: the flash kernels under `hvd_attn_full`,
the causal call of 2 x 32 query heads on 8 kv heads (group 4) at 8192
positions and head width 64, by the kernels' own names (the grouped matmuls
of its routed layers are `tpu_custom_call`s too). A part of
`attn_full_ms.lfm2`. Source: device trace (`lfm2_reduce.py`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.flash_ms(trace, context, "full")
