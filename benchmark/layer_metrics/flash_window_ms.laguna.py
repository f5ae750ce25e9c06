"""`flash_window_ms` for the Laguna cell: the flash kernels under
`hvd_attn_window`, the call of 64 query heads on 8 kv heads (group 8) at
8192 positions under `ops.BandMask(512)`, by the kernels' own names (see
`flash_window_ms.py`; `laguna_reduce.py`). A part of
`attn_window_ms.laguna`."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.flash_ms(trace, context, "window")
