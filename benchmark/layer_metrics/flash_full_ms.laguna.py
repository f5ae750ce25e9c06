"""`flash_full_ms` for the Laguna cell: the flash kernels under
`hvd_attn_full`, the causal call of 48 query heads on 8 kv heads (group 6)
at 8192 positions, by the kernels' own names (see `flash_full_ms.py`;
`laguna_reduce.py`). A part of `attn_full_ms.laguna`."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.flash_ms(trace, context, "full")
