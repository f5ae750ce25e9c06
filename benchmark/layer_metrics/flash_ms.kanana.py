"""`flash_ms` for the Kanana cell: device milliseconds per step in the flash
kernels BY THE NAMES the program gave them (`hvd_flash_fwd`, and
`hvd_flash_bwd` or `hvd_flash_dq` + `hvd_flash_dkv`: scores of two products
at 8192 positions, every layer, the recomputed forwards too), since the
grouped matmuls of its routed layers are `tpu_custom_call`s too.
`flash_fwd_ms.kanana` + `flash_bwd_ms.kanana`. Source: device trace
(`kanana_reduce.py`)."""

from benchmark import kanana_reduce


def read(trace, context):
    return kanana_reduce.ms(trace, context, "flash")
