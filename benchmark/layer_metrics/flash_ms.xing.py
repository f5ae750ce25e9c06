"""`flash_ms` for the Xing cell: device milliseconds per step in the flash
kernels BY THE NAMES the program gave them (`hvd_flash_fwd`, and
`hvd_flash_bwd` or `hvd_flash_dq` + `hvd_flash_dkv`), since the grouped
matmuls of its routed layers are `tpu_custom_call`s too. Source: device
trace (`xing_reduce.py`)."""

from benchmark import xing_reduce


def read(trace, context):
    return xing_reduce.ms(trace, context, "flash") or None
