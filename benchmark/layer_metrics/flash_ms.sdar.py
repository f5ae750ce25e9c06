"""`flash_ms` for the SDAR cell: device milliseconds per step in the flash
kernels BY THE NAMES the program gave them (`hvd_flash_fwd`, and
`hvd_flash_dq` + `hvd_flash_dkv` or `hvd_flash_bwd`), since the grouped
matmuls and the rows' kernels of its routed layers are `tpu_custom_call`s
too. Source: device trace (`sdar_reduce.py`)."""

from benchmark import sdar_reduce


def read(trace, context):
    return sdar_reduce.ms(trace, context, "flash") or None
