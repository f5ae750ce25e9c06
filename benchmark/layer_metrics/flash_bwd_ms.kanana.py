"""`flash_bwd_ms` for the Kanana cell: the sum of whatever backward kernels
the plan names (`hvd_flash_bwd`, the whole backward in one kernel held by
the q block with the second product; or `hvd_flash_dq` + `hvd_flash_dkv`),
by their own names. Source: device trace (`kanana_reduce.py`)."""

from benchmark import kanana_reduce


def read(trace, context):
    return kanana_reduce.ms(trace, context, "flash_bwd")
