"""`flash_ms` for the Nemotron cell: device milliseconds per step in the
flash kernels BY THE NAMES the program gave them (`hvd_flash_fwd`, and
`hvd_flash_bwd` or `hvd_flash_dq` + `hvd_flash_dkv`), since the grouped
matmuls and the rows' kernels of its routed layers are `tpu_custom_call`s
too. One attention layer in eleven, 32 query heads on 2 kv heads. Source:
device trace (`nemo3_reduce.py`)."""

from benchmark import nemo3_reduce


def read(trace, context):
    return nemo3_reduce.ms(trace, context, "flash") or None
