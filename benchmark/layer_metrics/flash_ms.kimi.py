"""`flash_ms` for the Kimi-Linear cell: device milliseconds per step in the
flash kernels BY THE NAMES the program gave them (`hvd_flash_fwd`, and
`hvd_flash_bwd` or `hvd_flash_dq` + `hvd_flash_dkv`: scores of two unrotated
products at 8192 positions, the two latent layers, a recomputed forward
too), since the grouped matmuls of its routed layers are `tpu_custom_call`s
too. Source: device trace (`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "flash")
