"""Device milliseconds per step in the Pallas kernel `hvd_flash_bwd` (flash
attention backward as ONE kernel: dQ, dK and dV of every layer), mean over
devices. With `flash_fwd_ms` it adds up to `flash_ms`. Nothing to read
where the backward runs as `hvd_flash_dq` + `hvd_flash_dkv`. Source: device
trace, by the kernel's own name (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.kernel_ms(trace, context, sr.names.FLASH_BWD)
