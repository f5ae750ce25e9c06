"""Device milliseconds per step in the Pallas kernel `hvd_flash_dq` (flash
attention backward, the dQ kernel of every layer), mean over devices.
Source: device trace, by the kernel's own name (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.kernel_ms(trace, context, sr.names.FLASH_DQ)
