"""Device milliseconds per step in the Pallas kernel `hvd_flash_fwd` (flash
attention forward, every layer), mean over devices. With `flash_bwd_ms` it
adds up to `flash_ms`. Source: device trace, by the kernel's own name
(`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.kernel_ms(trace, context, sr.names.FLASH_FWD)
