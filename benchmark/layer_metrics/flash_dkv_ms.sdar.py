"""Device milliseconds per step in the Pallas kernel `hvd_flash_dkv` (flash
attention backward, the dK/dV kernel of every layer, under the
block-diffusion mask), mean over devices. With `flash_fwd_ms.sdar` and
`flash_dq_ms.sdar` it adds up to `flash_ms.sdar`. Nothing to read where the
backward is one kernel. Source: device trace, by the kernel's own name
(`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.kernel_ms(trace, context, sr.names.FLASH_DKV)
