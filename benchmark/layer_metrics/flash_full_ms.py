"""Device milliseconds per step in the flash kernels of the FULL layers, by
the kernels' own names (`hvd_flash_fwd`, `hvd_flash_bwd`) under the scope
`hvd_attn_full`: the two kinds' kernels share a name and a shape, and the
scope tells them apart. A part of `attn_full_ms`. Source: device trace
(`mellum_reduce.py`)."""

from benchmark import mellum_reduce


def read(trace, context):
    return mellum_reduce.flash_ms(trace, context, "full")
