"""`mla_ms` for the Kanana cell: device milliseconds per step in latent
attention, forward and backward and recomputed, every block: everything
under a block's `attn` half (the queries' projection straight from the
state, the latent's down- and up-projection, its norm, rotary on the 64-wide
slice, the output projection) and the flash kernels. A part of `fwd_bwd_ms`.
Source: device trace, self time by the program's own scope
(`kanana_reduce.py`)."""

from benchmark import kanana_reduce


def read(trace, context):
    return kanana_reduce.ms(trace, context, "mla")
