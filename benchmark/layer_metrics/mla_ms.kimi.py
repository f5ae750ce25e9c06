"""`mla_ms` for the Kimi-Linear cell: device milliseconds per step in the
latent attention layers (one in four), forward and backward and recomputed:
everything under their scope `hvd_attn_full` (the norm before, the queries'
projection straight from the state, the latent's down- and up-projection,
its norm, NO rotation, the output projection, the residual add) and the
flash kernels. A part of `fwd_bwd_ms`. Source: device trace, self time by
the program's own scope (`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "mla")
