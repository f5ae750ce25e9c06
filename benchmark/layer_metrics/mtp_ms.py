"""Device milliseconds per step in the multi-token prediction module,
forward and backward, mean over devices: everything under the scope
`hvd_mtp` (the next token's embedding, the two norms, the projection of
their concatenation, the module's block with its hyper-connections,
attention and routed feed-forward, its final norm). WITHOUT the module's
share of `hvd_loss`: both heads' rows go through one call of the chunked
loss, whose scope does not tell them apart (`loss_ms` holds both). A part
of `fwd_bwd_ms`. Source: device trace, self time by the program's own scope
(`xing_reduce.py`)."""

from benchmark import xing_reduce


def read(trace, context):
    return xing_reduce.ms(trace, context, "mtp")
