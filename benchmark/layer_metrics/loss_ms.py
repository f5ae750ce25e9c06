"""Device milliseconds per step under the scope `hvd_loss`, forward and
backward (for the chunked vocabulary loss its recomputation too), mean over
devices; a part of `fwd_bwd_ms`. Source: device trace, self time by the
program's own scope (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.loss_ms(trace, context)
