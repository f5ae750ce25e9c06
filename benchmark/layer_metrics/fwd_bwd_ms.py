"""Device milliseconds per step under the scope `hvd_fwd_bwd` (the loss
function's forward and backward pass, the Pallas kernels and the loss
included), mean over devices. Source: device trace, self time by the
program's own scope (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.phase_ms(trace, context, sr.names.FWD_BWD)
