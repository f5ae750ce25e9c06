"""Device milliseconds per step under the scope `hvd_grad_sync` (every
collective on gradients and what is fused around it), mean over devices;
None where the step has none. Source: device trace, self time by the
program's own scope (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.phase_ms(trace, context, sr.names.GRAD_SYNC, zero_is_none=True)
