"""Device milliseconds per step that carry none of the program's phase
scopes, mean over devices: above 2% of the step a scope is missing. With
`fwd_bwd_ms`, `grad_sync_ms` and `optimizer_ms` (and zero1's
`hvd_param_gather`) it adds up to the device's busy time. Source: device
trace (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.phase_ms(trace, context, sr.UNSCOPED)
