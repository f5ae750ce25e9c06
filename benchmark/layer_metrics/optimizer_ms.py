"""Device milliseconds per step under the scope `hvd_optimizer` (optimizer
maths and the parameter write), mean over devices. Source: device trace,
self time by the program's own scope (`scope_reduce.py`)."""

from benchmark import scope_reduce as sr


def read(trace, context):
    return sr.phase_ms(trace, context, sr.names.OPTIMIZER)
