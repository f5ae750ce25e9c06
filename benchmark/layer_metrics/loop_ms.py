"""Device milliseconds per step in the looped stack, forward and backward,
all the passes over the stack, mean over devices: everything under the
scope `hvd_loop` (the blocks of every pass with their flash kernels, and the
final norm that closes a pass). A part of `fwd_bwd_ms`; with `exit_ms`,
`loss_ms` and the embedding it makes it up. Source: device trace, self time
by the program's own scope (`loop_reduce.py`)."""

from benchmark import loop_reduce


def read(trace, context):
    return loop_reduce.ms(trace, context, "loop")
