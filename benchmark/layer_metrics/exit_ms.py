"""Device milliseconds per step under the scope `hvd_exit`, forward and
backward, mean over devices: the exit gate on every pass's hidden state, the
exit distribution, its entropy and the weights it gives the loss's rows. A
part of `fwd_bwd_ms`. Source: device trace, self time by the program's own
scope (`loop_reduce.py`)."""

from benchmark import loop_reduce


def read(trace, context):
    return loop_reduce.ms(trace, context, "exit")
