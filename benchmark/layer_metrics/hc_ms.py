"""Device milliseconds per step in the hyper-connections, forward and
backward, both branches of every block and the prediction module's, mean
over devices: everything under the scope `hvd_hc` (the norm over the
streams, the projection onto the maps, the Sinkhorn iterations, reading a
branch's input from the streams and writing the mixed streams back; under
`hc_remat` their recomputation too). A part of `fwd_bwd_ms`. Source: device
trace, self time by the program's own scope (`xing_reduce.py`)."""

from benchmark import xing_reduce


def read(trace, context):
    return xing_reduce.ms(trace, context, "hc")
