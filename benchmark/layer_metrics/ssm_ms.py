"""Device milliseconds per step in the Mamba-2 mixers, forward and backward
(a recomputed forward too), every such layer, mean over devices: everything
under the scope `hvd_ssm` (the in-projection to z, xBC and dt, the causal
depthwise convolution, the chunked scan, the gate, the grouped norm, the
out-projection). A part of `fwd_bwd_ms`. Source: device trace, self time by
the program's own scope (`nemo3_reduce.py`); None for a program that names
no such scope."""

from benchmark import nemo3_reduce


def read(trace, context):
    return nemo3_reduce.ms(trace, context, "ssm")
