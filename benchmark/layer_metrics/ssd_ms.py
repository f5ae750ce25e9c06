"""Device milliseconds per step in the chunked state-space scan, forward
and backward, every Mamba-2 layer, mean over devices: the part of `ssm_ms`
under the scope `hvd_ssd` (decays and cumulative sums, the products inside
a chunk, the chunk states, the carry over the chunks, `ops/ssd.py`).
Source: device trace, self time by the program's own scope
(`nemo3_reduce.py`); None for a program that names no such scope."""

from benchmark import nemo3_reduce


def read(trace, context):
    return nemo3_reduce.ms(trace, context, "ssd")
