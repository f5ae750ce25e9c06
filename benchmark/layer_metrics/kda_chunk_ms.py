"""The part of `kda_ms` under `hvd_kda_chunk`: what the chunked recurrence
makes for all chunks at once (`ops/kda.py`: the cumulative decays, the
decayed scores by sub-blocks, the unit-lower-triangular solve, W and U, the
decayed operands of the scan), both directions. Source: device trace
(`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda", "KDA_CHUNK")
