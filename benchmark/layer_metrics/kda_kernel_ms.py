"""Device milliseconds per step in the two Pallas kernels of the chunked
KDA recurrence BY THE NAMES the program gave them (`hvd_kda_scores`,
`hvd_kda_scores_bwd`: a sub-block's own decayed scores term by term, and
their gradients), every KDA layer, a recomputed forward too. A part of
`kda_chunk_ms`. Source: device trace (`kimi_reduce.py`); None for a program
that names no such kernel."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda_kernel")
