"""The part of `sconv_ms` under `hvd_sconv_proj`: the conv mixers' two
projections ([2048, 6144] in, [2048, 2048] out), both directions and a
recomputed forward. By fusion. Source: device trace (`lfm2_reduce.py`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.ms(trace, context, "SCONV_PROJ")
