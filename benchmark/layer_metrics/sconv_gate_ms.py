"""The part of `sconv_ms` under `hvd_sconv_gate`: the pass between a conv
mixer's projections (`ops/sconv.py::gated_conv`: u = B * z, three causal
taps a channel, G * c, f32 to one rounding), both directions and a recomputed
forward. By fusion. Source: device trace (`lfm2_reduce.py`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.ms(trace, context, "SCONV_GATE")
