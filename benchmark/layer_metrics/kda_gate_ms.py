"""The part of `kda_ms` under `hvd_kda_gate`: the mixers' elementwise part
in f32 (the l2 norms of q and k, the decay's softplus, beta's sigmoid, the
gated head norm), both directions. By fusion. Source: device trace
(`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda", "KDA_GATE")
