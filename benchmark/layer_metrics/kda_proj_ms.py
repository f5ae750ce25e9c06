"""The part of `kda_ms` under `hvd_kda_proj`: the mixers' projections (the
in-projection to q, k, v, the two low ranks and beta; the low ranks' up
sides; the output projection) and their weight gradients, with whatever XLA
fused behind them. By fusion. Source: device trace (`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda", "KDA_PROJ")
