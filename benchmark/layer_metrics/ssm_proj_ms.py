"""Device milliseconds per step in the two projections of the Mamba-2
mixers, forward and backward (a recomputed forward too), every such layer,
mean over devices: the part of `ssm_ms` under the scope `hvd_ssm_proj`
(`in_proj` to z, xBC and dt, `out_proj`; their weight gradients, with
whatever XLA fused behind them). By fusion (`hvd.profile.fused_scopes`).
Source: device trace, self time by the program's own scope
(`inner_reduce.py`); None for a program that names no such scope."""

from benchmark import inner_reduce


def read(trace, context):
    return inner_reduce.ms(trace, context, "ssm", "SSM_PROJ")
