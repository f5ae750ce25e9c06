"""Device milliseconds per step in the elementwise part of the Mamba-2
mixers, forward and backward (a recomputed forward too), every such layer,
mean over devices: the part of `ssm_ms` under the scope `hvd_ssm_gate`
(dt's bias and softplus, `a`, the skip, `y * silu(z)`, the grouped mean
square and `rsqrt`, the `norm` scale, the cast before `out_proj`; all f32).
Neither the convolution nor the scan. By fusion
(`hvd.profile.fused_scopes`). Source: device trace, self time by the
program's own scope (`inner_reduce.py`); None for a program that names no
such scope."""

from benchmark import inner_reduce


def read(trace, context):
    return inner_reduce.ms(trace, context, "ssm", "SSM_GATE")
