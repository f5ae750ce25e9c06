"""Device milliseconds per step in the experts' grouped-matmul kernels
(`hvd_moe_gmm`, `hvd_moe_gmm_dlhs`, `hvd_moe_gmm_drhs`: nine calls a layer
in a train step), mean over devices. Source: device trace, by the kernels'
own names (`moe_reduce.py`), never by `tpu_custom_call` alone."""

from benchmark import moe_reduce


def read(trace, context):
    return moe_reduce.ms(trace, context, "gmm")
