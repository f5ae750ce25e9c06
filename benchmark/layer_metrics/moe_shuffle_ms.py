"""Device milliseconds per step of the routed feed-forward outside its
grouped-matmul kernels: router, softmax, top-k, the auxiliary losses, the
sort, the gathers into and out of the sorted order, the gate, the weighted
sum, both directions (`moe_ms` - `moe_gmm_ms`). Source: device trace
(`moe_reduce.py`)."""

from benchmark import moe_reduce


def read(trace, context):
    return moe_reduce.ms(trace, context, "shuffle")
