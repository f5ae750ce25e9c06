"""`moe_ms` for the Xing cell: everything under `hvd_moe` in its four routed
layers and the prediction module's (router by sigmoid, top-k, sort, the
gathers over all k x T rows, the grouped matmuls over the held experts'
rows, the weighted sum, and the shared expert under `hvd_moe_shared`)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
