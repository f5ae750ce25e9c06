"""`moe_gmm_ms` for the Laguna cell: the grouped-matmul kernels over the
rows the router's top-8 of 256 put on the 32 held experts (see
`moe_gmm_ms.py`; `hvd_moe_rows`, `hvd_moe_sum` and `hvd_moe_act` are not
among them)."""

from benchmark.layer_metrics.moe_gmm_ms import read  # noqa: F401
