"""`moe_gmm_ms` for the Kanana cell: the grouped-matmul kernels over the
rows the router's top-6 of 128 put on the 16 held experts (see
`moe_gmm_ms.py`; `hvd_moe_rows`, `hvd_moe_sum` and the activation's kernels
are not among them)."""

from benchmark.layer_metrics.moe_gmm_ms import read  # noqa: F401
