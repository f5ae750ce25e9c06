"""`moe_gmm_ms` for the LFM2 cell: the grouped-matmul kernels over the rows
the router's top-4 of 32 put on the 8 held experts, about 2048 an expert (see
`moe_gmm_ms.py`; `hvd_moe_rows`, `hvd_moe_sum` and `hvd_moe_act` are not
among them)."""

from benchmark.layer_metrics.moe_gmm_ms import read  # noqa: F401
