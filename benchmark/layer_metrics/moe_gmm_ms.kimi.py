"""`moe_gmm_ms` for the Kimi-Linear cell: the grouped-matmul kernels of its
routed layers by name (see `moe_gmm_ms.py`)."""

from benchmark.layer_metrics.moe_gmm_ms import read  # noqa: F401
