"""`moe_gmm_ms` for the Nemotron cell: the grouped-matmul kernels over the
rows the router's top-22 of 512 put on the 8 held experts, in the 1024-wide
latent (see `moe_gmm_ms.py`; six calls a layer: the experts have no gate)."""

from benchmark.layer_metrics.moe_gmm_ms import read  # noqa: F401
