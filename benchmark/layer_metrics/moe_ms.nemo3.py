"""`moe_ms` for the Nemotron cell: everything under `hvd_moe` in its five
LatentMoE layers (router by sigmoid over 512, top-22, the sort of 90112
assignments, the two latent projections, the rows' kernels over the live
rows of the k x T-row buffer, the grouped matmuls over the 8 held experts'
rows, relu2, the weighted sum, the shared expert)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
