"""`moe_shuffle_ms` for the Nemotron cell: `moe_ms.nemo3` -
`moe_gmm_ms.nemo3` (router, sigmoid over 512, top-22, the sort of 90112
assignments, the latent projections, the rows' kernels, relu2 over the
buffer, the weighted sum, the shared expert, both directions; see
`moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
