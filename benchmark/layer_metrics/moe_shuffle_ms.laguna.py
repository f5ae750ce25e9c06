"""`moe_shuffle_ms` for the Laguna cell: `moe_ms.laguna` -
`moe_gmm_ms.laguna` (router, sigmoid over 256, top-8, the counted order of
the held rows, the rows' kernels, the gate, the weighted sum, the shared
expert, both directions; see `moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
