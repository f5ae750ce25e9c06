"""`moe_shuffle_ms` for the Kanana cell: `moe_ms.kanana` -
`moe_gmm_ms.kanana` (router, sigmoid over 128, top-6, the counted order, the
rows' kernels, the gate, the weighted sum, the shared pair, both directions;
see `moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
