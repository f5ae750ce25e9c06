"""`moe_shuffle_ms` for the LFM2 cell: `moe_ms.lfm2` - `moe_gmm_ms.lfm2`
(router, sigmoid over 32, top-4, the counted order of the held rows, the
rows' kernels, the gate, the weighted sum, both directions; see
`moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
