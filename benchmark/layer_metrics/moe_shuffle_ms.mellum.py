"""`moe_shuffle_ms` for the Mellum2 cell: `moe_ms.mellum` -
`moe_gmm_ms.mellum` (router, softmax over 64, top-8, the sort of 65536
assignments, the rows' kernels, the gate, the weighted sum, both
directions; see `moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
