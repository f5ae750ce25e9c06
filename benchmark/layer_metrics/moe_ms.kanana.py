"""`moe_ms` for the Kanana cell: everything under `hvd_moe` in its routed
layers (router by sigmoid over 128, top-6 on score + bias, the counted order
of the 16 held experts' rows, the rows' kernels, the grouped matmuls, the
gate, the weighted sum, and the shared pair under `hvd_moe_shared`), both
directions and the recomputed forwards (see `moe_ms.py`)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
