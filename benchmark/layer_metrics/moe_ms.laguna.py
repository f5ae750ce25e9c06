"""`moe_ms` for the Laguna cell: everything under `hvd_moe` in its seven
routed layers (router by sigmoid over 256, top-8, the counted order of the
32 held experts' rows, the rows' kernels, the grouped matmuls, the gate, the
weighted sum, and the shared expert under `hvd_moe_shared`), both directions
and the recomputed forwards (see `moe_ms.py`)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
