"""`moe_ms` for the LFM2 cell: everything under `hvd_moe` in its eight
routed layers (router by sigmoid over 32, top-4, the counted order of the 8
held experts' rows, the rows' kernels, the grouped matmuls at 1792 wide, the
gate, the weighted sum; no shared expert), both directions and the
recomputed forwards (see `moe_ms.py`)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
