"""`moe_shuffle_ms` for the Kimi-Linear cell: what its routed layers spend
ordering and moving rows (see `moe_shuffle_ms.py`)."""

from benchmark.layer_metrics.moe_shuffle_ms import read  # noqa: F401
