"""`flash_bwd_ms` for the OLMoE cell (by the kernel's own name; see
`flash_bwd_ms.py`)."""

from benchmark.layer_metrics.flash_bwd_ms import read  # noqa: F401
