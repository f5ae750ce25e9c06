"""`flash_dq_ms` for the OLMoE cell (by the kernel's own name; see
`flash_dq_ms.py`)."""

from benchmark.layer_metrics.flash_dq_ms import read  # noqa: F401
