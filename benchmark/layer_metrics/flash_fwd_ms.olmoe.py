"""`flash_fwd_ms` for the OLMoE cell (by the kernel's own name; see
`flash_fwd_ms.py`)."""

from benchmark.layer_metrics.flash_fwd_ms import read  # noqa: F401
