"""`flash_dkv_ms` for the OLMoE cell (by the kernel's own name; see
`flash_dkv_ms.py`)."""

from benchmark.layer_metrics.flash_dkv_ms import read  # noqa: F401
