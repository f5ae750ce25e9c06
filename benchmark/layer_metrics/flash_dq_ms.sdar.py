"""`flash_dq_ms` for the SDAR cell (by the kernel's own name, under the
block-diffusion mask; see `flash_dq_ms.py`). With the two others it adds
up to `flash_ms.sdar`."""

from benchmark.layer_metrics.flash_dq_ms import read  # noqa: F401
