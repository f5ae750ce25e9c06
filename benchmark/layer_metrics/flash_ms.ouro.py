"""`flash_ms` for the Ouro cell, whose only `tpu_custom_call`s are the flash
kernels (the forward and the one-kernel backward a layer pass, T x N layer
passes a step)."""

from benchmark.layer_metrics.flash_ms import read  # noqa: F401
