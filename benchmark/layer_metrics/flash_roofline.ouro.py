"""`flash_roofline` for the Ouro cell: the bounds over `flops_ouro`'s
executed counts of T x N layer passes (the builder's `counts`)."""

from benchmark.layer_metrics.flash_roofline import read  # noqa: F401
