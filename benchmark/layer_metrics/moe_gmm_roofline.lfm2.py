"""`moe_gmm_roofline` for the LFM2 cell: the bounds over the grouped matmuls
on the rows the run COUNTED on the held experts after its window (the
builder's `verify` puts them into `counts`; before it, the rows expected:
top_k x tokens x held / experts = 16384 a layer, 2048 an expert) and the 8
held experts' f32 matrices at 1792 wide (see `moe_gmm_roofline.py`)."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
