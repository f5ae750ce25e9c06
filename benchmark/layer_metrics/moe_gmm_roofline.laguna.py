"""`moe_gmm_roofline` for the Laguna cell: the bounds over the grouped
matmuls on the rows EXPECTED on the held experts (top_k x tokens x held /
experts = 8192 a layer, 256 an expert) and the 32 held experts' f32 matrices
(the builder's `counts`; see `moe_gmm_roofline.py`)."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
