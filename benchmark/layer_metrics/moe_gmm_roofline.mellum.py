"""`moe_gmm_roofline` for the Mellum2 cell: the bounds over the grouped
matmuls on the rows EXPECTED on the held experts (top_k x tokens x held /
experts = 16384 a layer, 1024 an expert) and the held experts' f32 matrices
(the builder's `counts`)."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
