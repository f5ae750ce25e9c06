"""`moe_gmm_roofline` for the Xing cell: the bounds over `flops_xing`'s
counts of the grouped matmuls on the rows EXPECTED on the held experts
(top_k x tokens x held / experts a layer) and the held experts' matrices
(the builder's `counts`); at 256 rows an expert over f32 matrices the bytes
bind."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
