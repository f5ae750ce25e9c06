"""`moe_gmm_roofline` for the Nemotron cell: the bounds over the grouped
matmuls on the rows EXPECTED on the held experts (top_k x tokens x held /
experts = 1408 a layer) and the held experts' f32 matrices (the builder's
`counts`, `flops_nemo3.ungated_experts_*`); at 176 rows an expert the bytes
of the matrices bind."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
