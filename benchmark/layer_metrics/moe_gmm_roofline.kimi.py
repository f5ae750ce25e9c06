"""`moe_gmm_roofline` for the Kimi-Linear cell: the bounds over `flops_moe`'s
counts of the grouped matmuls on the rows EXPECTED on the held experts
(top_k x tokens x held / experts = 2048 a layer, 256 an expert) and the held
experts' matrices (the builder's `counts`); at 256 rows an expert over f32
matrices the bytes bind (see `moe_gmm_roofline.py`)."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
