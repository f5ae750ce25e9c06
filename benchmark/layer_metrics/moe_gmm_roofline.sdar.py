"""`moe_gmm_roofline` for the SDAR cell: the bounds over the grouped matmuls
on the rows EXPECTED on the held experts (top_k x positions x held / experts
= 8192 a layer) and the held experts' f32 matrices (the builder's `counts`);
at 512 rows an expert the bytes and the operations lie close."""

from benchmark.layer_metrics.moe_gmm_roofline import read  # noqa: F401
