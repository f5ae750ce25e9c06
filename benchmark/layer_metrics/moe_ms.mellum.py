"""`moe_ms` for the Mellum2 cell: everything under `hvd_moe` in its routed
layers (router by softmax over 64, top-8, sort, the rows' kernels over the
live rows of the k x T = 65536-row buffer, the grouped matmuls over the 16
held experts' rows, the gate over the live tiles, the weighted sum; no
shared expert)."""

from benchmark.layer_metrics.moe_ms import read  # noqa: F401
