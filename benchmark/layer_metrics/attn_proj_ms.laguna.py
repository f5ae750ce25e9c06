"""`attn_proj_ms` for the Laguna cell: what lies under `hvd_attn_proj` in its
eight attention modules (`query` 48 or 64 heads wide, `key`, `value`, `out`;
their weight gradients; the gate's projection is `attn_gate_ms`'s), see
`attn_proj_ms.py` (`inner_reduce.py`)."""

from benchmark.layer_metrics.attn_proj_ms import read  # noqa: F401
