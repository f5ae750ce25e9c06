"""`attn_rope_ms` for the Laguna cell: what lies under `hvd_attn_rope` in its
eight attention modules (the full layers: the slice of the first 64 channels
of each head, YaRN's rotation of it, the concatenation with the other 64; the
window layers: the plain rotation of the whole head), see `attn_rope_ms.py`
(`inner_reduce.py`)."""

from benchmark.layer_metrics.attn_rope_ms import read  # noqa: F401
