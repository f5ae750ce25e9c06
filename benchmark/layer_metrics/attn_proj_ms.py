"""Device milliseconds per step in the projections of the attention
modules, forward and backward (and the forward again where a block is
recomputed), every block, mean over devices: what lies under the scope
`hvd_attn_proj` inside a block's `attn` half (`Attention`: `query`, `key`,
`value`, `out`; `LatentAttention`: `q_a`, `q_b`, `kv_a`, `kv_b`, `out`;
their weight gradients, with whatever XLA fused behind them). A part of
what `mla_ms`, `attn_ms.sdar`, `attn_window_ms` + `attn_full_ms` time from
outside; by fusion (`hvd.profile.fused_scopes` says which fusions mix the
parts). Source: device trace, self time by the program's own scope
(`inner_reduce.py`); None for a program that names no such scope."""

from benchmark import inner_reduce


def read(trace, context):
    return inner_reduce.ms(trace, context, "attn", "ATTN_PROJ")
