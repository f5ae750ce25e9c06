"""Device milliseconds per step in the norms inside the attention modules,
forward and backward (and the forward again where a block is recomputed),
every block, mean over devices: what lies under the scope `hvd_attn_norm`
inside a block's `attn` half (`q_norm` and `k_norm`, over each head or over
all of them; latent attention's `q_norm` and `kv_norm` on the two ranks). A
part of what `mla_ms`, `attn_ms.sdar`, `attn_window_ms` + `attn_full_ms`
time from outside; by fusion (`hvd.profile.fused_scopes`). Source: device
trace, self time by the program's own scope (`inner_reduce.py`); None for a
program that names no such scope or has no such norm."""

from benchmark import inner_reduce


def read(trace, context):
    return inner_reduce.ms(trace, context, "attn", "ATTN_NORM")
