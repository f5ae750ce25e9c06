"""Device milliseconds per step in the rotations of the attention modules,
forward and backward (and the forward again where a block is recomputed),
every block, mean over devices: what lies under the scope `hvd_attn_rope`
inside a block's `attn` half (cos and sin of the positions and the rotation
of q and k, plain or on YaRN's frequencies; latent attention: the slices of
q and kv into their no-position and rotary parts and the rotation of the two
rotary parts). A part of what `mla_ms`, `attn_ms.sdar`, `attn_window_ms` +
`attn_full_ms` time from outside; by fusion (`hvd.profile.fused_scopes`).
Source: device trace, self time by the program's own scope
(`inner_reduce.py`); None for a program that names no such scope or rotates
nothing."""

from benchmark import inner_reduce


def read(trace, context):
    return inner_reduce.ms(trace, context, "attn", "ATTN_ROPE")
