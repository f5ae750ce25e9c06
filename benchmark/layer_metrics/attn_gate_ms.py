"""Device milliseconds per step under the attention gate's scope
`hvd_attn_gate` (`models/transformer.py::Attention`, `attention_gate`): the
gate's projection of the branch's normed input [L, C] x [C, H], its sigmoid
in f32 and the product with the heads' outputs before `out`, forward and
backward (and the forward again where a block is recomputed), every block,
mean over devices. A part of `attn_full_ms.laguna` + `attn_window_ms.laguna`;
by fusion, as every scope's reading (`hvd.profile.fused_scopes`). Source:
device trace, self time by the program's own scope (`laguna_reduce.py`); None
for a program that names no such scope."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.gate_ms(trace, context)
