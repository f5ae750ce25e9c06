"""Analytic operation and byte counts for Mellum2's train step
(`benchmark/builders/mellum.py`; beside `flops.py`, `flops_moe.py` and
`flops_sdar.py`, whose conventions they keep): Qwen3-MoE's layer with window
and full attention layers in one stack, as one rank of an expert- and
vocabulary-parallel group holds it (`held` of `experts` routed experts).

`model_flops_per_token` is what `mfu` reads: what the mathematics needs,
attention by the pairs each layer's mask leaves VISIBLE. The flash kernels'
executed operations, BY KIND of layer, are `flops_sdar.flash_executed_flops`
of the tiles `hvd.profile.flash_plan(..., mask=)` says each kernel visits (a
cut tile computed whole: never L^2 / 2): a window layer's under
`ops.BandMask(window)`, a full layer's under a band as long as the sequence,
which is the causal triangle walked with the same blocks. Their least bytes
are `flops.flash_min_bytes`, the same for both kinds: every tensor once.
"""

from benchmark import flops, flops_sdar

attention_params = flops_sdar.attention_params
layer_matmul_params = flops_sdar.layer_matmul_params
params = flops_sdar.params  # the same layer: two norms, two per-head scales
flash_executed_flops = flops_sdar.flash_executed_flops
flash_min_bytes = flops.flash_min_bytes


def visible_pairs(length, window=None):
    """(query, key) pairs a causal layer leaves visible over one sequence,
    by `flops.py`'s convention for the triangle (L^2 / 2); under a window
    the first `window` queries see that triangle and every later one
    `window` keys."""
    if window is None or window >= length:
        return length * length / 2.0
    return window * window / 2.0 + (length - window) * window


def model_flops_per_token(hidden, heads, kv_heads, head_dim, expert_width,
                          experts, held, top_k, vocab, kinds, length,
                          window):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (attention's projections, the router over all
    `experts`, the `top_k * held / experts` held experts it is EXPECTED to
    be sent to, in every layer; the head), and attention's two products
    forward and four backward over the pairs its layer's mask leaves
    visible (`kinds`: "window" | "full" a layer). Recomputation, sort, the
    rows' kernels and the rotations are not counted."""
    position = (attention_params(hidden, heads, kv_heads, head_dim)
                + hidden * experts
                + top_k * held / experts * 3 * hidden * expert_width)
    pairs = sum(visible_pairs(length, window if kind == "window" else None)
                for kind in kinds)
    return (6.0 * (len(kinds) * position + hidden * vocab)
            + 6.0 * 2.0 * heads * head_dim * pairs / length)
