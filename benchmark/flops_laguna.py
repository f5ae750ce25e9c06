"""Analytic operation and byte counts for Laguna-XS.2's train step
(`benchmark/builders/laguna.py`; beside `flops.py`, `flops_moe.py`,
`flops_sdar.py` and `flops_mellum.py`, whose conventions they keep): full
and window attention layers of DIFFERENT head counts on the same kv heads, a
gate a head, a leading dense layer, then a router over all published
experts, the `held` of them this rank holds and a shared one.

`model_flops_per_token` is what `mfu` reads: what the mathematics needs, each
layer with its OWN heads and the pairs its own mask leaves visible. The flash
kernels' executed operations, BY KIND of layer, are
`flops_sdar.flash_executed_flops` of the tiles
`hvd.profile.flash_plan(..., mask=)` says each kernel visits at the kind's
own head count (a cut tile computed whole: never L^2 / 2); their least bytes
are `flops.flash_min_bytes` at the kind's heads: every tensor once.
"""

from benchmark import flops, flops_mellum, flops_sdar

flash_executed_flops = flops_sdar.flash_executed_flops
flash_min_bytes = flops.flash_min_bytes
visible_pairs = flops_mellum.visible_pairs


def attention_params(hidden, heads, kv_heads, head_dim):
    """Matmul parameters of one attention of `heads` query heads: q, k, v,
    the output and the gate's [hidden, heads]."""
    return (flops_sdar.attention_params(hidden, heads, kv_heads, head_dim)
            + hidden * heads)


def feed_forward_params(hidden, dense_width, expert_width, shared_width,
                        experts, routed_experts):
    """Matmul parameters of a layer's feed-forward with `routed_experts`
    gated experts computed (None: the dense one of `dense_width`; else the
    router over all `experts`, the shared expert and that many routed
    ones, a fraction where they are a token's EXPECTED share)."""
    if routed_experts is None:
        return 3 * hidden * dense_width
    return (hidden * experts + 3 * hidden * shared_width
            + routed_experts * 3 * hidden * expert_width)


def params(hidden, heads_by_kind, kv_heads, head_dim, dense_width,
           expert_width, shared_width, experts, held, vocab, kinds,
           dense_layers):
    """Parameters resident on this rank: every layer's attention at its
    kind's heads, two norms, the dense feed-forward in the first
    `dense_layers` layers and router + selection bias + shared + `held`
    experts in the others; embedding, head, final norm."""
    total = 2 * vocab * hidden + hidden
    for i, kind in enumerate(kinds):
        routed = i >= dense_layers
        total += (attention_params(hidden, heads_by_kind[kind], kv_heads,
                                   head_dim) + 2 * hidden
                  + feed_forward_params(hidden, dense_width, expert_width,
                                        shared_width, experts,
                                        held if routed else None)
                  + (experts if routed else 0))
    return total


def model_flops_per_token(hidden, heads_by_kind, kv_heads, head_dim,
                          dense_width, expert_width, shared_width, experts,
                          held, top_k, vocab, kinds, dense_layers, length,
                          window):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (each layer's projections and gate at its own
    heads; the dense feed-forward once; in a routed layer the router over
    all `experts`, the shared expert and the `top_k * held / experts` held
    experts it is EXPECTED to be sent to; the head), and attention's two
    products forward and four backward at each layer's own heads over the
    pairs its own mask leaves visible (`kinds`: "window" | "full" a layer).
    Recomputation, the top-k, the rows' kernels, the rotations and the
    gate's product are not counted."""
    matmul, attention = hidden * vocab, 0.0
    for i, kind in enumerate(kinds):
        heads = heads_by_kind[kind]
        matmul += attention_params(hidden, heads, kv_heads, head_dim) \
            + feed_forward_params(
                hidden, dense_width, expert_width, shared_width, experts,
                top_k * held / experts if i >= dense_layers else None)
        attention += heads * head_dim * visible_pairs(
            length, window if kind == "window" else None)
    return 6.0 * matmul + 6.0 * 2.0 * attention / length
