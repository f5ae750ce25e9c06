"""Analytic operation and byte counts for LFM2-8B-A1B's train step
(`benchmark/builders/lfm2.py`; beside `flops.py`, `flops_moe.py`,
`flops_sdar.py` and `flops_mellum.py`, whose conventions they keep):
double-gated short convolutions as mixers beside grouped-query attention at
head width 64, leading dense layers, then a router over all published
experts and the `held` of them this rank holds (no shared one), the head
tied to the embedding.

`model_flops_per_token` is what `mfu` reads: what the mathematics needs. The
gated pass between a conv mixer's projections is counted in BYTES
(`gate_min_bytes`: what a one-pass form moves, whatever implements it; its
few multiplies a channel are no matmul and in no count). The flash kernels'
executed operations are `flops_sdar.flash_executed_flops` of the tiles
`hvd.profile.flash_plan` says each kernel visits (a cut tile computed
whole), their least bytes `flops.flash_min_bytes`: every tensor once. The
grouped matmuls are `flops_moe`'s on the rows the run counted.
"""

from benchmark import flops, flops_mellum, flops_moe, flops_sdar

flash_executed_flops = flops_sdar.flash_executed_flops
flash_min_bytes = flops.flash_min_bytes
attention_params = flops_sdar.attention_params  # q, k, v and the output
visible_pairs = flops_mellum.visible_pairs
gated_experts_flops = flops_moe.gated_experts_flops
gated_experts_min_bytes = flops_moe.gated_experts_min_bytes


def conv_params(hidden):
    """Matmul parameters of one conv mixer: the in-projection to three
    column blocks and the out-projection."""
    return 4 * hidden * hidden


def mixer_params(kind, hidden, heads, kv_heads, head_dim):
    """Matmul parameters of a layer's first branch (`kind`: "conv" |
    "full")."""
    return conv_params(hidden) if kind == "conv" \
        else attention_params(hidden, heads, kv_heads, head_dim)


def feed_forward_params(hidden, dense_width, expert_width, experts,
                        routed_experts):
    """Matmul parameters of a layer's feed-forward with `routed_experts`
    gated experts computed (None: the dense one of `dense_width`; else the
    router over all `experts` and that many routed ones, a fraction where
    they are a token's EXPECTED share)."""
    if routed_experts is None:
        return 3 * hidden * dense_width
    return hidden * experts + routed_experts * 3 * hidden * expert_width


def params(hidden, heads, kv_heads, head_dim, dense_width, expert_width,
           experts, held, taps, vocab, kinds, dense_layers):
    """Parameters resident on this rank: every layer's mixer (a conv
    mixer's taps, an attention's two per-head scales), two norms, the dense
    feed-forward in the first `dense_layers` layers and router + selection
    bias + `held` experts in the others; the ONE table of embedding and
    head, the final norm."""
    total = vocab * hidden + hidden
    for i, kind in enumerate(kinds):
        routed = i >= dense_layers
        total += (mixer_params(kind, hidden, heads, kv_heads, head_dim)
                  + (taps * hidden if kind == "conv" else 2 * head_dim)
                  + 2 * hidden
                  + feed_forward_params(hidden, dense_width, expert_width,
                                        experts, held if routed else None)
                  + (experts if routed else 0))
    return total


def model_flops_per_token(hidden, heads, kv_heads, head_dim, dense_width,
                          expert_width, experts, held, top_k, vocab, kinds,
                          dense_layers, length):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (a conv mixer's two projections or an
    attention's four; the dense feed-forward, or the router over all
    `experts` and the `top_k * held / experts` held experts it is EXPECTED
    to be sent to; the head, which is the table a second time), and
    attention's two products forward and four backward over the causal
    pairs of its layers. Recomputation, the gated pass, the top-k, the
    rows' kernels, the norms and the rotations are not counted."""
    matmul, attention = hidden * vocab, 0.0
    for i, kind in enumerate(kinds):
        matmul += mixer_params(kind, hidden, heads, kv_heads, head_dim) \
            + feed_forward_params(
                hidden, dense_width, expert_width, experts,
                top_k * held / experts if i >= dense_layers else None)
        if kind != "conv":
            attention += heads * head_dim * visible_pairs(length)
    return 6.0 * matmul + 6.0 * 2.0 * attention / length


def gate_min_bytes(tokens, hidden, taps, itemsize=2):
    """{"forward", "backward"}: the least bytes the pass between a conv
    mixer's projections moves over `tokens` positions (u = B * z, the taps,
    G * c): forward the three column blocks read and y written, 4 blocks of
    tokens x hidden; backward the three blocks and y's cotangent read and
    the blocks' cotangent written, 7; the taps (f32) read, and written once
    more as their gradient. 8 and 14 bytes a token and channel in bf16."""
    block = tokens * hidden * itemsize
    weights = taps * hidden * 4
    return {"forward": 4 * block + weights,
            "backward": 7 * block + 2 * weights}


def gate_step_min_bytes(tokens, hidden, taps, layers, forwards_again,
                        itemsize=2):
    """Least bytes of the gated pass in a train step: `layers` conv mixers
    forward and backward, `forwards_again` of them forward once more (a
    recomputed block)."""
    one = gate_min_bytes(tokens, hidden, taps, itemsize)
    return (layers + forwards_again) * one["forward"] \
        + layers * one["backward"]
