"""Analytic operation and byte counts for SDAR's block-diffusion train step
(`benchmark/builders/sdar.py`; beside `flops.py` and `flops_moe.py`, whose
conventions they keep): an item is a DATA token, which passes every layer
twice (its noisy and its clean copy) and the head once.

`model_flops_per_token` is what `mfu` reads: what the mathematics needs,
attention by the pairs the mask leaves VISIBLE. `flash_executed_flops` is
what `flash_roofline.sdar` reads: what the kernels run, by the tiles
`hvd.profile.flash_plan(..., mask=)` says each kernel visits (a cut tile is
computed whole).
"""

from benchmark import flops

# Matrix products a flash kernel forms on a tile it visits, and the least
# bytes a call of it moves (positions = all 2 x length rows): `flops.py`'s
# tables by kernel name, one for plain and ruled calls alike.
FLASH_MATMULS = flops.FLASH_EXECUTED_MATMULS
flash_min_bytes = flops.flash_min_bytes


def visible_pairs(length, block):
    """(query, key) pairs the block-diffusion mask leaves visible among the
    2 x `length` rows of one sequence: a noisy row sees its own block
    (`block` keys) and the clean blocks before it, a clean row the clean
    blocks up to its own: length * block + length ** 2."""
    return length * block + length * length


def attention_params(hidden, heads, kv_heads, head_dim):
    """Matmul parameters of one attention: q, k, v and the output."""
    return hidden * head_dim * (2 * heads + 2 * kv_heads)


def layer_matmul_params(hidden, heads, kv_heads, head_dim, expert_width,
                        experts, held):
    """Matmul parameters of one layer ON THIS RANK: attention, the router
    over all `experts`, `held` gated experts."""
    return (attention_params(hidden, heads, kv_heads, head_dim)
            + hidden * experts + held * 3 * hidden * expert_width)


def params(hidden, heads, kv_heads, head_dim, expert_width, experts, held,
           vocab, layers):
    """Parameters resident on this rank: the layers (with two norms and the
    two per-head scales each), embedding, head, final norm."""
    return (layers * (layer_matmul_params(hidden, heads, kv_heads, head_dim,
                                          expert_width, experts, held)
                      + 2 * hidden + 2 * head_dim)
            + 2 * vocab * hidden + hidden)


def model_flops_per_token(hidden, heads, kv_heads, head_dim, expert_width,
                          experts, held, top_k, vocab, layers, length,
                          block):
    """Forward + backward operations one DATA token requires on this rank:
    6 per matmul parameter a position meets (attention's projections, the
    router, the `top_k * held / experts` held experts it is EXPECTED to be
    sent to) for its TWO positions in every layer, 6 per parameter of the
    head for ONE, and attention's two products forward and four backward
    over the visible pairs. Recomputation, sort, the rows' kernels and the
    noise are not counted."""
    position = (attention_params(hidden, heads, kv_heads, head_dim)
                + hidden * experts
                + top_k * held / experts * 3 * hidden * expert_width)
    attention = 6.0 * 2.0 * heads * head_dim * visible_pairs(length, block)
    return (6.0 * (2.0 * layers * position + hidden * vocab)
            + layers * attention / length)


def flash_executed_flops(plans, head_dim):
    """Operations the flash kernels of `plans` ({kernel: FlashKernelPlan}
    with `tiles_visited`) execute in one call each: a visited tile is
    block_q rows x block_k keys, whole."""
    return sum(FLASH_MATMULS[name] * 2.0 * p.tiles_visited * p.block_q
               * p.block_k * head_dim for name, p in plans.items())
