"""Analytic operation and byte counts of Kimi-Linear-48B-A3B as one rank of
an expert- and vocabulary-parallel group holds it (Kimi Delta Attention in
`kda_layers` layers, latent attention without position in the others, one
leading dense layer, a shared expert and `held` of `experts` sigmoid-routed
experts a layer): beside `flops.py`, and like it independent of the program
and the compiler. Every count follows from the sizes in a configuration
file. The latent layers' and the flash kernels' counts are `flops_kanana`'s
(the same layer; a rotation was never counted), the grouped matmuls'
`flops_moe`'s. One multiply-accumulate is two operations.

The chunked recurrence is counted FROM THE SHAPES of its chunked form
(`horovod_tpu/ops/kda.py`'s docstring; chunks of C tokens, a head's q and k
D wide, v Dv wide), the same work whatever implements it, so that a later
kernel is judged on this count:

    scores   q k^T and k k^T over a chunk, D wide        2 x 2 C C D
    solve    (I + A)^-1 on [C, D + Dv], a triangle       C C (D + Dv)
    W S, (Q e^G) S, (K e^(G_last - G))^T V'              3 x 2 C D Dv
    lower(q k^T) V'                                      2 C C Dv

a chunk and head, forward; a backward pass is counted as two forwards. What
it leaves out: the exponentials and the elementwise decays (the [C, C, D]
terms of the decayed scores among them: an implementation that forms them
term by term pays for them on the vector unit, and the share says so by
reading low), the cumulative sums, the masks.
"""

from benchmark import flops, flops_kanana


def kda_matmul_params(hidden, heads, head_dim):
    """Matmul parameters of one KDA mixer: the in-projection (q, k, v, the
    two low ranks' down sides `head_dim` wide, beta), the two up sides, the
    output projection."""
    inner = heads * head_dim
    return (hidden * (3 * inner + 2 * head_dim + heads)
            + 2 * head_dim * inner + inner * hidden)


def kda_params(hidden, heads, head_dim, taps):
    """Every parameter of one KDA mixer: the matrices, the three depthwise
    convolutions, `A_log` a head, `dt_bias` a channel, the head norm."""
    inner = heads * head_dim
    return (kda_matmul_params(hidden, heads, head_dim) + 3 * taps * inner
            + heads + inner + head_dim)


def ffn_params(hidden, dense_width=None, expert_width=None, shared_width=0,
               held=0, experts=0):
    """A layer's feed-forward as held, with the layer's two block norms:
    the dense gated one, or router + selection bias + the shared expert +
    `held` routed experts."""
    if dense_width is not None:
        return 2 * hidden + 3 * hidden * dense_width
    return (2 * hidden + hidden * experts + experts
            + 3 * hidden * shared_width + held * 3 * hidden * expert_width)


def params(hidden, heads, head_dim, taps, kv_rank, nope, rope, vd,
           dense_width, expert_width, shared_width, held, experts, vocab,
           kinds, dense_layers):
    """Every parameter held: a mixer a layer by `kinds` ("kda" | "full"),
    the first `dense_layers` feed-forwards dense and the others routed, the
    embedding and the head over `vocab` ids, the final norm."""
    total = 2 * vocab * hidden + hidden
    for i, kind in enumerate(kinds):
        total += kda_params(hidden, heads, head_dim, taps) if kind == "kda" \
            else flops_kanana.latent_attention_params(
                hidden, heads, kv_rank, nope, rope, vd) + kv_rank
        total += ffn_params(hidden, dense_width=dense_width) \
            if i < dense_layers else ffn_params(
                hidden, expert_width=expert_width, shared_width=shared_width,
                held=held, experts=experts)
    return total


def kda_recurrence_flops_per_token(heads, head_dim, v_dim):
    """Forward operations of the recurrence a token as the EQUATIONS have
    it: a head's k^T S, the rank-one update and S^T q, each 2 D Dv."""
    return 3 * 2.0 * heads * head_dim * v_dim


def model_flops_per_token(hidden, heads, head_dim, kv_rank, nope, rope, vd,
                          dense_width, expert_width, shared_width, held,
                          experts, top_k, vocab, kinds, dense_layers, length):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (a mixer's projections, the dense feed-forward
    or router + the shared expert + the `top_k * held / experts` held
    experts it is EXPECTED to be sent to; the head), plus a latent layer's
    two products forward and four backward over a causal context, plus a
    KDA layer's recurrence forward and twice backward. Recomputation, the
    convolutions, the chunked form's solve and scores, sort and the rows'
    kernels are not counted."""
    matmul, other = hidden * vocab, 0.0
    for i, kind in enumerate(kinds):
        if kind == "kda":
            matmul += kda_matmul_params(hidden, heads, head_dim)
            other += 3 * kda_recurrence_flops_per_token(heads, head_dim,
                                                        head_dim)
        else:
            matmul += flops_kanana.latent_attention_params(
                hidden, heads, kv_rank, nope, rope, vd)
            other += 3.0 * (
                flops.attention_matmul_flops(1, heads, length, nope + rope)
                + flops.attention_matmul_flops(1, heads, length, vd)) / length
        matmul += 3 * hidden * dense_width if i < dense_layers else (
            hidden * experts + 3 * hidden * shared_width
            + top_k * held / experts * 3 * hidden * expert_width)
    return 6.0 * matmul + other


def kda_chunk_forward_flops(batch, length, heads, head_dim, v_dim, chunk):
    """Operations ONE forward pass of the chunked recurrence executes for
    one layer, from the shapes of the chunked form (the module docstring's
    table)."""
    C, D, Dv = chunk, head_dim, v_dim
    a_chunk = (2 * 2.0 * C * C * D + 1.0 * C * C * (D + Dv)
               + 3 * 2.0 * C * D * Dv + 2.0 * C * C * Dv)
    return batch * heads * (length // chunk) * a_chunk


def kda_chunk_min_bytes(batch, length, heads, head_dim, v_dim, itemsize=2,
                        backward=False):
    """Least bytes one pass of the chunked recurrence moves for one layer,
    each tensor once: forward reads q, k (D wide) and v (Dv) at `itemsize`,
    g (D wide, f32) and beta (f32 a head), and writes o (Dv, f32); the
    backward reads all of those and o's gradient and writes the five
    inputs' gradients."""
    rows = batch * length * heads
    inputs = rows * ((2 * head_dim + v_dim) * itemsize + 4 * head_dim + 4)
    o = rows * v_dim * 4
    return 2 * inputs + o if backward else inputs + o


def kda_scores_flops(batch, length, heads, head_dim, sub, backward=False):
    """Operations the kernel of a sub-block's own decayed scores executes
    for one layer, by the [sub, sub, D] terms it forms (every term of the
    square: the rows above the diagonal are computed and masked): forward,
    exp(G_t - G_u) k_u and the two products with their sums (5 a term);
    backward, the decay, the same product, two accumulations and the
    column sum of the keys' part (10 a term). The exponential counts as
    one."""
    return (10.0 if backward else 5.0) * batch * heads * length * sub \
        * head_dim


def kda_scores_min_bytes(batch, length, heads, head_dim, sub, itemsize=2,
                         backward=False):
    """Least bytes that kernel moves for one layer: q, k at `itemsize` and G
    (f32) in and two [sub, sub] f32 blocks out; backward those five in, dq,
    dk at `itemsize` and dG (f32) out."""
    rows = batch * heads * length
    wide = rows * head_dim * (2 * itemsize + 4)
    squares = 2 * rows * sub * 4
    return 2 * wide + squares if backward else wide + squares
