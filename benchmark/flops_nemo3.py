"""Analytic operation and byte counts of a Nemotron-H stack as one rank of an
expert- and vocabulary-parallel group holds it (a layer pattern of Mamba-2
layers, attention layers and LatentMoE layers with `held` of `experts`
routed relu2 experts in a latent beside a shared one): beside `flops.py`,
and like it independent of the program and the compiler. Every count
follows from the sizes in a configuration file. The flash kernels' counts
are `flops.py`'s by the kernels `hvd.profile.flash_plan` names; the grouped
matmuls' are `flops_moe`'s per matmul, over the rows expected on the HELD
experts (the builder passes both). One multiply-accumulate is two
operations.
"""

from benchmark import flops, flops_moe

# Grouped matmuls of ONE expert layer without a gate in a train step:
# forward two (up, down), backward for each the gradient of its rows and of
# its matrices.
UNGATED_EXPERTS_MATMULS = 6


def mamba2_matmul_params(hidden, heads, head_dim, groups, state):
    """In-projection to [z | xBC | dt] and out-projection."""
    inner = heads * head_dim
    return (hidden * (2 * inner + 2 * groups * state + heads)
            + inner * hidden)


def mamba2_params(hidden, heads, head_dim, groups, state, taps):
    """Every parameter of one Mamba-2 layer: the two projections, the
    convolution's taps and bias, dt_bias, A_log and D a head, the gated
    norm's scale, the layer's norm."""
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    return (mamba2_matmul_params(hidden, heads, head_dim, groups, state)
            + (taps + 1) * conv_dim + 3 * heads + inner + hidden)


def attention_matmul_params(hidden, heads, kv_heads, head_dim):
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def latent_moe_shared_params(hidden, experts, latent, shared):
    """What a LatentMoE layer holds whole on every rank, all in matrix
    products: router, W_1, W_2, the shared expert's two matrices."""
    return hidden * experts + 2 * hidden * latent + 2 * hidden * shared


def expert_params(latent, width):
    return 2 * latent * width


def params(pattern, hidden, vocab, ssm, attn, moe):
    """Every parameter held. `pattern`: a kind a layer ("ssm" | "attn" |
    "moe"); ssm = (heads, head_dim, groups, state, taps); attn = (heads,
    kv_heads, head_dim); moe = (experts, held, latent, width, shared)."""
    experts, held, latent, width, shared = moe
    per = {"ssm": mamba2_params(hidden, *ssm),
           "attn": attention_matmul_params(hidden, *attn) + hidden,
           "moe": latent_moe_shared_params(hidden, experts, latent, shared)
           + experts + held * expert_params(latent, width) + hidden}
    return sum(per[k] for k in pattern) + 2 * vocab * hidden + hidden


def ssd_forward_flops(length, heads, head_dim, groups, state, chunk,
                      causal=False):
    """Operations of the chunked scan's four products over one sequence,
    forward: C B^T a group (chunk x chunk x state), (C B^T . decay)(dt x) a
    head (chunk x chunk x head_dim), a chunk's state and C . S a head
    (head_dim x state a token each). `causal`: the two products inside a
    chunk at half the square, which is what the recurrence requires; the
    executed count takes them whole."""
    inner = heads * head_dim
    inside = 2.0 * length * chunk * (groups * state + inner)
    return (inside / 2.0 if causal else inside) \
        + 2 * 2.0 * length * inner * state


def ssd_min_bytes(length, heads, head_dim, groups, state, itemsize=2):
    """Least bytes one pass of the scan moves: x in and y out at `itemsize`,
    B and C, and dt in f32. The backward pass reads these and the
    cotangent and writes four gradients: twice a forward."""
    return ((2 * length * heads * head_dim + 2 * length * groups * state)
            * itemsize + length * heads * 4)


def model_flops_per_token(pattern, hidden, vocab, length, ssm, attn, moe,
                          top_k, chunk):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (a Mamba-2 layer's two projections;
    attention's four; router, W_1, W_2, the shared expert and the `top_k *
    held / experts` held experts it is EXPECTED to be sent to; the head),
    3 times the scan's four products forward with the two inside a chunk at
    half the square, and attention's two products forward and four backward
    over a causal context. Recomputation, the convolution, decays and
    cumulative sums, top-k, sort and the rows' moves are not counted."""
    heads, head_dim, groups, state, _ = ssm
    a_heads, kv_heads, a_dim = attn
    experts, held, latent, width, shared = moe
    per = {"ssm": 6.0 * mamba2_matmul_params(hidden, heads, head_dim, groups,
                                             state)
           + 3.0 * ssd_forward_flops(length, heads, head_dim, groups, state,
                                     chunk, causal=True) / length,
           "attn": 6.0 * attention_matmul_params(hidden, a_heads, kv_heads,
                                                 a_dim)
           + 6.0 * flops.attention_matmul_flops(1, a_heads, length, a_dim)
           / length,
           "moe": 6.0 * (latent_moe_shared_params(hidden, experts, latent,
                                                  shared)
                         + top_k * held / experts
                         * expert_params(latent, width))}
    return sum(per[k] for k in pattern) + 6.0 * hidden * vocab


def ungated_experts_flops(rows, latent, width):
    """Operations the six grouped matmuls of one ungated expert layer
    execute in a train step on `rows` assigned rows."""
    return UNGATED_EXPERTS_MATMULS * flops_moe.grouped_matmul_flops(
        rows, latent, width)


def ungated_experts_min_bytes(rows, latent, width, experts, itemsize,
                              matrix_itemsize):
    """Least bytes those six move (`flops_moe.grouped_matmul_min_bytes`:
    every one has the same three shapes)."""
    return UNGATED_EXPERTS_MATMULS * flops_moe.grouped_matmul_min_bytes(
        rows, latent, width, experts, itemsize, matrix_itemsize)
