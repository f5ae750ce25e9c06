"""Analytic operation and byte counts of Kanana-2-30B-A3B as one rank of an
expert- and vocabulary-parallel group holds it (latent attention with the
queries straight from the state, one leading dense layer, a shared pair and
`held` of `experts` sigmoid-routed experts a layer): beside `flops.py`, and
like it independent of the program and the compiler. Every count follows
from the sizes in a configuration file; the flash kernels' EXECUTED
operations from the score tiles the kernels `horovod_tpu.profile.flash_plan`
names compute at the blocks it gives (a tile the diagonal cuts is computed
whole: never L^2 / 2), both products, v `vd` wide. The grouped matmuls'
counts are `flops_moe`'s, over the rows expected on the HELD experts (the
builder passes both). One multiply-accumulate is two operations.
"""

from benchmark import flops, flops_xing

FLASH_WIDTHS = flops_xing.FLASH_WIDTHS  # products a tile, (q.k, p.v) wide


def latent_attention_params(hidden, heads, kv_rank, nope, rope, vd):
    """Matmul parameters of one latent-attention layer with direct queries:
    W_q, W_kva, W_kvb, W_o."""
    return (hidden * heads * (nope + rope) + hidden * (kv_rank + rope)
            + kv_rank * heads * (nope + vd) + heads * vd * hidden)


def layer_params(hidden, heads, kv_rank, nope, rope, vd, dense_width=None,
                 expert_width=None, shared_width=0, held=0, experts=0):
    """Every parameter of one layer as held: attention with its one inner
    norm, two block norms, and either the dense gated feed-forward or router
    + selection bias + the shared pair + `held` routed experts."""
    out = (latent_attention_params(hidden, heads, kv_rank, nope, rope, vd)
           + kv_rank + 2 * hidden)
    if dense_width is not None:
        return out + 3 * hidden * dense_width
    return (out + hidden * experts + experts + 3 * hidden * shared_width
            + held * 3 * hidden * expert_width)


def params(hidden, heads, kv_rank, nope, rope, vd, dense_width, expert_width,
           shared_width, held, experts, vocab, dense_layers, routed_layers):
    """Every parameter held: the layers, the embedding and the head over
    `vocab` ids, the final norm."""
    attn = (hidden, heads, kv_rank, nope, rope, vd)
    return (dense_layers * layer_params(*attn, dense_width=dense_width)
            + routed_layers * layer_params(
                *attn, expert_width=expert_width, shared_width=shared_width,
                held=held, experts=experts)
            + 2 * vocab * hidden + hidden)


def layer_forward_flops_per_token(hidden, heads, kv_rank, nope, rope, vd,
                                  expert_width, shared_width, held, experts,
                                  top_k, length):
    """A routed layer's forward operations a token, by part: the latent
    projections, attention's two products over a causal context (length / 2
    keys on average), the shared pair, the held experts the token is
    EXPECTED to be sent to."""
    return {
        "projections": 2.0 * latent_attention_params(
            hidden, heads, kv_rank, nope, rope, vd),
        "attention": (flops.attention_matmul_flops(1, heads, length,
                                                   nope + rope)
                      + flops.attention_matmul_flops(1, heads, length, vd))
        / length,
        "shared": 2.0 * 3 * hidden * shared_width,
        "held_experts": 2.0 * top_k * held / experts * 3 * hidden
        * expert_width}


def model_flops_per_token(hidden, heads, kv_rank, nope, rope, vd,
                          dense_width, expert_width, shared_width, held,
                          experts, top_k, vocab, dense_layers, routed_layers,
                          length):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (attention's projections, the dense
    feed-forward or router + the shared pair + the `top_k * held / experts`
    held experts it is EXPECTED to be sent to; the head), plus attention's
    two products forward and four backward over a causal context (q.k is
    nope + rope wide, p.v is vd wide). Recomputation, sort, the rows'
    kernels and the rotations are not counted."""
    common = latent_attention_params(hidden, heads, kv_rank, nope, rope, vd)
    dense = common + 3 * hidden * dense_width
    routed = (common + hidden * experts + 3 * hidden * shared_width
              + top_k * held / experts * 3 * hidden * expert_width)
    matmul = dense_layers * dense + routed_layers * routed + hidden * vocab
    attn = (dense_layers + routed_layers) * 3.0 * (
        flops.attention_matmul_flops(1, heads, length, nope + rope)
        + flops.attention_matmul_flops(1, heads, length, vd))
    return 6.0 * matmul + attn / length


def executed_pairs(plan, length, group=1):
    """(query, key) pairs of ONE head whose scores a causal flash kernel
    under `plan` (a `FlashKernelPlan`: `block_q` rows of the grouped layout,
    `block_k` keys) computes over a sequence: every [block_q // group,
    block_k] tile that holds a pair at or below the diagonal, whole.
    Resident or gridded, held by either side, the kernels compute exactly
    those tiles (`_walk_k_blocks`, `_walk_q_blocks`, the gridded kernels'
    `visible`)."""
    bqp, bk = plan.block_q // group, plan.block_k
    tiles = sum(-(-(i + 1) * bqp // bk) for i in range(length // bqp))
    return tiles * bqp * bk


def flash_executed_flops(plans, batch, heads, length, nope, rope, vd,
                         group=1):
    """Operations the flash kernels of `plans` ({kernel name: plan}, the
    forward's and the backward's answers of `flash_plan(..., shared_dim=)`)
    execute for one layer: each kernel's products (`FLASH_WIDTHS`) over the
    pairs of the tiles it computes."""
    total = 0.0
    for name, plan in plans.items():
        qk, pv = FLASH_WIDTHS[name]
        total += (2.0 * batch * heads * executed_pairs(plan, length, group)
                  * (qk * (nope + rope) + pv * vd))
    return total


def flash_min_bytes(kernels, batch, heads, length, nope, rope, vd,
                    itemsize=2):
    """Least bytes the kernels named `kernels` move for one layer, each
    tensor once per kernel that needs it: per head q_nope, k_nope (nope
    wide), v, o, dO (vd wide), q_rope (rope wide) and their gradients; the
    shared key AND its gradient once a batch (the one-kernel backward sums
    the gradient over the heads where it is formed; `flops_xing` counts it
    once a head, as the kernels held by the k block write it); a row
    statistic at 4 bytes a row."""
    xing = flops_xing.flash_min_bytes(kernels, batch, heads, length, nope,
                                      rope, vd, itemsize)
    once = batch * length * rope * itemsize
    per_head = batch * heads * length * rope * itemsize
    return xing - sum(per_head - once for k in kernels
                      if k in ("hvd_flash_bwd", "hvd_flash_dkv"))
