"""Analytic operation and byte counts of Xing4.0-29B-A4B as one rank of an
expert- and vocabulary-parallel group holds it (latent attention,
hyper-connections, a shared expert and `held` of `experts` routed ones a
layer, a multi-token prediction module): beside `flops.py`, and like it
independent of the program and the compiler. Every count follows from the
sizes in a configuration file; the flash kernels' from the PATH
`horovod_tpu.profile.flash_plan` reports for the shapes (one backward kernel
or two), which is read from the shapes too. The grouped matmuls' counts are
`flops_moe`'s, over the rows expected on the HELD experts and their
matrices (the builder passes both). One multiply-accumulate is two
operations.
"""

from benchmark import flops


def latent_attention_params(hidden, heads, q_rank, kv_rank, nope, rope, vd):
    """Matmul parameters of one latent-attention layer: W_qa, W_qb, W_kva,
    W_kvb, W_o."""
    return (hidden * q_rank + q_rank * heads * (nope + rope)
            + hidden * (kv_rank + rope) + kv_rank * heads * (nope + vd)
            + heads * vd * hidden)


def hyper_connection_params(hidden, streams):
    """Parameters of ONE branch's hyper-connection: phi [n*C, 2n + n^2],
    its bias and the three scales."""
    k = 2 * streams + streams * streams
    return streams * hidden * k + k + 3


def layer_params(hidden, heads, q_rank, kv_rank, nope, rope, vd, streams,
                 dense_width=None, expert_width=None, held=0, experts=0):
    """Every parameter of one layer as held: attention with its two inner
    norms, two hyper-connections, two block norms, and either the dense
    gated feed-forward or router + selection bias + shared expert + `held`
    routed experts."""
    out = (latent_attention_params(hidden, heads, q_rank, kv_rank, nope,
                                   rope, vd) + q_rank + kv_rank
           + 2 * hyper_connection_params(hidden, streams) + 2 * hidden)
    if dense_width is not None:
        return out + 3 * hidden * dense_width
    return (out + hidden * experts + experts
            + (1 + held) * 3 * hidden * expert_width)


def params(hidden, heads, q_rank, kv_rank, nope, rope, vd, streams,
           dense_width, expert_width, held, experts, vocab, dense_layers,
           routed_layers):
    """Every parameter held: the layers, the embedding and the head over
    `vocab` ids, the final norm, and the prediction module (one routed
    layer, W_eh [2C, C] and its three norms)."""
    routed = layer_params(hidden, heads, q_rank, kv_rank, nope, rope, vd,
                          streams, expert_width=expert_width, held=held,
                          experts=experts)
    dense = layer_params(hidden, heads, q_rank, kv_rank, nope, rope, vd,
                         streams, dense_width=dense_width)
    return (dense_layers * dense + (routed_layers + 1) * routed
            + 2 * vocab * hidden + hidden
            + 2 * hidden * hidden + 3 * hidden)


def model_flops_per_token(hidden, heads, q_rank, kv_rank, nope, rope, vd,
                          streams, dense_width, expert_width, held, experts,
                          top_k, vocab, dense_layers, routed_layers, length):
    """Forward + backward operations one token requires on this rank: 6 per
    matmul parameter it meets (attention's projections, the two
    hyper-connections' phi, the dense feed-forward or router + shared expert
    + the `top_k * held / experts` held experts it is EXPECTED to be sent
    to), the module's W_eh and BOTH head projections, plus attention's two
    products forward and four backward over a causal context (q.k is nope +
    rope wide, p.v is vd wide). Recomputation, sort, gathers, the Sinkhorn
    iterations and the streams' mixing are not counted."""
    k = 2 * streams + streams * streams
    common = (latent_attention_params(hidden, heads, q_rank, kv_rank, nope,
                                      rope, vd)
              + 2 * streams * hidden * k)
    dense = common + 3 * hidden * dense_width
    routed = (common + hidden * experts
              + (1.0 + top_k * held / experts) * 3 * hidden * expert_width)
    matmul = (dense_layers * dense + (routed_layers + 1) * routed
              + 2 * hidden * hidden + 2 * hidden * vocab)
    layers = dense_layers + routed_layers + 1
    attn = layers * 3.0 * (
        flops.attention_matmul_flops(1, heads, length, nope + rope)
        + flops.attention_matmul_flops(1, heads, length, vd))
    return 6.0 * matmul + attn / length


# Widths of the matrix products a flash kernel executes per block pair
# under scores of two products, in units of (nope + rope, vd): the forward
# forms s and p.v; the one-kernel backward s, dp, dv, dk (both slices) and
# dq (both slices); as two kernels dQ forms s, dp, dq and dK/dV forms s,
# dp, dv, dk.
FLASH_WIDTHS = {"hvd_flash_fwd": (1, 1), "hvd_flash_bwd": (3, 2),
                "hvd_flash_dq": (2, 1), "hvd_flash_dkv": (2, 2)}


def flash_executed_flops(kernels, batch, heads, length, nope, rope, vd):
    """Operations the flash kernels named `kernels` (the keys of
    `flash_plan`'s forward and backward answers) execute for one layer."""
    qk = flops.attention_matmul_flops(batch, heads, length, nope + rope)
    pv = flops.attention_matmul_flops(batch, heads, length, vd)
    return sum(FLASH_WIDTHS[k][0] * qk + FLASH_WIDTHS[k][1] * pv
               for k in kernels)


def flash_min_bytes(kernels, batch, heads, length, nope, rope, vd,
                    itemsize=2):
    """Least bytes those kernels move for one layer, each tensor once per
    kernel that needs it: per head q_nope, k_nope (nope wide), v, o, dO (vd
    wide), q_rope (rope wide) and their gradients; the shared key ONCE a
    batch, its gradient once a head (that is how it leaves the kernel); a
    row statistic at 4 bytes a row."""
    rows = batch * heads * length
    per_head = lambda width: rows * width * itemsize  # noqa: E731
    shared = batch * length * rope * itemsize
    stat = rows * 4
    cost = {
        # reads q, q2, k, v, k2; writes o, lse
        "hvd_flash_fwd": (2 * per_head(nope) + per_head(rope)
                          + 2 * per_head(vd) + shared + stat),
        # reads q, q2, k, v, k2, dO, lse, delta; writes dq, dq2, dk, dv, dk2
        "hvd_flash_bwd": (4 * per_head(nope) + 3 * per_head(rope)
                          + 3 * per_head(vd) + shared + 2 * stat),
        # reads the same; writes dq, dq2
        "hvd_flash_dq": (3 * per_head(nope) + 2 * per_head(rope)
                         + 2 * per_head(vd) + shared + 2 * stat),
        # reads the same; writes dk, dv, dk2
        "hvd_flash_dkv": (3 * per_head(nope) + 2 * per_head(rope)
                          + 3 * per_head(vd) + shared + 2 * stat)}
    return sum(cost[k] for k in kernels)

