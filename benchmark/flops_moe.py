"""Analytic operation and byte counts of mixture-of-experts configurations —
beside `flops.py`, and like it independent of the program and the compiler:
every count follows from the sizes in a configuration file. One
multiply-accumulate is two operations.
"""

from benchmark import flops

# Grouped matmuls of ONE gated expert layer in a train step: forward three
# (gate, up, down), backward for each of them the gradient of its rows and
# the gradient of its weights.
GATED_EXPERTS_MATMULS = 9


def olmoe_layer_matmul_params(hidden, width, experts, router):
    """Parameters of one OLMoE layer that sit in a matrix multiplication:
    q, k, v, out (4·hidden², full MHA), the router (hidden·router) and
    `experts` gated experts of three hidden x width matrices each."""
    return (4 * hidden * hidden + hidden * router
            + experts * 3 * hidden * width)


def olmoe_params(hidden, width, experts, vocab, layers, router=None):
    """Every parameter held by a model of `experts` experts a layer (the
    router `router` wide, by default as wide as that): the layers' matmul
    parameters, four norm scales a layer (two block norms of `hidden`, the
    q and k norms of `hidden` each under MHA), the embedding, the head and
    the final norm. With `experts` = the experts a token uses and `router`
    = all of them it is the ACTIVE parameters of a token."""
    router = experts if router is None else router
    return (layers * (olmoe_layer_matmul_params(hidden, width, experts,
                                                router) + 4 * hidden)
            + 2 * vocab * hidden + hidden)


def olmoe_model_flops_per_token(hidden, width, experts, top_k, vocab, layers,
                                heads, head_dim, length):
    """Forward + backward operations one token requires: 6 per ACTIVE
    matmul parameter (the router over all `experts`, `top_k` experts, the
    attention projections, the head) plus attention's 2 products forward
    and 4 backward over a causal context of `length` (`flops.py`'s
    convention; the kernels' recomputation is not counted, nor are sort,
    gather and the elementwise gate)."""
    active = (layers * olmoe_layer_matmul_params(hidden, width, top_k,
                                                 experts) + hidden * vocab)
    attn = layers * 6.0 * flops.attention_matmul_flops(1, heads, length,
                                                       head_dim)
    return 6.0 * active + attn / length


def grouped_matmul_flops(rows, k, n):
    """Operations of one grouped matmul [rows, k] x [groups, k, n]: every
    row meets one k x n matrix, whatever the group sizes."""
    return 2.0 * rows * k * n


def grouped_matmul_min_bytes(rows, k, n, groups, itemsize, matrix_itemsize):
    """Least bytes one grouped matmul moves: its rows and its result at
    `itemsize` (the compute dtype) and all the groups' matrices at
    `matrix_itemsize` (the dtype they are RESIDENT in: the program keeps no
    copy of them in the compute dtype), each once. Also for the two
    backward forms, whose operands are these three arrays in other roles:
    the gradient of the rows reads the matrices, the gradient of the
    matrices writes an array of their shape and dtype."""
    return (rows * k + rows * n) * itemsize + groups * k * n * matrix_itemsize


def gated_experts_flops(rows, hidden, width):
    """Operations the nine grouped matmuls of one gated expert layer
    execute in a train step on `rows` assigned rows (top_k x tokens)."""
    return GATED_EXPERTS_MATMULS * grouped_matmul_flops(rows, hidden, width)


def gated_experts_min_bytes(rows, hidden, width, experts, itemsize,
                            matrix_itemsize):
    """Least bytes those nine move (every one has the same three shapes)."""
    return GATED_EXPERTS_MATMULS * grouped_matmul_min_bytes(
        rows, hidden, width, experts, itemsize, matrix_itemsize)
