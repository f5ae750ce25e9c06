"""Analytic operation and byte counts of a looped language model (Ouro: one
stack of N layers run T times on shared weights, an exit after every pass)
— beside `flops.py`, and like it independent of the program and the
compiler: every count follows from the sizes in a configuration file. One
multiply-accumulate is two operations.

A weight that is used T times a step does T times the work: the counts are
of layer PASSES (T x N) and of head projections (T: the objective needs the
logits of every exit), not of parameters held.
"""

from benchmark import flops


def layer_matmul_params(hidden, width):
    """Parameters of one layer that sit in a matrix multiplication: q, k,
    v, out (4·hidden², full MHA) and the gated feed-forward's three
    hidden x width matrices."""
    return 4 * hidden * hidden + 3 * hidden * width


def params(hidden, width, vocab, layers):
    """Every parameter HELD, each once however often it is used: the
    layers' matrices, four norm scales a layer (sandwich norm), the
    embedding, the untied head, the final norm, and the exit gate with its
    bias."""
    return (layers * (layer_matmul_params(hidden, width) + 4 * hidden)
            + 2 * vocab * hidden + hidden + hidden + 1)


def model_flops_per_token(hidden, width, vocab, layers, passes, heads,
                          head_dim, length):
    """Forward + backward operations one token requires: 6 per matmul
    parameter of each of the passes x layers layer passes and of each of
    the `passes` head projections, plus attention's 2 products forward and
    4 backward over a causal context of `length` in every layer pass
    (`flops.py`'s convention: the kernels' recomputation is not counted,
    nor are the norms, the gate of one column and the elementwise work)."""
    dense = 6.0 * passes * (layers * layer_matmul_params(hidden, width)
                            + hidden * vocab)
    attn = passes * layers * 6.0 * flops.attention_matmul_flops(
        1, heads, length, head_dim)
    return dense + attn / length


def flash_executed_flops(kernels, layers, passes, batch, heads, length,
                         head_dim):
    """Operations the flash kernels named `kernels` (`flash_plan`'s, for
    one layer) execute in a train step: once a layer pass."""
    return passes * layers * flops.flash_executed_flops(
        kernels, batch, heads, length, head_dim)


def flash_min_bytes(kernels, layers, passes, batch, heads, kv_heads, length,
                    head_dim):
    """Least bytes those kernels move, once a layer pass."""
    return passes * layers * flops.flash_min_bytes(
        kernels, batch, heads, kv_heads, length, head_dim)
