"""Memory-lean LM losses.

`chunked_softmax_cross_entropy` computes causal-LM cross entropy
without ever materializing the full [B, L, vocab] logits tensor in
f32: it flattens the tokens to [B * L] rows and scans over chunks of
rows, projecting each chunk to the vocabulary, reducing it to
logsumexp + target-logit immediately. Under differentiation the same
scan forms the gradient while the chunk's logits are there
(``softmax - onehot`` is known the moment they are): a step makes
three passes of the head (logits, d-hidden, d-kernel), nothing is
formed twice, and the backward rule only scales what the forward
left. Peak live memory is O(rows * vocab) for a chunk's logits plus
the residuals, the [D, vocab] f32 kernel gradient and the [B, L, D]
hidden gradient, instead of O(B * L * vocab). How many rows
a chunk holds follows from the shapes (`loss_plan`); at L=8192 the
dense form does not fit a single v5e at all, the chunked form does.

No reference analogue (the reference never sees model internals); this
is part of the long-context extension the flash kernels anchor.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile

# The most logits a chunk may hold, counted in f32 (the widest a caller's
# hidden states make them), unless the caller's `chunk` asks for more:
# 1024 rows at V=50304 (206 MB in f32, 103 MB as a bf16 call keeps them).
# The sweep on a v5e (PERF.md §6, PR 31; examples/loss_rows_sweep.py; ms a
# call of value and gradient at D=2048, V=50304, bf16 hidden states over
# an f32 head; [2, 2048] and [1, 4096] equal to 0.01): 512 rows 20.85,
# 1024 rows 16.90, 2048 rows 15.70, 4096 rows (one shot) 14.76. More rows
# are faster and what holds the budget here is memory: each doubling is
# 98 MiB more of live logits where the step's memory peaks, and the
# benchmark's four-chip cell has no room for it.
LOGITS_BUDGET_BYTES = 256 * 2**20


def loss_plan(B, L, D, V, chunk=512, dtype=jnp.bfloat16, weighted=False):
    """How `chunked_softmax_cross_entropy` runs a call of the given
    shapes (`hvd.profile.loss_plan`; the function runs what this
    returns, so it needs no chip):

        {"rows": rows of a scan iteration, "iterations": B * L / rows,
         "head_passes": matmuls over the whole head a training step
         makes, "logits_bytes": a chunk's logits live in HBM,
         "residual_bytes": what the forward keeps for the backward}

    `rows` is the largest divisor of B * L whose logits, were they f32,
    fit `LOGITS_BUDGET_BYTES`, and never fewer than the B * chunk rows
    the caller's `chunk` already allows. `dtype` is the hidden states':
    a chunk's logits are kept in it, and so is the residual by `hidden`;
    the residual by the kernel is counted in f32, as the scan carries it.
    `weighted`: a call with per-row weights, which keeps each row's loss
    in f32 as well (the gradient by its weight).
    """
    if L % chunk != 0:
        raise ValueError("L=%d not divisible by chunk=%d" % (L, chunk))
    total = B * L
    fit = min(total, max(1, LOGITS_BUDGET_BYTES // (4 * V)))
    rows = max(B * chunk,
               next(r for r in range(fit, 0, -1) if total % r == 0))
    itemsize = jnp.dtype(dtype).itemsize
    return {"rows": rows, "iterations": total // rows, "head_passes": 3,
            "logits_bytes": rows * V * itemsize,
            "residual_bytes": 4 * D * V + total * D * itemsize
            + (4 * total if weighted else 0)}


def _scan_chunks(rows, hidden, kernel, targets, weights, with_grads):
    """sum_i w_i * nll_i over the rows, `weights` None standing for
    1 / rows-in-all on every row (the mean); with `with_grads` also its
    gradients by `hidden` and `kernel`, formed chunk by chunk while the
    logits are there, and with `weights` each row's nll, which is the
    gradient by its weight."""
    with jax.named_scope(profile.LOSS):
        w = kernel.astype(hidden.dtype)   # once, not once an iteration
        scale = 1.0 / targets.size

        def body(carry, xs):
            h_c, t_c = xs[:2]
            # The MXU accumulates in f32; what it hands on is rounded to
            # the compute dtype, and softmax and lse are f32 on that. A
            # chunk's logits so live in HBM in the compute dtype: asked
            # for in f32 they are 98 MiB more a 1024 rows at V=50304.
            logits = (h_c @ w).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            # The target's logit by a masked sum, which fuses with the
            # other reductions; a gather makes XLA keep a second, f32,
            # copy of the logits for it.
            onehot = t_c[:, None] == jnp.arange(logits.shape[-1])
            tgt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
            nll = lse - tgt
            if weights is None:
                total, row_scale = carry[0] + jnp.sum(nll), scale
            else:
                w_c = xs[2]
                total, row_scale = carry[0] + jnp.sum(w_c * nll), w_c[:, None]
            if not with_grads:
                return (total,), None
            # Rounded to the compute dtype as autodiff's cotangent of
            # the projection would be.
            dl = ((jnp.exp(logits - lse[:, None]) - onehot)
                  * row_scale).astype(w.dtype)
            dh_c = lax.dot_general(dl, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            # The MXU's f32 accumulator goes into the f32 sum as it is.
            dw = carry[1] + lax.dot_general(
                h_c, dl, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dh_c = dh_c.astype(hidden.dtype)
            return (total, dw), dh_c if weights is None else (dh_c, nll)

        init = (jnp.float32(0.0),)
        if with_grads:
            init += (jnp.zeros(kernel.shape, jnp.float32),)
        xs = (hidden.reshape(-1, rows, hidden.shape[-1]),
              targets.reshape(-1, rows))
        if weights is not None:
            xs += (weights.astype(jnp.float32).reshape(-1, rows),)
        carry, ys = lax.scan(body, init, xs)
        loss = carry[0] * scale if weights is None else carry[0]
        if not with_grads:
            return loss
        dw = carry[1].astype(kernel.dtype)
        if weights is None:
            return loss, (ys.reshape(hidden.shape), dw)
        return loss, (ys[0].reshape(hidden.shape), dw,
                      ys[1].reshape(weights.shape))


def _scale(g, residual):
    # In g's f32, then rounded: a g rounded to bf16 first would bias
    # every row alike.
    return (g * residual).astype(residual.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mean_nll(rows, hidden, kernel, targets):
    return _scan_chunks(rows, hidden, kernel, targets, None, False)


def _mean_nll_fwd(rows, hidden, kernel, targets):
    return _scan_chunks(rows, hidden, kernel, targets, None, True)


def _mean_nll_bwd(rows, residuals, g):
    with jax.named_scope(profile.LOSS):
        return tuple(_scale(g, r) for r in residuals) + (None,)


_mean_nll.defvjp(_mean_nll_fwd, _mean_nll_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_nll(rows, hidden, kernel, targets, weights):
    return _scan_chunks(rows, hidden, kernel, targets, weights, False)


def _weighted_nll_fwd(rows, hidden, kernel, targets, weights):
    return _scan_chunks(rows, hidden, kernel, targets, weights, True)


def _weighted_nll_bwd(rows, residuals, g):
    dh, dw, nll = residuals
    with jax.named_scope(profile.LOSS):
        return _scale(g, dh), _scale(g, dw), None, g * nll


_weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def chunked_softmax_cross_entropy(hidden, kernel, targets, chunk=512,
                                  weights=None):
    """Mean token cross entropy over chunked vocab projections.

    Args:
      hidden: [B, L, D] final hidden states (any float dtype; the
        projection runs in this dtype and reduces in f32).
      kernel: [D, V] lm-head kernel (no bias, the standard LM head).
      targets: [B, L] int target token ids.
      chunk: sequence chunk length; L must be divisible by it (pass
        chunk=L for one-shot). A scan iteration takes at least
        B * chunk of the B * L rows, and more where their logits fit
        `LOGITS_BUDGET_BYTES` (`loss_plan`).
      weights: optional [B, L] float, a weight a row; the result is then
        ``sum(weights * nll)`` (None stands for 1 / (B * L) on every
        row), differentiable by the weights too: a row's gradient is
        its nll.

    Returns the scalar mean loss = mean(logsumexp(logits) -
    logits[target]) — identical math to log_softmax + gather. All
    gradients are formed in the forward pass (module text).
    """
    B, L, D = hidden.shape
    plan = loss_plan(B, L, D, kernel.shape[1], chunk, hidden.dtype)
    if weights is None:
        return _mean_nll(plan["rows"], hidden, kernel, targets)
    return _weighted_nll(plan["rows"], hidden, kernel, targets, weights)


# --------------------------------------------------------------------------
# The loss of a looped stack with an exit after every pass
# (`models.Transformer` with `num_passes` > 1 and `exit_gate`)
# --------------------------------------------------------------------------

def exit_distribution(gate_logits):
    """(p, log p), each [T, ...] f32: the share of every token that
    leaves at exit t, from the gates' logits [T, ...]. lam^t =
    sigmoid(logit^t) is the share of what reached exit t that leaves
    there; the last exit takes what is left, so its own gate is not read:

        p^t = lam^t * prod_{j<t} (1 - lam^j)   (t < T)
        p^T = prod_{j<T} (1 - lam^j)

    Formed in logs (`log_sigmoid`), so that log p is finite wherever the
    logits are (a trained gate saturates, and 0 * log 0 would poison the
    entropy's gradient); p sums to 1 over t as closely as the device's
    exp and log are exact (1.5e-4 on a v5e)."""
    g = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)
    reached = jnp.concatenate([jnp.zeros_like(g[:1]), stay], axis=0)
    leave = jnp.concatenate([jax.nn.log_sigmoid(g[:-1]),
                             jnp.zeros_like(g[:1])], axis=0)
    logp = reached + leave
    return jnp.exp(logp), logp


def expected_exit_loss(hidden, gate_logits, kernel, targets, beta=0.0,
                       chunk=512):
    """The training objective of a looped LM with learned exits (Ouro's
    Stage I, arXiv:2510.25741 section 3): the cross-entropy expected
    under each token's exit distribution, less `beta` times that
    distribution's entropy (a uniform prior over the exits),

        mean_i [ sum_t p^t_i * nll^t_i  -  beta * H(p_i) ]

    hidden [T, B, L, D] and gate_logits [T, B, L] are every pass's, as
    `models.Transformer` returns them; kernel [D, V] the one head of all
    exits; targets [B, L]. All T * B * L rows go through ONE call of
    `chunked_softmax_cross_entropy` with p / (B * L) as the rows'
    weights: one scan, one f32 gradient of the head, and the gradient by
    the gates comes back through the weights."""
    T, B, L, D = hidden.shape
    with jax.named_scope(profile.EXIT):
        p, logp = exit_distribution(gate_logits)
        weights = p / (B * L)
    # One sequence of rows: the plan's floor of B * chunk rows a chunk
    # would otherwise grow with the exits.
    expected = chunked_softmax_cross_entropy(
        hidden.reshape(1, T * B * L, D), kernel,
        jnp.broadcast_to(targets, (T, B, L)).reshape(1, T * B * L),
        chunk=chunk, weights=weights.reshape(1, T * B * L))
    with jax.named_scope(profile.EXIT):
        entropy = -jnp.sum(p * logp) / (B * L)
        return expected - beta * entropy


def exit_stats(gate_logits):
    """A step's exit statistics from the gates' logits [T, ...]:
    ``p_mean`` [T], the mean share of a token that leaves at each exit
    (sums to 1), and ``entropy``, the mean entropy of a token's exit
    distribution in nats (at most ln T)."""
    p, logp = exit_distribution(gate_logits)
    p = p.reshape(p.shape[0], -1)
    return {"p_mean": jnp.mean(p, axis=1),
            "entropy": -jnp.sum(p * logp.reshape(p.shape)) / p.shape[1]}
