"""Plain reference for Ouro (Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741; `OuroForCausalLM` of
huggingface.co/ByteDance/Ouro-2.6B), on the parameter tree that
`models.Transformer` builds for it.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: no kernel, no chunking, no bf16, the exit
distribution by its products and not in logs. Call it under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bf16 passes.

```
h^0      = E[tokens]                                           E [V, D]
for t = 1..T, the SAME N layers:      u = h^(t-1)
  for l = 1..N:
    o    = Attn_l( rms(u) * g1_l )                             16 heads x 128, no bias, causal, scale 128^-1/2,
                                                               rotary base 1e6 over the whole head (rotate-half)
    u    = u + rms(o) * g2_l                                   sandwich norm: a second RMSNorm on the branch's OUTPUT
    f    = Wdown_l ( silu(Wgate_l m) * (Wup_l m) ),  m = rms(u) * g3_l          width 5632
    u    = u + rms(f) * g4_l
  h^t    = rms(u) * g_f                                        the one final norm, after EVERY pass; h^t is both
                                                               exit t's hidden state and the next pass's input
  lam^t  = sigmoid( h^t . w_g + b_g )                          the exit gate, Linear(D, 1) with bias, per token
p^1 = lam^1;  p^t = lam^t * prod_{j<t} (1 - lam^j)  (1 < t < T);  p^T = prod_{j<T} (1 - lam^j)      sums to 1 a token
nll^t_i  = logsumexp(h^t_i W_head) - (h^t_i W_head)[target_i]  one untied head [D, V] for all four exits
loss     = mean_i [ sum_t p^t_i * nll^t_i  -  beta * H(p_i) ],   H(p) = - sum_t p^t log p^t
```

`rms(x) = x / sqrt(mean(x^2) + eps)` with a plain (not zero-centred) scale.
The pass loop, the final norm inside it, the gate and the exit distribution
are as `modeling_ouro.py` forms them (`OuroModel.forward`'s
`hidden_states_list`, `gate_list`; `OuroForCausalLM`'s `pdf_list`); the
objective is the paper's Stage I (its section 3, entropy-regularised with a
uniform prior).

Departures from the published model (the configuration's `departures`):
- The loss closes the sequence on itself (the last position predicts the
  first token), as the repository's other references do, so that every
  position has a target; a training job would mask it.
- Stage I's objective trains gate and model together, from random weights;
  the published model's gate had a second stage of its own.
(Weight decay on every parameter is the job's, not this file's.)

`passes=1` is the same stack run once (one exit, p = 1): what the system
must NOT agree with. `separate` gives every pass its own parameter tree
(a list of T trees) for the same purpose in the tests.
"""

import jax
import jax.numpy as jnp


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def _rotary(x, base):
    """x [L, H, D] at positions 0..L-1; dimension i pairs with i + D/2."""
    L, _, D = x.shape
    half = D // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(u, p, rope_base, eps):
    """One layer on u [L, D]: attention and the gated feed-forward, each
    between its two norms."""
    L = u.shape[0]
    a = p["attn"]
    m = _rms(u, p["norm1"]["scale"], eps)
    q = jnp.einsum("ld,dhk->lhk", m, _f32(a["query"]["kernel"]))
    k = jnp.einsum("ld,dhk->lhk", m, _f32(a["key"]["kernel"]))
    v = jnp.einsum("ld,dhk->lhk", m, _f32(a["value"]["kernel"]))
    q, k = _rotary(q, rope_base), _rotary(k, rope_base)
    s = jnp.einsum("qhk,thk->hqt", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
    o = jnp.einsum("qhk,hkd->qd", o, _f32(a["out"]["kernel"]))
    u = u + _rms(o, p["norm1_out"]["scale"], eps)
    m = _rms(u, p["norm2"]["scale"], eps)
    f = (jax.nn.silu(m @ _f32(p["mlp_gate"]["kernel"]))
         * (m @ _f32(p["mlp_up"]["kernel"]))) @ _f32(p["mlp_out"]["kernel"])
    return u + _rms(f, p["norm2_out"]["scale"], eps)


def exit_distribution(lam):
    """p [T, L] from the gates lam [T, L] in (0, 1), by the products."""
    T = lam.shape[0]
    p, left = [], jnp.ones_like(lam[0])
    for t in range(T - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(left)
    return jnp.stack(p)


def forward(params, tokens, num_layers, passes, rope_base, eps=1e-6,
            beta=0.05, separate=None):
    """Everything of ONE sequence `tokens` [L], in float32:

        {"hidden": [T, L, D] every pass's h^t, "gate_logits": [T, L],
         "p": [T, L] the exit distribution, "nll": [T, L] each token's
         cross-entropy at each exit, "expected_nll": mean_i sum_t p nll,
         "entropy": mean_i H(p_i), "loss": expected_nll - beta * entropy}
    """
    trees = separate if separate is not None else [params] * passes
    targets = jnp.roll(tokens, -1)
    head = _f32(params["lm_head"]["kernel"])
    w_g = _f32(params["exit_gate"]["kernel"])[:, 0]
    b_g = _f32(params["exit_gate"]["bias"])[0]
    h = _f32(params["embed"]["embedding"])[tokens]
    hidden, gates, nll = [], [], []
    for t in range(passes):
        u = h
        for i in range(num_layers):
            u = layer(u, trees[t]["block_%d" % i], rope_base, eps)
        h = _rms(u, trees[t]["norm_f"]["scale"], eps)
        logits = h @ head
        hidden.append(h)
        gates.append(h @ w_g + b_g)
        nll.append(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[:, None], axis=-1)[:, 0])
    gates, nll = jnp.stack(gates), jnp.stack(nll)
    p = exit_distribution(jax.nn.sigmoid(gates))
    expected = jnp.mean(jnp.sum(p * nll, axis=0))
    # 0 * log 0 = 0 (a gate saturated in float32)
    entropy = jnp.mean(-jnp.sum(jnp.where(p > 0, p * jnp.log(
        jnp.where(p > 0, p, 1.0)), 0.0), axis=0))
    return {"hidden": jnp.stack(hidden), "gate_logits": gates, "p": p,
            "nll": nll, "expected_nll": expected, "entropy": entropy,
            "loss": expected - beta * entropy}
