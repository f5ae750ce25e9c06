"""Plain reference for OLMoE (Muennighoff et al., arXiv:2409.02060;
`OlmoeForCausalLM` of Hugging Face transformers), on the parameter tree that
`models.Transformer` builds for it.

Straightforward `jax.numpy` in float32, written from the layer equations and
not from the program's code: every expert is computed densely for every
token and masked by the top-k weights; no sort, no grouped matmul, no
kernel, no chunking, no bf16. Call it under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bf16 passes.

    x_0   = E[tokens]
    h     = rms(x_l) * g1
    q     = rms(W_q h) * g_q ,  k = rms(W_k h) * g_k ,  v = W_v h
            (QK-norm: over the whole projection, all heads at once, before
             the split into heads and before rotary)
    a     = x_l + W_o CausalSoftmaxAttn(rot(q), rot(k), v)
    u     = rms(a) * g2
    p     = softmax_E(W_r u)                                  (float32)
    S     = the k experts of largest p (ties: the lower index)
    x_l+1 = a + sum_{e in S} p_e * W_down,e (silu(W_gate,e u) * (W_up,e u))
            (weights p_e as they are unless `renormalize`; no token dropped)
    hid   = rms(x_N) * g_f
    ce    = mean_t ( logsumexp(hid_t W_head) - (hid_t W_head)[tokens[t+1 mod L]] )
    lb_l  = E * sum_e f_e * P_e ,  f_e = |{t: e in S_t}| / T ,  P_e = mean_t p_te
    z_l   = mean_t logsumexp(W_r u_t)^2
    loss  = ce + w_lb * mean_l lb_l + w_z * mean_l z_l

with rms(x) = x / sqrt(mean(x^2) + eps), attention over heads of `head_dim`
with scale head_dim^-1/2, rotary embedding over the whole head (rotate-half
pairing: dimension i with i + head_dim/2).

Departures from the published description, all in the auxiliary terms:
- Hugging Face's `load_balancing_loss_func` concatenates the router logits
  of all layers and forms f and P over layers x tokens before their
  product; here each layer has its own product and the layers are averaged
  (megablocks' `batched_load_balancing_loss`, which OLMoE was trained with,
  also sums per-layer products). For one layer the two are the same number.
  f counts all k choices and is not divided by k (Hugging Face's scale:
  even routing gives k); megablocks divides by k.
- The loss closes the sequence on itself (the last position predicts the
  first token), as the repository's other reference does, so that every
  position has a target; a training job would mask it.
"""

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, base):
    """x [L, H, D] at positions 0..L-1."""
    L, _, D = x.shape
    half = D // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def top_k_mask(p, k):
    """[T, E] bool: the k largest of each row of `p`, ties to the lower
    index, from each entry's rank (no sort)."""
    E = p.shape[-1]
    idx = jnp.arange(E)
    ahead = (p[:, None, :] > p[:, :, None]) | (
        (p[:, None, :] == p[:, :, None]) & (idx[None, None, :]
                                            < idx[None, :, None]))
    return jnp.sum(ahead, axis=-1) < k


def routed_ffn(u, p_moe, k, renormalize=False):
    """(y [T, D], chosen [T, E] bool, load-balancing loss, z-loss) of one
    layer's routed feed-forward on `u` [T, D]."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    T = u.shape[0]
    logits = u @ f32(p_moe["router"])
    E = logits.shape[-1]
    p = jax.nn.softmax(logits, axis=-1)
    chosen = top_k_mask(p, k)
    w = jnp.where(chosen, p, 0.0)
    if renormalize and k > 1:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    gate = jnp.einsum("td,edf->tef", u, f32(p_moe["w_gate"]))
    up = jnp.einsum("td,edf->tef", u, f32(p_moe["w_up"]))
    h = jax.nn.silu(gate) * up * w[:, :, None]
    y = jnp.einsum("tef,efd->td", h, f32(p_moe["w_down"]))
    frac = jnp.sum(chosen, axis=0) / T
    balance = E * jnp.sum(frac * jnp.mean(p, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, chosen, balance, z


def hidden_and_loss(params, tokens, num_layers, rope_base, eps=1e-5,
                    top_k=8, renormalize=False, qk_norm=True,
                    balance_weight=0.01, z_weight=0.001):
    """(final normed hidden states [L, hidden], loss, parts) of ONE
    sequence `tokens` [L], everything in float32. `parts`: the
    cross-entropy, the mean load-balancing loss and the mean z-loss that
    make up the loss, `nll` [L], each position's term of the
    cross-entropy, and `chosen` [layers, L, E] bool, each layer's
    routing."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    L = tokens.shape[0]
    x = f32(params["embed"]["embedding"])[tokens]
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    chosen, balance, z = [], [], []
    for i in range(num_layers):
        p = params["block_%d" % i]
        h = _rms(x, f32(p["norm1"]["scale"]), eps)
        q = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["query"]["kernel"]))
        k = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["key"]["kernel"]))
        v = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["value"]["kernel"]))
        if qk_norm:
            q = _rms(q.reshape(L, -1), f32(p["attn"]["q_norm"]["scale"]),
                     eps).reshape(q.shape)
            k = _rms(k.reshape(L, -1), f32(p["attn"]["k_norm"]["scale"]),
                     eps).reshape(k.shape)
        q, k = _rotary(q, rope_base), _rotary(k, rope_base)
        s = jnp.einsum("qhk,thk->hqt", q, k) * (q.shape[-1] ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("qhk,hkd->qd", o, f32(p["attn"]["out"]["kernel"]))
        u = _rms(x, f32(p["norm2"]["scale"]), eps)
        y, c, b, zz = routed_ffn(u, p["moe_mlp"], top_k, renormalize)
        x = x + y
        chosen.append(c)
        balance.append(b)
        z.append(zz)
    hidden = _rms(x, f32(params["norm_f"]["scale"]), eps)
    logits = hidden @ f32(params["lm_head"]["kernel"])
    targets = jnp.roll(tokens, -1)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    parts = {"cross_entropy": jnp.mean(nll), "nll": nll,
             "load_balance": sum(balance) / num_layers,
             "router_z": sum(z) / num_layers,
             "chosen": jnp.stack(chosen)}
    loss = (parts["cross_entropy"] + balance_weight * parts["load_balance"]
            + z_weight * parts["router_z"])
    return hidden, loss, parts
