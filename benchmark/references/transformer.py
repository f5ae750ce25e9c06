"""Plain reference for the decoder-only LM that `models.Transformer` builds.

Straightforward `jax.numpy` in float32 on the program's own parameter tree:
no kernel, no chunking, no bf16, written from the layer equations and not
from the program's code. Call it under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bf16 passes.

The equations (the program's architecture; where it departs from GPT-NeoX
is listed in the configuration files that use it):

    x_0   = E[tokens]
    a_l   = x_l + Attn_l(rms(x_l) * g1_l)
    x_l+1 = a_l + W2_l silu(W1_l (rms(a_l) * g2_l))
    h     = rms(x_N) * g_f
    loss  = mean_t ( logsumexp(h_t W_head) - (h_t W_head)[tokens[t+1 mod L]] )

with rms(x) = x / sqrt(mean(x^2) + eps), causal softmax attention over
heads of `head_dim` with scale head_dim^-1/2, and rotary embedding over the
whole head (rotate-half pairing: dimension i with i + head_dim/2).
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


def hidden_and_loss(params, tokens, num_layers, rope_base, eps=1e-6):
    """(final normed hidden states [L, hidden], mean next-token loss) of
    ONE sequence `tokens` [L], everything in float32."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    L = tokens.shape[0]
    x = f32(params["embed"]["embedding"])[tokens]
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    for i in range(num_layers):
        p = params["block_%d" % i]
        h = _rms(x, f32(p["norm1"]["scale"]), eps)
        q = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["query"]["kernel"]))
        k = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["key"]["kernel"]))
        v = jnp.einsum("ld,dhk->lhk", h, f32(p["attn"]["value"]["kernel"]))
        q, k = _rotary(q, rope_base), _rotary(k, rope_base)
        s = jnp.einsum("qhk,thk->hqt", q, k) * (q.shape[-1] ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("qhk,hkd->qd", o, f32(p["attn"]["out"]["kernel"]))
        h = _rms(x, f32(p["norm2"]["scale"]), eps)
        h = jax.nn.silu(h @ f32(p["mlp_in"]["kernel"]))
        x = x + h @ f32(p["mlp_out"]["kernel"])
    hidden = _rms(x, f32(params["norm_f"]["scale"]), eps)
    logits = hidden @ f32(params["lm_head"]["kernel"])
    targets = jnp.roll(tokens, -1)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    return hidden, jnp.mean(nll)
