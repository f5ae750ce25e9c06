"""Plain reference of a Nemotron-H stack (NVIDIA-Nemotron-3-Super-120B-A12B:
`hybrid_override_pattern` over Mamba-2 layers `M`, attention layers `*` and
LatentMoE layers `E`, each layer one mixer behind one RMSNorm): forward pass
and loss in straightforward `jax.numpy`, float32, no kernels, one sequence at
a time; gradients by `jax.grad` of `loss`. Callers set
`jax.default_matmul_precision("highest")`.

Independent of the program's forms: the recurrence is THE SEQUENTIAL SCAN
over the tokens (`lax.scan` of `S_t = a_t S_{t-1} + dt_t x_t (x) B_t`; the
program computes it chunked), attention is the masked einsum a head at a
time, the routed layer a loop over the held experts under a dense [T, E]
weight matrix (no sort, no grouped matmul). Computed in blocks so that the
published widths fit one chip: the scan's backward keeps one state a block
of `SCAN_BLOCK` tokens and recomputes inside it, attention runs a head at a
time, and `remat=True` keeps a layer's input alone for the backward pass.

The parameter tree is `models.Transformer`'s under `layer_types`
(`block_<i>/norm`, then `ssm`, `attn` or `moe_mlp`).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

SCAN_BLOCK = 64  # tokens of the sequential scan between two kept states


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def relu2(h):
    return jnp.square(jnp.maximum(h, 0.0))


def sequential_scan(x, dt, a, b, c, block=SCAN_BLOCK):
    """y_t = S_t C_t of S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t, token
    by token: x [L, H, P], dt [L, H], a [H], b, c [L, G, N] -> (y [L, H, P],
    the largest |S_t| met at a multiple of the block, the end included;
    the block is the largest divisor of L that divides `block`)."""
    L, H, P = x.shape
    G, N = b.shape[1:]
    K = H // G

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        bh, ch = jnp.repeat(b_t, K, axis=0), jnp.repeat(c_t, K, axis=0)
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        return S, jnp.sum(S * ch[:, None, :], axis=-1)

    @jax.checkpoint
    def run(S, inp):
        S, y = lax.scan(step, S, inp)
        return S, (y, jnp.max(jnp.abs(S)))

    block = math.gcd(block, L)
    cut = lambda t: t.reshape((L // block, block) + t.shape[1:])  # noqa: E731
    _, (y, tops) = lax.scan(run, jnp.zeros((H, P, N), jnp.float32),
                            (cut(x), cut(dt), cut(b), cut(c)))
    return y.reshape(L, H, P), jnp.max(tops)


def mamba2(p, u, arch):
    """The Mamba-2 mixer on the normed u [L, D] -> ([L, D], max |state|)."""
    H, P, G, N = (arch["ssm_heads"], arch["ssm_head_dim"],
                  arch["ssm_groups"], arch["ssm_state"])
    inner, L = H * P, u.shape[0]
    conv_dim = inner + 2 * G * N
    taps = p["conv_kernel"].shape[0]
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                  zxbcdt[:, inner + conv_dim:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][j] * padded[j:j + L] for j in range(taps)))
    x = xbc[:, :inner].reshape(L, H, P)
    b = xbc[:, inner:inner + G * N].reshape(L, G, N)
    c = xbc[:, inner + G * N:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y, top = sequential_scan(x, dt, -jnp.exp(p["A_log"]), b, c)
    y = y + p["D"][:, None] * x
    y = (y.reshape(L, inner) * jax.nn.silu(z)).reshape(L, G, inner // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + arch["eps"])
    return (y.reshape(L, inner) * p["norm"]) @ p["out_proj"]["kernel"], top


def attention(p, u):
    """Causal grouped-query attention WITHOUT rotary on the normed u [L, D]:
    scale head_dim^-1/2, query head h on kv head h // (H / G)."""
    wq, wk, wv, wo = (p[n]["kernel"] for n in ("query", "key", "value", "out"))
    H, G, hd = wq.shape[1], wk.shape[1], wq.shape[2]
    L = u.shape[0]
    q = jnp.einsum("ld,dhk->hlk", u, wq)
    k = jnp.repeat(jnp.einsum("ld,dgk->glk", u, wk), H // G, axis=0)
    v = jnp.repeat(jnp.einsum("ld,dgk->glk", u, wv), H // G, axis=0)
    mask = jnp.tril(jnp.ones((L, L), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = lax.map(head, (q, k, v))  # [H, L, hd]
    return jnp.einsum("hlk,hkd->ld", o, wo)


def latent_moe(p, u, arch, shared=1.0):
    """LatentMoE on the normed u [L, D] -> (out [L, D], chosen [L, E] bool,
    the rows on the held experts): sigmoid scores over all E, the
    `top_k` of largest score + bias, weights scale * s / (sum s + 1e-20)
    (the sum over all the chosen, held or not), the HELD experts'
    `W_down relu(W_up v)^2` in the latent v = W_1 u, back through W_2, beside
    the shared expert on u itself (`shared` = 0 leaves it out)."""
    first, count = arch["held"]
    s = jax.nn.sigmoid(u @ p["router"])
    E = s.shape[-1]
    _, idx = lax.top_k(s + p["select_bias"], arch["top_k"])
    chosen = jnp.any(jax.nn.one_hot(idx, E, dtype=jnp.bool_), axis=-2)
    w = jnp.where(chosen, s, 0.0)
    if arch["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * arch["route_scale"]
    v = u @ p["latent_in"]["kernel"]
    r = jnp.zeros_like(v)
    for e in range(count):
        r = r + w[:, first + e, None] * (
            relu2(v @ p["w_in"][e]) @ p["w_out"][e])
    out = r @ p["latent_out"]["kernel"] + shared * (
        relu2(u @ p["shared_up"]["kernel"]) @ p["shared_down"]["kernel"])
    return out, chosen, jnp.sum(chosen[:, first:first + count])


def layer(p, kind, h, arch, shared=1.0):
    """One layer, h + mixer(rms(h)) -> (h, counters of the kind)."""
    u = rms(h, p["norm"]["scale"], arch["eps"])
    if kind == "ssm":
        y, top = mamba2(p["ssm"], u, arch)
        return h + y, {"state_max": top}
    if kind == "attn":
        return h + attention(p["attn"], u), {}
    y, chosen, rows = latent_moe(p["moe_mlp"], u, arch, shared)
    return h + y, {"chosen": chosen, "held_rows": rows}


def forward(params, seq, arch, shared=1.0, remat=False):
    """One sequence `seq` [L] of ids. Returns {"states": every layer's
    output [layers, L, D], "nll": each token's cross-entropy of the NEXT
    token [L] (the sequence closed on itself), "loss": their mean, "chosen"
    [routed layers, L, E] bool, "held_rows" [routed layers], "state_max":
    the largest |state| of any scan}. `arch`: "pattern" (a kind a layer:
    "ssm" | "attn" | "moe"), "eps", the ssm_* sizes, "top_k",
    "norm_topk_prob", "route_scale", "held" (first, count)."""
    h = params["embed"]["embedding"][seq]
    states, chosen, rows, tops = [], [], [], []
    for i, kind in enumerate(arch["pattern"]):
        run = lambda p, h, kind=kind: layer(p, kind, h, arch,  # noqa: E731
                                            shared)
        h, seen = (jax.checkpoint(run) if remat else run)(
            params["block_%d" % i], h)
        states.append(h)
        if kind == "moe":
            chosen.append(seen["chosen"])
            rows.append(seen["held_rows"])
        if kind == "ssm":
            tops.append(seen["state_max"])
    logits = rms(h, params["norm_f"]["scale"], arch["eps"]) \
        @ params["lm_head"]["kernel"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               jnp.roll(seq, -1)[:, None], axis=-1)[:, 0]
    out = {"states": jnp.stack(states), "nll": nll, "loss": jnp.mean(nll)}
    if chosen:
        out.update(chosen=jnp.stack(chosen), held_rows=jnp.stack(rows))
    if tops:
        out["state_max"] = jnp.max(jnp.stack(tops))
    return out


def loss(params, seq, arch, remat=True):
    return forward(params, seq, arch, remat=remat)["loss"]
