"""Plain reference for a training step of Kimi-Linear-48B-A3B (Moonshot;
`model_type` `kimi_linear`; Kimi Linear, arXiv:2510.26692), on the parameter
tree that `models.Transformer` builds for it: Kimi Delta Attention (a gated
delta rule whose decay is a vector a head and token) three layers in four,
latent attention WITHOUT position in the fourth (`mla_use_nope`), one
leading dense layer, then sigmoid-scored experts beside one shared expert.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: the KDA recurrence TOKEN BY TOKEN (`lax.scan`
over t; the backward keeps a state every `SCAN_BLOCK` tokens and runs the
block again), plain softmax attention under a materialised causal mask a
block of query rows at a time, every held expert computed densely for every
position and weighted by the routing weights; no kernel, no chunk, no solve,
no sort, no grouped matmul, no chunked loss, no bf16. Call it under
`jax.default_matmul_precision("highest")`.

    x      = E[tokens]                                    [L, C]
    a      = x + mixer(rms(x) g1);  x' = a + ffn(rms(a) g2)        eps 1e-5

KDA mixer on h [L, C] (H heads of D = 128; r = 128 the low ranks):
    [q | k | v | f | z | b] = W_in h        widths HD | HD | HD | r | r | H
    q, k, v = silu(conv4(.))    causal depthwise, 4 taps, no bias: tap j
                                reads the token 3 - j behind, zeros before 0
    q_t = l2(q_t) D^-1/2,  k_t = l2(k_t)    per head; l2(x) = x /
                                            sqrt(sum x^2 + 1e-6)
    g_t = -exp(A_log_h) softplus(W_f_up f_t + dt_bias)   in R^{H x D}
    beta_t = sigmoid(b_t)                                per head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(decay g_t)) S_{t-1}
          + beta_t k_t v_t^T                S in R^{D x D}, S_0 = 0
          (`decay` 1: the model; 0: the plain delta rule, ANOTHER model)
    o_t = S_t^T q_t
    y_t = W_o vec(rms_D(o_t) g_o (.) sigmoid(W_g_up z_t))   g_o [D], one
                                                            for all heads
Latent attention, no position (layers 4, 8):
    [q_1 | q_2]_h = W_q h                   per head, 128 | 64
    [c_kv | k_2]  = W_kva h;  c_kv = rms(c_kv) g_kv         512 | 64
    [k_1 | v]_h   = W_kvb c_kv              per head, 128 | 128
    s_ij = (q_1i . k_1j + q_2i . k_2j) 192^-1/2, j <= i; k_2 ONE key a
           position for all heads, NOTHING rotated
    y    = W_o vec(softmax_j(s) v)
Feed-forward: layer 0 W_down (silu(W_gate u) * W_up u), 9216 wide; the
others `references/kanana.py::routed_ffn` at this model's numbers (sigmoid
scores over all published experts in f32, top-8 on score + bias, the chosen
renormalised, x route_scale; the HELD experts' results alone added, and one
shared expert).
    hid    = rms(x_last) g_f
    nll_l  = logsumexp(hid_l W_head) - (hid_l W_head)[tokens_{l+1 mod L}]
             (DEPARTURE: the sequence closed on itself)
    loss   = mean_l nll_l
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references.kanana import _rms, attention, routed_ffn
from benchmark.references.xing import gated

SCAN_BLOCK = 64  # tokens of the sequential scan between two kept states


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def kda_recurrence(q, k, v, g, beta, block=SCAN_BLOCK):
    """o_t = S_t^T q_t of S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T, token by token: q, k, g [L, H, D], v
    [L, H, Dv], beta [L, H] -> (o [L, H, Dv], the final S [H, D, Dv], the
    largest |S_t| met at a multiple of the block, the end included; the
    block is the largest divisor of L that divides `block`)."""
    L, H, D = k.shape

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[:, :, None] * S
        S = S + (b_t[:, None] * k_t)[:, :, None] * (
            v_t - jnp.einsum("hd,hdv->hv", k_t, S))[:, None, :]
        return S, jnp.einsum("hd,hdv->hv", q_t, S)

    @jax.checkpoint
    def run(S, inp):
        S, o = lax.scan(step, S, inp)
        return S, (o, jnp.max(jnp.abs(S)))

    block = math.gcd(block, L)
    cut = lambda t: t.reshape((L // block, block) + t.shape[1:])  # noqa: E731
    final, (o, tops) = lax.scan(
        run, jnp.zeros((H, D, v.shape[-1]), jnp.float32),
        tuple(cut(_f32(t)) for t in (q, k, v, g, beta)))
    return o.reshape(L, H, -1), final, jnp.max(tops)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_inputs(h, p, arch, decay=1.0):
    """(q, k, v, g, beta, z) of the mixer's recurrence on the normed h
    [L, C] under the block's `attn` parameters: q, k, v, g [L, H, D], beta
    [L, H], z [L, r] (the output gate's low rank)."""
    H, D = arch["kda_heads"], arch["kda_head_dim"]
    L, inner = h.shape[0], H * D
    taps = p["conv_kernel"].shape[0]
    proj = h @ _f32(p["in_proj"]["kernel"])
    r = (proj.shape[-1] - 3 * inner - H) // 2
    qkv, f, z, b = (proj[:, :3 * inner], proj[:, 3 * inner:3 * inner + r],
                    proj[:, 3 * inner + r:3 * inner + 2 * r],
                    proj[:, 3 * inner + 2 * r:])
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(_f32(p["conv_kernel"])[j] * padded[j:j + L]
                          for j in range(taps)))
    q, k, v = (qkv[:, i * inner:(i + 1) * inner].reshape(L, H, D)
               for i in range(3))
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * jax.nn.softplus(
        (f @ _f32(p["f_up"]["kernel"])
         + _f32(p["dt_bias"])).reshape(L, H, D))
    return (_l2(q) * D ** -0.5, _l2(k), v, decay * g, jax.nn.sigmoid(b), z)


def kda_mixer(h, p, arch, decay=1.0):
    """The KDA mixer on the normed h [L, C] -> ([L, C], max |state|)."""
    q, k, v, g, beta, z = kda_inputs(h, p, arch, decay)
    L, H, D = q.shape
    o, _, top = kda_recurrence(q, k, v, g, beta,
                               arch.get("kda_chunk", SCAN_BLOCK))
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + arch["eps"]) * _f32(p["norm"])
    o = o * jax.nn.sigmoid((z @ _f32(p["g_up"]["kernel"])).reshape(L, H, D))
    return o.reshape(L, H * D) @ _f32(p["out_proj"]["kernel"]), top


def latent_attention(h, p, arch):
    """W_o vec(attention) of h [L, C] under the block's `attn` parameters,
    no slice rotated."""
    nope, rope = arch["nope"], arch["rope"]
    q = jnp.einsum("lc,chd->lhd", h, _f32(p["q"]["kernel"]))
    kv = h @ _f32(p["kv_a"]["kernel"])
    rank = kv.shape[-1] - rope
    c_kv = _rms(kv[:, :rank], p["kv_norm"]["scale"], arch["eps"])
    kv_h = jnp.einsum("lr,rhd->lhd", c_kv, _f32(p["kv_b"]["kernel"]))
    o = attention(q[..., :nope], q[..., nope:], kv_h[..., :nope],
                  kv[:, rank:], kv_h[..., nope:])
    return jnp.einsum("lhv,hvc->lc", o, _f32(p["out"]["kernel"]))


def forward(params, tokens, arch, follow=None, shared=1.0, decay=1.0):
    """One sequence `tokens` [L]: a dict of ``states`` [layers, L, C] (every
    block's output), ``mixer`` [layers, L, C] (every block's first branch,
    the output projection's result before the residual add), ``chosen``
    [routed layers, L, E] bool (the reference's own top-k), ``margin``
    [routed layers, L] (`kanana.routed_ffn`; all 0 without `follow` [routed
    layers, L, E] bool, a system's chosen sets to compute with),
    ``held_rows`` [routed layers], ``kda_state_max`` (the largest |S| any
    KDA layer met at a block's end), ``nll`` [L] and ``loss``, their mean.
    `arch`: kinds (a layer's "kda" | "full"), first_k_dense, eps,
    kda_heads, kda_head_dim, kda_chunk (the tokens between two readings of
    |S|: a system's chunk), nope, rope, top_k, norm_topk_prob, route_scale,
    held (first, count). `shared` other than 1 (the shared expert weighed
    by it) and `decay` other than 1 (0: the plain delta rule) compute
    ANOTHER model, which a comparison must refuse."""
    eps = arch["eps"]
    first, count = arch["held"]
    x = _f32(params["embed"]["embedding"])[tokens]

    def layer(x, p, given, kind, routed):
        h = _rms(x, p["norm1"]["scale"], eps)
        if kind == "kda":
            branch, top = kda_mixer(h, p["attn"], arch, decay)
        else:
            branch, top = latent_attention(h, p["attn"], arch), 0.0
        x = x + branch
        u = _rms(x, p["norm2"]["scale"], eps)
        if not routed:
            return x + gated(u, p["mlp_gate"]["kernel"],
                             p["mlp_up"]["kernel"],
                             p["mlp_out"]["kernel"]), branch, top
        y, own, margin = routed_ffn(u, p["moe_mlp"], arch, given, shared)
        rows = jnp.sum((own if given is None else given)[
            :, first:first + count])
        return x + y, branch, top, own, margin, rows

    layer = jax.checkpoint(layer, static_argnums=(3, 4))
    states, mixer, tops, routing = [], [], [], []
    for i, kind in enumerate(arch["kinds"]):
        routed = i >= arch["first_k_dense"]
        given = None if follow is None or not routed \
            else follow[i - arch["first_k_dense"]]
        x, branch, top, *rest = layer(x, params["block_%d" % i], given, kind,
                                      routed)
        states.append(x)
        mixer.append(branch)
        tops.append(top)
        if routed:
            routing.append(rest)
    chosen, margins, rows = (jnp.stack(t) for t in zip(*routing))
    hid = _rms(x, params["norm_f"]["scale"], eps)
    logits = hid @ _f32(params["lm_head"]["kernel"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(tokens, -1)[:, None], axis=-1)[:, 0]
    return {"states": jnp.stack(states), "mixer": jnp.stack(mixer),
            "chosen": chosen, "margin": margins, "held_rows": rows,
            "kda_state_max": jnp.max(jnp.stack([_f32(t) for t in tops])),
            "nll": nll, "loss": jnp.mean(nll)}


def gradient(params, tokens, arch, follow=None):
    """The loss's gradient by every parameter, of one sequence: the first
    step's gradient as the reference has it (`follow`: as `forward`)."""
    return jax.grad(lambda p: forward(p, tokens, arch, follow)["loss"])(
        jax.tree_util.tree_map(_f32, params))
