"""Plain reference for a training step of Kanana-2-30B-A3B (Kakao;
`model_type` `deepseek_v3`), on the parameter tree that `models.Transformer`
builds for it: DeepSeek-V2's multi-head latent attention (arXiv:2405.04434
s2.1) with the queries projected STRAIGHT from the state (`q_lora_rank`
null: no W_qa, no norm on the queries, as DeepSeek-V2-Lite), one leading
dense layer, then DeepSeek-V3's sigmoid-scored experts beside a shared pair
(arXiv:2412.19437 s2.1.2).

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: einsums under a materialised causal mask (a
block of query rows at a time, so that [H, 8192, 8192] never exists), every
held expert computed densely for every position and weighted by the routing
weights; no kernel, no sort, no grouped matmul, no chunked loss, no bf16.
Call it under `jax.default_matmul_precision("highest")`.

    x      = E[tokens]                                    [L, C]
    h      = rms(x) g1
    [q_nope | q_rope]_h = W_q h                           per head, 128 | 64
    [c_kv | k_rope]     = W_kva h;  c_kv = rms(c_kv) g_kv  512 | 64
    [k_nope | v]_h      = W_kvb c_kv                      per head, 128 | 128
    rot    : rotate-half pairs (i, i + 32) of the 64 rotary columns by the
             angle p theta^(-2i/64)     (no scaling: `rope_scaling` null)
    s_ij   = (q_nope_i . k_nope_j + rot(q_rope_i) . rot(k_rope_j)) 192^-1/2,
             j <= i;  k_rope ONE key a position for all heads
    a      = x + W_o vec(softmax_j(s) v)
    u      = rms(a) g2
    layer 0:   x' = a + W_down (silu(W_gate u) * W_up u)          (6144 wide)
    the others:
      s_e  = sigmoid(u W_r)_e  over ALL published experts, f32
      S    = the k experts of largest s_e + bias_e (ties: the lower index)
      g_e  = route_scale * s_e / (sum_{e in S} s_e + 1e-20)   (norm_topk_prob)
      x'   = a + sum_{e in S, e HELD} g_e E_e(u) + E_shared(u)
             (E: the gated form above, 768 wide; the shared pair one gated
              expert 1536 wide; the experts [first, first + count) are held
              and what the others would add is left out, as in the program:
              one rank's share of the layer)
    hid    = rms(x_last) g_f
    nll_l  = logsumexp(hid_l W_head) - (hid_l W_head)[tokens_{l+1 mod L}]
             (DEPARTURE: the sequence closed on itself)
    loss   = mean_l nll_l        (`noaux_tc`: no balancing term in the loss)
"""

import jax
import jax.numpy as jnp

# ties to the lower index; W_down (silu(W_gate u) * W_up u)
from benchmark.references.xing import gated, top_k_mask

Q_ROWS = 512  # query rows of the dense mask and scores made at a time


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _rotate(x, theta):
    """x [L, ..., d] at positions 0..L-1: pairs (i, i + d/2) by the angle
    p theta^(-2i/d)."""
    L, d = x.shape[0], x.shape[-1]
    freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                       jnp.float32)
    ang = (jnp.arange(L, dtype=jnp.float32)[:, None] * freq[None, :]).reshape(
        (L,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q_nope, q_rope, k_nope, k_rope, v):
    """softmax over j <= i of (q_nope . k_nope + q_rope . k_rope) (nope +
    rope)^-1/2, times v, by blocks of `Q_ROWS` query rows: q_nope [L, H,
    nope], q_rope [L, H, rope], k_nope [L, H, nope], k_rope [L, rope] (one
    key a position), v [L, H, vd]."""
    L, H, nope = q_nope.shape
    scale = (nope + q_rope.shape[-1]) ** -0.5
    rows = min(Q_ROWS, L)
    j = jnp.arange(L)[None, :]

    @jax.checkpoint
    def some_rows(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, rows, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, rows, axis=0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        seen = j <= (start + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(some_rows, jnp.arange(0, L, rows)).reshape(
        L, H, v.shape[-1])


def latent_attention(h, p, arch):
    """W_o vec(attention) of h [L, C] under the block's `attn` parameters."""
    nope, rope = arch["nope"], arch["rope"]
    q = jnp.einsum("lc,chd->lhd", h, _f32(p["q"]["kernel"]))
    kv = h @ _f32(p["kv_a"]["kernel"])
    rank = kv.shape[-1] - rope
    c_kv = _rms(kv[:, :rank], p["kv_norm"]["scale"], arch["eps"])
    kv_h = jnp.einsum("lr,rhd->lhd", c_kv, _f32(p["kv_b"]["kernel"]))
    o = attention(q[..., :nope], _rotate(q[..., nope:], arch["rope_theta"]),
                  kv_h[..., :nope], _rotate(kv[:, rank:], arch["rope_theta"]),
                  kv_h[..., nope:])
    return jnp.einsum("lhv,hvc->lc", o, _f32(p["out"]["kernel"]))


def routed_ffn(u, p, arch, follow=None, shared=1.0):
    """(y [T, C], own [T, E] bool, margin [T]) of a routed layer as HELD on
    u [T, C]: the router over all E experts, the held experts' results alone
    added, and `shared` (1: the model) times the shared pair's. `own` is the
    reference's own top-k of score + bias. `follow` [T, E] bool: the sets a
    SYSTEM chose, computed with in place of `own` (the weights are still the
    reference's scores of them), so that a near tie the system's precision
    decided otherwise does not send the two down different paths; `margin`
    then says how near a tie each such choice was: the reference's k-th
    largest score + bias less the least one followed, over the k-th largest
    (0 where the sets agree)."""
    k = arch["top_k"]
    s = jax.nn.sigmoid(u @ _f32(p["router"]))
    T, E = s.shape
    biased = s + _f32(p["select_bias"])
    own = jax.lax.map(lambda rows: top_k_mask(rows, k),
                      biased.reshape(-1, min(512, T), E)).reshape(s.shape)
    chosen = own if follow is None else follow
    kth = jnp.min(jnp.where(own, biased, jnp.inf), axis=-1)
    margin = (kth - jnp.min(jnp.where(chosen, biased, jnp.inf), axis=-1)) / kth
    g = jnp.where(chosen, s, 0.0)
    if arch["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * arch["route_scale"]
    first, count = arch["held"]
    y = jnp.zeros_like(u)
    for e in range(count):
        y = y + g[:, first + e, None] * gated(
            u, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    y = y + shared * gated(u, p["shared_gate"]["kernel"],
                           p["shared_up"]["kernel"],
                           p["shared_down"]["kernel"])
    return y, own, margin


def forward(params, tokens, arch, follow=None, shared=1.0):
    """One sequence `tokens` [L]: a dict of ``states`` [layers, L, C] (every
    block's output), ``attn`` [layers, L, C] (every block's attention
    branch, W_o's output before the residual add), ``chosen`` [routed
    layers, L, E] bool (the reference's own top-k), ``margin`` [routed
    layers, L] (`routed_ffn`; all 0 without `follow` [routed layers, L, E]
    bool, a system's chosen sets to compute with), ``held_rows`` [routed
    layers], ``nll`` [L] (each row's cross-entropy against the next token,
    the sequence closed on itself) and ``loss``, their mean. `arch`:
    num_layers, first_k_dense, eps, nope, rope, rope_theta, top_k,
    norm_topk_prob, route_scale, held (first, count). `shared` other than 1
    computes ANOTHER model's routed layers (the shared pair weighed by it),
    which a comparison must refuse."""
    eps = arch["eps"]
    first, count = arch["held"]
    x = _f32(params["embed"]["embedding"])[tokens]

    def layer(x, p, given, routed):
        branch = latent_attention(_rms(x, p["norm1"]["scale"], eps),
                                  p["attn"], arch)
        x = x + branch
        u = _rms(x, p["norm2"]["scale"], eps)
        if not routed:
            return x + gated(u, p["mlp_gate"]["kernel"],
                             p["mlp_up"]["kernel"],
                             p["mlp_out"]["kernel"]), branch
        y, own, margin = routed_ffn(u, p["moe_mlp"], arch, given, shared)
        rows = jnp.sum((own if given is None else given)[
            :, first:first + count])
        return x + y, branch, own, margin, rows

    layer = jax.checkpoint(layer, static_argnums=(3,))
    states, attn, routing = [], [], []
    for i in range(arch["num_layers"]):
        routed = i >= arch["first_k_dense"]
        given = None if follow is None or not routed \
            else follow[i - arch["first_k_dense"]]
        x, branch, *rest = layer(x, params["block_%d" % i], given, routed)
        states.append(x)
        attn.append(branch)
        if routed:
            routing.append(rest)
    chosen, margins, rows = (jnp.stack(t) for t in zip(*routing))
    hid = _rms(x, params["norm_f"]["scale"], eps)
    logits = hid @ _f32(params["lm_head"]["kernel"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(tokens, -1)[:, None], axis=-1)[:, 0]
    return {"states": jnp.stack(states), "attn": jnp.stack(attn),
            "chosen": chosen, "margin": margins, "held_rows": rows,
            "nll": nll, "loss": jnp.mean(nll)}


def gradient(params, tokens, arch, follow=None):
    """The loss's gradient by every parameter, of one sequence: the first
    step's gradient as the reference has it (`follow`: as `forward`)."""
    return jax.grad(lambda p: forward(p, tokens, arch, follow)["loss"])(
        jax.tree_util.tree_map(_f32, params))
