"""Plain reference for Xing4.0-29B-A4B (`model_type` `xing4_0`), on the
parameter tree that `models.Transformer` builds for it: DeepSeek-V2's
multi-head latent attention (arXiv:2405.04434 §2.1) with YaRN frequencies,
DeepSeek-V3's sigmoid-routed experts beside a shared one and its
multi-token prediction module (arXiv:2412.19437 §2.1.2, §2.2), and
manifold-constrained hyper-connections around every branch (mHC,
arXiv:2512.24880, over Zhu et al., arXiv:2409.19606).

Straightforward `jax.numpy` in float32, written from the layer equations
and not from the program's code: no kernel, no sort, no grouped matmul, no
chunked loss, no bf16; every held expert is computed densely for every
token and masked by the routing weights. Call it under
`jax.default_matmul_precision("highest")`. So that it fits beside the
step's state at the published widths, attention runs a head at a time
(`lax.map`); nothing else is blocked.

C the width, n the streams, H heads, eps the norms' epsilon; a token's
state is X in R^{n x C}; rms(x) = x / sqrt(mean(x^2) + eps).

Hyper-connection around a branch F (own phi [nC, 2n + n^2], b, a):

    x~     = rms(vec(X))                        (stream-major, no scale)
    pre    = a_0 (x~ phi[:, :n])   + b[:n]
    post   = a_1 (x~ phi[:, n:2n]) + b[n:2n]
    res    = a_2 (x~ phi[:, 2n:])  + b[2n:]     as [n, n]
    H_pre  = sigmoid(pre);  H_post = 2 sigmoid(post)
    M_0    = exp(clip(res, lo, hi));  M_{t+1} = cols(rows(M_t)),
             rows(M) = M / (M 1 + hc_eps),  cols(M) = M / (1^T M + hc_eps)
    H_res  = M_iters
    X'     = H_res X + H_post^T F(H_pre X)

Latent attention on h = rms(.) * g1:

    c_q = rms(W_qa h) * g_q;   [q_nope | q_rope] = W_qb c_q      per head
    [c_kv | k_rope] = W_kva h; c_kv = rms(c_kv) * g_kv
    [k_nope | v] = W_kvb c_kv                                    per head
    s_ij = scale (q_nope_i . k_nope_j + rot(q_rope_i) . rot(k_rope_j)), j <= i
    out = W_o vec(softmax(s) v)
    scale = (nope + rope)^-1/2 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1

with k_rope ONE key a token for all heads, rotate-half pairing (i, i +
rope/2) and YaRN's frequencies (`yarn_inv_freq`).

Feed-forward on u = rms(.) * g2: the first `first_k_dense` layers
`W_down(silu(W_gate u) * W_up u)`; the others

    s = sigmoid(W_r u)          chosen = the k largest of s + bias
    g_e = route_scale * s_e / (sum over the chosen of s + 1e-20)
    y = sum over chosen e in [first, first + count) of g_e E_e(u) + E_shared(u)

(the router over ALL experts; only the held ones' results are added).

The model: X_0 the token's embedding in every stream; blocks; `last` = the
sum of the streams; hidden = rms(last) * g_f. The prediction module:
h' = W_eh [rms(last) * g_h | rms(Emb(t_{i+1})) * g_e], one more routed block
on h' in every stream, hidden_mtp = rms(sum of its streams) * g_m. The
sequence closes on itself (targets roll(-1), roll(-2)):

    loss = mean_i nll(Head hidden_i, t_{i+1})
         + lam * mean_i nll(Head hidden_mtp_i, t_{i+2})
"""

import math

import jax
import jax.numpy as jnp


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _rms(x, eps, scale=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * _f32(scale)


def yarn_inv_freq(dim, base, factor, beta_fast, beta_slow, original_len):
    """[dim / 2] rotary frequencies under YaRN, as python floats."""
    def index_of(turns):  # the frequency index that turns so often
        return (dim * math.log(original_len / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(index_of(beta_fast)), 0)
    hi = min(math.ceil(index_of(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    freqs = []
    for i in range(dim // 2):
        f = base ** (-i / (dim // 2))
        g = 1.0 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        freqs.append(f / factor * (1.0 - g) + f * g)
    return freqs


def _rotate(x, inv_freq, mscale):
    """x [L, ..., D] at positions 0..L-1, pairs (i, i + D/2)."""
    L, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((L,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def sinkhorn(m, iters, eps):
    """`iters` (a number, or a traced one) times rows then columns."""
    def rows_then_columns(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, rows_then_columns, m)


def hyper_connection_maps(X, p, arch, iters=None):
    """(H_pre [L, n], H_post [L, n], H_res [L, n, n]) from X [L, n, C]."""
    L, n, C = X.shape
    xt = _rms(X.reshape(L, n * C), arch["eps"])
    raw = xt @ _f32(p["phi"])
    a, b = _f32(p["alpha"]), _f32(p["bias"])
    pre = a[0] * raw[:, :n] + b[:n]
    post = a[1] * raw[:, n:2 * n] + b[n:2 * n]
    res = (a[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(L, n, n)
    lo, hi = arch["hc_clamp"]
    m = sinkhorn(jnp.exp(jnp.clip(res, lo, hi)),
                 arch["hc_iters"] if iters is None else iters, arch["hc_eps"])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


def hyper_connected(X, p, arch, branch, iters=None):
    """X' = H_res X + H_post^T branch(H_pre X), X [L, n, C]; also H_res."""
    h_pre, h_post, h_res = hyper_connection_maps(X, p, arch, iters)
    y = branch(jnp.einsum("ln,lnc->lc", h_pre, X))
    return (jnp.einsum("lmn,lnc->lmc", h_res, X)
            + h_post[:, :, None] * y[:, None, :]), h_res


def latent_attention(h, p, arch):
    L = h.shape[0]
    nope, rope, eps = arch["nope"], arch["rope"], arch["eps"]
    c_q = _rms(h @ _f32(p["q_a"]["kernel"]), eps, p["q_norm"]["scale"])
    q = jnp.einsum("lr,rhd->lhd", c_q, _f32(p["q_b"]["kernel"]))
    kv = h @ _f32(p["kv_a"]["kernel"])
    rank = kv.shape[-1] - rope
    c_kv = _rms(kv[:, :rank], eps, p["kv_norm"]["scale"])
    k_rope = kv[:, rank:]
    kv = jnp.einsum("lr,rhd->lhd", c_kv, _f32(p["kv_b"]["kernel"]))
    yarn = arch["yarn"]
    inv_freq = yarn_inv_freq(rope, arch["rope_base"], yarn["factor"],
                             yarn["beta_fast"], yarn["beta_slow"],
                             yarn["original_max_position_embeddings"])

    def get_mscale(scale):
        return 0.1 * scale * math.log(yarn["factor"]) + 1.0

    m_all = get_mscale(yarn["mscale_all_dim"])
    on_tables = get_mscale(yarn["mscale"]) / m_all
    scale = (nope + rope) ** -0.5 * m_all * m_all
    q_rope = _rotate(q[..., nope:], inv_freq, on_tables)
    k_rope = _rotate(k_rope, inv_freq, on_tables)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]

    def one_head(args):
        q_n, q_r, k_n, v = args  # [L, nope], [L, rope], [L, nope], [L, vd]
        s = (q_n @ k_n.T + q_r @ k_rope.T) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v

    by_head = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(q[..., :nope]), by_head(q_rope),
                               by_head(kv[..., :nope]),
                               by_head(kv[..., nope:])))  # [H, L, vd]
    return jnp.einsum("hlv,hvc->lc", o, _f32(p["out"]["kernel"]))


def top_k_mask(score, k):
    """[T, E] bool: the k largest of each row, ties to the lower index,
    from each entry's rank (no sort)."""
    E = score.shape[-1]
    idx = jnp.arange(E)
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    return jnp.sum(ahead, axis=-1) < k


def gated(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def routed_ffn(u, p, arch, shared=1.0):
    """(y [T, C], chosen [T, E] bool) of a routed layer as HELD: the
    router over all E experts, the held experts' results alone added, and
    `shared` (1: the model) times the shared expert's."""
    s = jax.nn.sigmoid(u @ _f32(p["router"]))
    chosen = top_k_mask(s + _f32(p["select_bias"]), arch["top_k"])
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
    return y, chosen


def block(X, p, arch, routed, iters=None, shared=1.0):
    """(X', chosen or None, the largest deviation of the block's two H_res
    from doubly stochastic)."""
    eps = arch["eps"]
    X, res_a = hyper_connected(
        X, p["hc_attn"], arch, lambda h: latent_attention(
            _rms(h, eps, p["norm1"]["scale"]), p["attn"], arch), iters)
    picked = []

    def feed_forward(h):
        u = _rms(h, eps, p["norm2"]["scale"])
        if not routed:
            return gated(u, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                         p["mlp_out"]["kernel"])
        y, chosen = routed_ffn(u, p["moe_mlp"], arch, shared)
        picked.append(chosen)
        return y

    X, res_m = hyper_connected(X, p["hc_mlp"], arch, feed_forward, iters)
    off = lambda m: jnp.maximum(  # noqa: E731
        jnp.max(jnp.abs(jnp.sum(m, axis=-1) - 1.0)),
        jnp.max(jnp.abs(jnp.sum(m, axis=-2) - 1.0)))
    return (X, picked[0] if routed else None,
            jnp.maximum(off(res_a), off(res_m)))


def _nll(hidden, head, targets):
    logits = hidden @ _f32(head)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]


def forward(params, tokens, arch, lam, iters=None, shared=1.0):
    """ONE sequence `tokens` [L], everything float32. `arch`: num_layers,
    first_k_dense, n (streams), eps, hc_iters, hc_eps, hc_clamp, nope, rope,
    rope_base, yarn (the published `rope_scaling`), top_k, norm_topk_prob,
    route_scale, held (first, count). `iters` (Sinkhorn iterations; may be
    traced) and `shared` (the shared expert's weight) other than the
    defaults make the reference of ANOTHER model, which the comparison has
    to refuse; so does "ce" alone, the loss without the module's term.

    Returns {"states" [num_layers + 1, L, C]: every block's summed state,
    the module's block last; "hidden", "hidden_mtp" [L, C]; "chosen"
    [routed layers + 1, L, E] bool; "nll" [2, L]; "loss"; "ce", "ce_mtp";
    "hc_off": the largest deviation of any H_res from doubly stochastic}."""
    n, eps = arch["n"], arch["eps"]
    emb = _f32(params["embed"]["embedding"])
    fill = lambda h: jnp.broadcast_to(  # noqa: E731
        h[:, None, :], (h.shape[0], n, h.shape[1]))
    X = fill(emb[tokens])
    states, chosen, off = [], [], []
    for i in range(arch["num_layers"]):
        X, c, o = block(X, params["block_%d" % i], arch,
                        i >= arch["first_k_dense"], iters, shared)
        states.append(jnp.sum(X, axis=1))
        off.append(o)
        if c is not None:
            chosen.append(c)
    last = states[-1]
    hidden = _rms(last, eps, params["norm_f"]["scale"])
    e_next = emb[jnp.roll(tokens, -1)]
    h = jnp.concatenate(
        [_rms(last, eps, params["mtp_norm_h"]["scale"]),
         _rms(e_next, eps, params["mtp_norm_e"]["scale"])],
        axis=-1) @ _f32(params["mtp_proj"]["kernel"])
    X, c, o = block(fill(h), params["mtp_block"], arch, True, iters, shared)
    states.append(jnp.sum(X, axis=1))
    chosen.append(c)
    off.append(o)
    hidden_mtp = _rms(states[-1], eps, params["mtp_norm_f"]["scale"])
    head = params["lm_head"]["kernel"]
    nll = jnp.stack([_nll(hidden, head, jnp.roll(tokens, -1)),
                     _nll(hidden_mtp, head, jnp.roll(tokens, -2))])
    ce, ce_mtp = jnp.mean(nll[0]), jnp.mean(nll[1])
    return {"states": jnp.stack(states), "hidden": hidden,
            "hidden_mtp": hidden_mtp, "chosen": jnp.stack(chosen),
            "nll": nll, "ce": ce, "ce_mtp": ce_mtp,
            "loss": ce + lam * ce_mtp,
            "hc_off": jnp.max(jnp.stack(off))}
