"""Plain reference for a training step of Mellum2-12B-A2.5B (JetBrains;
`model_type` `mellum`: Qwen3-MoE's layer, arXiv:2505.09388, with window and
full attention layers in one stack and YaRN, Peng et al., arXiv:2309.00071,
on the full layers' rotation), on the parameter tree that
`models.Transformer` builds for it.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: a dense [L, L] boolean mask a layer kind (a
block of query rows at a time, so that 8192 rows fit), the two frequency
tables written out from their formulas, every held expert computed densely
for every position and weighted by the top-k weights; no kernel, no sort, no
grouped matmul, no chunked loss, no bf16. Call it under
`jax.default_matmul_precision("highest")`.

    x     = E[tokens]                                   [L, C]
    layer l is "window" where l mod 4 != 3, else "full" (`layer_types`)
    h     = rms(x) g1
    q     = h W_q [L, H, d],  k = h W_k,  v = h W_v [L, G, d]
    q, k  = rot_l(rms_d(q) g_q), rot_l(rms_d(k) g_k)
            (rms over the d of ONE head; g_q, g_k [d] shared by the heads:
             ASSUMED, the family's convention; the config has no key for it)
    rot_l : rotate-half pairs (i, i + d/2) by the angle p f_i, i < d/2:
            window layers  f_i = theta^(-2i/d), cos and sin as they are;
            full layers    YaRN as `transformers` computes `rope_type` yarn:
              dim(r) = d ln(orig / (2 pi r)) / (2 ln theta)
              lo = max(floor(dim(beta_fast)), 0)
              hi = min(ceil(dim(beta_slow)), d - 1)
              ramp_i = clip((i - lo) / (hi - lo), 0, 1)
              f_i <- f_i / factor * ramp_i + f_i * (1 - ramp_i)
              cos and sin BOTH times attention_factor (0.1 ln factor + 1)
    s_ij  = q_i . k_j d^-1/2 (query head h on kv head h // (H / G))
    key j is visible to query i iff j <= i, and in a window layer also
            i - j < window  (`transformers`' overlay kv > q - window)
    a     = x + softmax_j(s over the visible keys) v  W_o
    u     = rms(a) g2
    p     = softmax_E(u W_r)          (E = all published experts), f32
    S     = the k experts of largest p (ties: the lower index)
    w_e   = p_e / sum_{e in S} p_e                     (norm_topk_prob)
    x'    = a + sum_{e in S, e HELD} w_e W_down,e (silu(u W_gate,e) * u W_up,e)
            (the experts [first, first + count) are held; what the others
             would add is left out, as in the program: one rank's share)
    lb    = E * sum_e f_e P_e over the L rows, f_e = |{i: e in S_i}| / L
    hid   = rms(x_last) g_f
    nll_l = logsumexp(hid_l W_head) - (hid_l W_head)[tokens_{l+1 mod L}]
            (DEPARTURE: the sequence closed on itself, so that every row
             has a target and the loss is a mean over L)
    loss  = mean_l nll_l  +  w_lb * mean_layers lb
            (DEPARTURE: the balancing term per layer, then averaged;
             w_lb ASSUMED 0.001, Qwen3-MoE's router_aux_loss_coef)

No prediction module: the config has no key for the "MTP head" the model's
card names (DEPARTURE; the configuration file says so).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.references.sdar import top_k_mask  # ties: the lower index

Q_ROWS = 1024  # query rows of the dense mask and scores made at a time

# Other models' stacks, which a comparison must refuse (`forward(variant=)`,
# a traced int): every layer full; the full layers on the plain
# frequencies; the full layers under the window too; YaRN's frequencies
# with its factor on cos and sin left out.
AS_PUBLISHED, ALL_FULL, PLAIN_ROTATION, ALL_WINDOW, NO_FACTOR = range(5)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def plain_frequencies(d, theta):
    return [theta ** (-2.0 * i / d) for i in range(d // 2)]


def yarn_frequencies(d, theta, yarn):
    """(the d / 2 frequencies, the factor on cos and sin) of `rope_type`
    yarn, python floats. `yarn`: factor, original_max_position_embeddings,
    beta_fast, beta_slow and, where the config gives it, attention_factor
    (else 0.1 ln factor + 1)."""
    factor = yarn["factor"]
    orig = yarn["original_max_position_embeddings"]

    def dim(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(yarn["beta_fast"])), 0)
    hi = min(math.ceil(dim(yarn["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    out = []
    for i, f in enumerate(plain_frequencies(d, theta)):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out, yarn.get("attention_factor", 0.1 * math.log(factor) + 1.0)


def _rotate(x, pos, freq, factor):
    """x [R, H, d] at positions pos [R]: pairs (i, i + d/2) by pos * freq_i,
    cos and sin times `factor`."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window):
    """softmax(mask(q k^T d^-1/2)) v by blocks of `Q_ROWS` query rows: q
    [L, H, d], k and v [L, G, d], query head h on kv head h // (H / G); key
    j visible to query i iff j <= i and i - j < `window` (a traced or a
    python int; the sequence's length or more: every key before it)."""
    L, H, d = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = min(Q_ROWS, L)
    j = jnp.arange(L)[None, :]

    @jax.checkpoint
    def some_rows(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * d ** -0.5
        i = (start + jnp.arange(rows))[:, None]
        seen = (j <= i) & (i - j < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(some_rows, jnp.arange(0, L, rows))
    return out.reshape(L, H, d)


def routed_ffn(u, p_moe, k, held, follow=None):
    """(y [T, D], own [T, E] bool, load-balancing term, margin [T]) of one
    layer on u [T, D]; the router over all E, the experts `held` = (first,
    count) computed, one at a time. `own` is the reference's own top-k.
    `follow` [T, E] bool: the sets a SYSTEM chose, computed with in place of
    `own` (the weights are still the reference's probabilities of them), so
    that a near tie the system's precision decided otherwise does not send
    the two down different paths; `margin` then says how near a tie each
    such choice was: the reference's k-th largest probability less the
    least probability followed, over the k-th largest (0 where the sets
    agree; near 1 where a set was not chosen by probability at all)."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    first, count = held
    p = jax.nn.softmax(u @ f32(p_moe["router"]), axis=-1)
    T, E = p.shape
    own = jax.lax.map(lambda rows: top_k_mask(rows, k),
                      p.reshape(-1, min(512, T), E)).reshape(p.shape)
    chosen = own if follow is None else follow
    kth = jnp.min(jnp.where(own, p, jnp.inf), axis=-1)
    margin = (kth - jnp.min(jnp.where(chosen, p, jnp.inf), axis=-1)) / kth
    w = jnp.where(chosen, p, 0.0)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(u)
    for e in range(count):
        gate = u @ f32(p_moe["w_gate"][e])
        up = u @ f32(p_moe["w_up"][e])
        y = y + w[:, first + e, None] * (
            (jax.nn.silu(gate) * up) @ f32(p_moe["w_down"][e]))
    frac = jnp.sum(chosen, axis=0) / T
    return y, own, E * jnp.sum(frac * jnp.mean(p, axis=0)), margin


def forward(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """One sequence `tokens` [L]: a dict of ``states`` [layers, L, hidden]
    (every block's output), ``attn`` [layers, L, hidden] (every block's
    attention branch, W_o's output before the residual add), ``chosen``
    [layers, L, E] bool (the reference's own top-k), ``margin`` [layers, L]
    (`routed_ffn`; all 0 without `follow` [layers, L, E] bool, a system's
    chosen sets to compute with), ``held_rows`` [layers] (assignments on the
    held experts, of the sets computed with), ``nll`` [L] (each row's
    cross-entropy against the next token, the sequence closed on itself),
    ``ce`` (their mean), ``balance`` (mean over the layers) and ``loss`` =
    ce + arch["balance_weight"] * balance. `arch`: kinds (a tuple of
    "window" | "full" a layer), eps, rope_theta, window, yarn (the full
    layers' `rope_parameters`, or None), top_k, held, balance_weight.
    `variant` (a traced int) computes ANOTHER model's stack, which a
    comparison must refuse (the names above)."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    L, eps = tokens.shape[0], arch["eps"]
    pos = jnp.arange(L)
    d = params["block_0"]["attn"]["query"]["kernel"].shape[-1]
    plain = jnp.asarray(plain_frequencies(d, arch["rope_theta"]), jnp.float32)
    if arch["yarn"] is None:
        scaled, factor = plain, 1.0
    else:
        scaled, factor = yarn_frequencies(d, arch["rope_theta"], arch["yarn"])
        scaled = jnp.asarray(scaled, jnp.float32)
    everything = L  # a window that cuts nothing
    x = f32(params["embed"]["embedding"])[tokens]

    @jax.checkpoint
    def layer(x, p, given, freq, cos_sin_factor, window):
        a = p["attn"]
        h = _rms(x, f32(p["norm1"]["scale"]), eps)
        q = jnp.einsum("ld,dhk->lhk", h, f32(a["query"]["kernel"]))
        k = jnp.einsum("ld,dhk->lhk", h, f32(a["key"]["kernel"]))
        v = jnp.einsum("ld,dhk->lhk", h, f32(a["value"]["kernel"]))
        q = _rotate(_rms(q, f32(a["q_norm"]["scale"]), eps), pos, freq,
                    cos_sin_factor)
        k = _rotate(_rms(k, f32(a["k_norm"]["scale"]), eps), pos, freq,
                    cos_sin_factor)
        o = attention(q, k, v, window)
        branch = jnp.einsum("qhk,hkd->qd", o, f32(a["out"]["kernel"]))
        x = x + branch
        u = _rms(x, f32(p["norm2"]["scale"]), eps)
        y, own, balance, margin = routed_ffn(
            u, p["moe_mlp"], arch["top_k"], arch["held"], given)
        rows = jnp.sum((own if given is None else given)[
            :, arch["held"][0]:arch["held"][0] + arch["held"][1]])
        return x + y, branch, own, balance, margin, rows

    outs = []
    for i, kind in enumerate(arch["kinds"]):
        if kind == "full":
            freq = jnp.where(variant == PLAIN_ROTATION, plain, scaled)
            cos_sin = jnp.where((variant == PLAIN_ROTATION)
                                | (variant == NO_FACTOR), 1.0, factor)
            window = jnp.where(variant == ALL_WINDOW, arch["window"],
                               everything)
        else:
            freq, cos_sin = plain, 1.0
            window = jnp.where(variant == ALL_FULL, everything,
                               arch["window"])
        x, *rest = layer(x, params["block_%d" % i],
                         None if follow is None else follow[i], freq,
                         cos_sin, window)
        outs.append([x] + rest)
    states, attn, chosen, balance, margins, rows = (
        jnp.stack(t) for t in zip(*outs))
    hid = _rms(x, f32(params["norm_f"]["scale"]), eps)
    logits = hid @ f32(params["lm_head"]["kernel"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(tokens, -1)[:, None], axis=-1)[:, 0]
    ce, mean_balance = jnp.mean(nll), jnp.mean(balance)
    return {"states": states, "attn": attn, "chosen": chosen,
            "margin": margins, "held_rows": rows, "nll": nll, "ce": ce,
            "balance": mean_balance,
            "loss": ce + arch["balance_weight"] * mean_balance}


def gradient(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """The loss's gradient by every parameter, of one sequence: the first
    step's gradient as the reference has it (`variant`, `follow`: as
    `forward`)."""
    return jax.grad(lambda p: forward(p, tokens, arch, variant,
                                      follow)["loss"])(
        jax.tree_util.tree_map(lambda t: jnp.asarray(t, jnp.float32), params))
