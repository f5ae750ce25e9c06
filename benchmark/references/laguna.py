"""Plain reference for a training step of Laguna-XS.2 (poolside; `model_type`
`laguna`, "33.4B-A3B"), on the parameter tree that `models.Transformer`
builds for it: full and window attention layers of DIFFERENT head counts on
the same 8 kv heads, a sigmoid gate a head on the attention's output, a
half-head YaRN rotation on the full layers beside a whole-head plain one on
the window layers, a leading dense layer, then sigmoid-scored experts (the
held ones) beside a shared one.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: a dense [L, L] boolean mask a layer from the
published clauses (a block of query rows at a time, so that 8192 rows fit),
the frequency tables written out from their formulas as `transformers`
computes `rope_parameters`, every held expert computed densely for every
position (a loop over them) and weighted by the routing weights; no kernel,
no sort, no grouped matmul, no chunked loss, no recomputation that changes a
number, no bf16. Call it under `jax.default_matmul_precision("highest")`.

    x     = E[tokens]                                        [L, C]
    layer l is "full" where l mod 4 = 0, else "window" (`layer_types`)
    h     = rms(x) w1
    q     = h W_q [L, H, d]    H = heads of the layer's kind (48 full | 64
            window: `num_attention_heads_per_layer`)
    k, v  = h W_k, h W_v [L, G, d]                           G = 8
    full:   the FIRST r = partial_rotary_factor x d = 64 channels of each
            head of q and k rotated, rotate-half pairs (i, i + r/2) inside
            that slice, by the angle p f_i, i < r/2, with YaRN's frequencies
            formed over the r-wide slice as `transformers` computes
            `rope_type` yarn:
              f_i    = theta^(-2i/r),   theta = 500000
              dim(t) = r ln(orig / (2 pi t)) / (2 ln theta)
              lo = max(floor(dim(beta_fast)), 0)
              hi = min(ceil(dim(beta_slow)), r - 1)
              ramp_i = clip((i - lo) / (hi - lo), 0, 1)
              f_i <- f_i / factor * ramp_i + f_i * (1 - ramp_i)
              cos and sin BOTH times attention_factor (0.1 ln factor + 1)
            channels r..d-1 pass as they are. Mask: key j visible to query i
            iff j <= i.
    window: all d channels rotated, f_i = theta^(-2i/d), theta = 10000, cos
            and sin as they are. Mask: j <= i and i - j < sliding_window.
    o_h   = softmax_j(q_h . k_{h // (H/G)} d^-1/2 over the visible j)
            v_{h // (H/G)}
    g     = sigmoid(h W_g) [L, H]          (ASSUMED: `gating` true read as
            the sibling Laguna-S-2.1's per-head gate: from the branch's
            normed input, on the head's output before W_o; no bias)
    a     = x + concat_h(g_h o_h) W_o
    u     = rms(a) w2
    layer 0:    x' = a + W_down (silu(W_gate u) * W_up u)    (8192 wide)
    the others:
      s_e = sigmoid(u W_r)_e over ALL published experts, f32
      S   = the k experts of largest s_e (ties: the lower index; no
            selection bias: the config has no key for one)
      w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
            (ASSUMED: DeepSeek-V3's renormalised sigmoid, whose 2.5 this
            is; the sibling states norm_topk_prob true)
      x'  = a + sum_{e in S, e HELD} w_e E_e(u) + E_shared(u)
            (E: the gated form above, 512 wide; the experts [first, first +
            count) are held and what the others would add is left out, as
            in the program: one rank's share of the layer)
    hid   = rms(x_last) w_f
    nll_l = logsumexp(hid_l W_head) - (hid_l W_head)[tokens_{l+1 mod L}]
            (DEPARTURE: the sequence closed on itself, so that every row has
            a target and the loss is a mean over L)
    loss  = mean_l nll_l          (ASSUMED: no balancing term in the loss)
"""

import jax
import jax.numpy as jnp

# the two frequency tables as `transformers` computes them, over a slice of
# any width; ties to the lower index; W_down (silu(W_gate u) * W_up u)
from benchmark.references.mellum import plain_frequencies, yarn_frequencies
from benchmark.references.xing import gated
from benchmark.references.xing import top_k_mask as _top_k_rows

# query rows of the dense mask and scores made at a time (64 heads of scores
# over 8192 keys in f32: a GiB at 512 rows, and the gradient holds several)
Q_ROWS = 256

# Other models' stacks, which a comparison must refuse (`forward(variant=)`,
# a traced int): the gate left out; the window layers' heads past the full
# layers' count left out (64 heads' weights read as 48); the full layers
# rotated over the whole head; the plain frequencies in YaRN's place (and no
# factor); the two bases swapped; a window of twice the keys; the routing
# weights not scaled; softmax scores in sigmoid's place.
(AS_PUBLISHED, NO_GATE, FEWER_HEADS, WHOLE_HEAD, PLAIN_FREQUENCIES,
 BASES_SWAPPED, WIDER_WINDOW, UNIT_ROUTE_SCALE, SOFTMAX_SCORES) = range(9)
VARIANTS = {
    "the gate left out": NO_GATE,
    "a window layer's heads past the full layers' count left out":
    FEWER_HEADS,
    "the full layers rotated over the whole head": WHOLE_HEAD,
    "plain frequencies in YaRN's place": PLAIN_FREQUENCIES,
    "the two rotary bases swapped": BASES_SWAPPED,
    "a window of twice the keys": WIDER_WINDOW,
    "the routing weights not scaled": UNIT_ROUTE_SCALE,
    "softmax scores in sigmoid's place": SOFTMAX_SCORES}
# What each variant changes first: the attention of a kind of layer (read by
# the attention branches from the first layer of that kind on) or the routing
# weights (read by the first routed layer's router's gradient, whose every
# entry is their form).
CHANGES = {NO_GATE: "full", WHOLE_HEAD: "full", PLAIN_FREQUENCIES: "full",
           BASES_SWAPPED: "full", FEWER_HEADS: "window",
           WIDER_WINDOW: "window", UNIT_ROUTE_SCALE: "routing",
           SOFTMAX_SCORES: "routing"}


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def rotate(x, freq, factor=1.0):
    """x [L, H, d] at positions 0..L-1: the first r = 2 len(freq) channels
    of each head in pairs (i, i + r/2) by the angle p freq_i, cos and sin
    times `factor`; the channels past r as they are."""
    L = x.shape[0]
    freq = jnp.asarray(freq, jnp.float32)
    half = freq.shape[0]
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           axis=-1)


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


def top_k_mask(score, k):
    """[T, E] bool: the k largest of each row, ties to the lower index, 512
    rows at a time."""
    T, E = score.shape
    return jax.lax.map(lambda rows: _top_k_rows(rows, k),
                       score.reshape(-1, min(512, T), E)).reshape(T, E)


def routing_weights(u, router, arch, follow=None, variant=AS_PUBLISHED):
    """(w [T, E] f32, own [T, E] bool, margin [T]) of a routed layer on u
    [T, C]: each token's weight on every expert (0 off its set), over ALL E
    published experts. `own` is the reference's own top-k of the scores.
    `follow` [T, E] bool: the sets a SYSTEM chose, computed with in place
    of `own` (the weights are still the reference's scores of them), so that
    a near tie the system's precision decided otherwise does not send the
    two down different paths; `margin` then says how near a tie each such
    choice was: the reference's k-th largest score less the least one
    followed, over the k-th largest (0 where the sets agree)."""
    logits = u @ _f32(router)
    s = jnp.where(variant == SOFTMAX_SCORES, jax.nn.softmax(logits, axis=-1),
                  jax.nn.sigmoid(logits))
    own = top_k_mask(s, arch["top_k"])
    chosen = own if follow is None else follow
    kth = jnp.min(jnp.where(own, s, jnp.inf), axis=-1)
    margin = (kth - jnp.min(jnp.where(chosen, s, jnp.inf), axis=-1)) / kth
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    scale = jnp.where(variant == UNIT_ROUTE_SCALE, 1.0, arch["route_scale"])
    return w * scale, own, margin


def routed_ffn(u, p, arch, held, follow=None, variant=AS_PUBLISHED,
               shared=True):
    """(y [T, C], own, margin) of a routed layer as the rank that holds the
    experts `held` = (first, count) of the tree's `p` computes it: the
    router over all E, the held experts' results alone added (a loop over
    them, each computed for every position), and the shared expert's
    (`shared` False: left out, for a share whose shared expert another
    share counts)."""
    w, own, margin = routing_weights(u, p["router"], arch, follow, variant)
    first, count = held

    @jax.checkpoint  # a gradient keeps an expert's inputs, not its products
    def weighed(w_gate, w_up, w_down, col):
        return col[:, None] * gated(u, w_gate, w_up, w_down)

    def one(y, expert):
        return y + weighed(*expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["w_gate"], p["w_up"], p["w_down"],
        jnp.moveaxis(w[:, first:first + count], 1, 0)))
    if shared:
        y = y + gated(u, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                      p["shared_down"]["kernel"])
    return y, own, margin


def tables(arch, head_dim):
    """The four frequency tables a variant chooses from, [(frequencies,
    factor)]: the full layers' as published (YaRN over the rotated slice),
    the window layers' as published (plain over the whole head), and the
    same two with the bases swapped."""
    full, window = arch["full"], arch["window"]
    r = int(full["partial_rotary_factor"] * head_dim)
    r_w = int(window.get("partial_rotary_factor", 1) * head_dim)
    return {
        "full": yarn_frequencies(r, full["rope_theta"], full),
        "window": (plain_frequencies(r_w, window["rope_theta"]), 1.0),
        "full_swapped": yarn_frequencies(r, window["rope_theta"], full),
        "window_swapped": (plain_frequencies(r_w, full["rope_theta"]), 1.0),
        "full_plain": (plain_frequencies(r, full["rope_theta"]), 1.0),
        "full_whole": yarn_frequencies(head_dim, full["rope_theta"], full)}


def attention_branch(h, a, kind, arch, variant=AS_PUBLISHED):
    """(concat_h(g_h o_h) W_o [L, C], the gate [L, H]) of the normed input h
    [L, C] under the block's `attn` parameters `a`, a layer of `kind`."""
    L = h.shape[0]
    q = jnp.einsum("lc,chd->lhd", h, _f32(a["query"]["kernel"]))
    k = jnp.einsum("lc,chd->lhd", h, _f32(a["key"]["kernel"]))
    v = jnp.einsum("lc,chd->lhd", h, _f32(a["value"]["kernel"]))
    d = q.shape[-1]
    t = tables(arch, d)

    def turned(x):
        if kind == "full":
            own = rotate(x, *t["full"])
            for other, name in ((WHOLE_HEAD, "full_whole"),
                                (PLAIN_FREQUENCIES, "full_plain"),
                                (BASES_SWAPPED, "full_swapped")):
                own = jnp.where(variant == other, rotate(x, *t[name]), own)
            return own
        return jnp.where(variant == BASES_SWAPPED,
                         rotate(x, *t["window_swapped"]),
                         rotate(x, *t["window"]))

    if kind == "full":
        window = L  # every key before it
    else:
        window = jnp.where(variant == WIDER_WINDOW, 2 * arch["sliding_window"],
                           arch["sliding_window"])
    o = attention(turned(q), turned(k), v, window)
    gate = jax.nn.sigmoid(h @ _f32(a["gate"]["kernel"]))
    gate = jnp.where(variant == NO_GATE, 1.0, gate)
    if kind == "window":
        kept = jnp.arange(o.shape[1]) < arch["heads"]["full"]
        gate = jnp.where((variant == FEWER_HEADS) & ~kept[None, :], 0.0, gate)
    return jnp.einsum("lhd,hdc->lc", o * gate[..., None],
                      _f32(a["out"]["kernel"])), gate


def forward(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """One sequence `tokens` [L]: a dict of ``states`` [layers, L, C] (every
    block's output), ``attn`` [layers, L, C] (every block's attention
    branch, W_o's output before the residual add), ``chosen`` [routed
    layers, L, E] bool (the reference's own top-k), ``margin`` [routed
    layers, L] (`routing_weights`; all 0 without `follow` [routed layers, L,
    E] bool, a system's chosen sets to compute with), ``held_rows`` [routed
    layers] (assignments on the held experts, of the sets computed with),
    ``logits`` [L, vocab], ``nll`` [L] (each row's cross-entropy against the
    next token, the sequence closed on itself) and ``loss``, their mean.
    `arch`: kinds (a tuple of "full" | "window" a layer), dense (how many
    leading layers keep the dense feed-forward), eps, heads {kind: count},
    full and window (the kinds' `rope_parameters`), sliding_window, top_k,
    route_scale, held (first, count). `variant` (a traced int) computes
    ANOTHER model's stack, which a comparison must refuse (the names
    above)."""
    eps = arch["eps"]
    first, count = arch["held"]
    x = _f32(params["embed"]["embedding"])[tokens]

    def layer(x, p, given, kind, routed):
        branch, _ = attention_branch(_rms(x, p["norm1"]["scale"], eps),
                                     p["attn"], kind, arch, variant)
        x = x + branch
        u = _rms(x, p["norm2"]["scale"], eps)
        if not routed:
            return x + gated(u, p["mlp_gate"]["kernel"],
                             p["mlp_up"]["kernel"],
                             p["mlp_out"]["kernel"]), branch
        y, own, margin = routed_ffn(u, p["moe_mlp"], arch, arch["held"],
                                    given, variant)
        rows = jnp.sum((own if given is None else given)[
            :, first:first + count])
        return x + y, branch, own, margin, rows

    layer = jax.checkpoint(layer, static_argnums=(3, 4))
    states, attn, routing = [], [], []
    for i, kind in enumerate(arch["kinds"]):
        routed = i >= arch["dense"]
        given = None if follow is None or not routed \
            else follow[i - arch["dense"]]
        x, branch, *rest = layer(x, params["block_%d" % i], given, kind,
                                 routed)
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
            "logits": logits, "nll": nll, "loss": jnp.mean(nll)}


def gradient(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """The loss's gradient by every parameter, of one sequence: the first
    step's gradient as the reference has it (`variant`, `follow`: as
    `forward`)."""
    return jax.grad(lambda p: forward(p, tokens, arch, variant,
                                      follow)["loss"])(
        jax.tree_util.tree_map(_f32, params))
