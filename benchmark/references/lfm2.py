"""Plain reference for a training step of LFM2-8B-A1B (LiquidAI; `model_type`
`lfm2_moe`, "8.3B-A1.5B"; the mixer: LFM2 Technical Report, arXiv:2511.23404),
on the parameter tree that `models.Transformer` builds for it: double-gated
short convolutions as mixers of their own beside grouped-query attention at
head width 64 with a norm a head, two leading dense layers, then
sigmoid-scored experts (the held ones, no shared one), the head tied to the
embedding.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: the convolution an explicit sum over three
shifted copies, a dense [L, L] boolean mask a layer (a block of query rows at
a time, so that 8192 rows fit), every held expert computed densely for every
position (a loop over them) and weighted by the routing weights; no kernel,
no sort, no grouped matmul, no chunked loss, no bf16. Call it under
`jax.default_matmul_precision("highest")`.

    x   = Emb[tokens]                                          [L, C]
    layer l is a conv or an attention layer (`layer_types`)
    h   = rms(x; w_op)                                         operator_norm
    conv:
      [B | G | z] = h W_in         three equal column blocks, in this order
                                   (ASSUMED: the order; `transformers`'
                                   lfm2 code chunks in_proj into B, C, x and
                                   computes C * conv(B * x))
      u    = B * z
      c[t] = sum_{j=0..2} w[j] u[t - (2 - j)]     depthwise, causal, 3 taps a
                                   channel, zeros before the sequence, no
                                   activation, no bias (ASSUMED: a
                                   cross-correlation aligned so that tap 2
                                   meets the current token: torch's Conv1d
                                   with left padding)
      a    = x + (G * c) W_out
    attention:
      q = h W_q [L, 32, 64];  k, v = h W_k, h W_v [L, 8, 64]
      q <- rms_64(q; w_q), k <- rms_64(k; w_k)    one learned [64] scale each,
                                   before rotary (ASSUMED: the family's
                                   per-head norms)
      rotary on the whole head, theta 1e6, rotate-half pairs (i, i + 32)
      o_h = softmax(q_h k_{h // 4}^T / 8 + causal) v_{h // 4}
      a   = x + concat_h(o_h) W_o
    u   = rms(a; w_ffn)                                        ffn_norm
    layers 0, 1:  x' = a + W_down(silu(W_gate u) * W_up u)     7168 wide
    the others:
      s_e = sigmoid(u W_r)_e over ALL 32 experts, f32
      S   = the 4 experts of largest s_e + b_e (ties: the lower index; b the
            selection bias, at its zeros: no gradient reaches it)
      w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-6)      norm_topk_prob
            (DEPARTURE of the program: `parallel.expert.route` adds 1e-20,
            not 1e-6: 5e-7 of a weight at a sum near 2, four f32 ulps)
      x'  = a + sum_{e in S, e HELD} w_e E_e(u)       E the gated form, 1792
            wide; no shared expert. The experts [first, first + count) are
            held and what the others would add is left out, as in the
            program: one rank's share of the layer; held = (0, 32) is the
            uncut layer.
    hid   = rms(x_last; w_f)
    nll_l = logsumexp(hid_l Emb^T) - (hid_l Emb^T)[tokens_{l+1 mod L}]
            (ASSUMED: the head IS the embedding; DEPARTURE: the sequence
            closed on itself, so that every row has a target)
    loss  = mean_l nll_l          (ASSUMED: no balancing term in the loss)
"""

import jax
import jax.numpy as jnp

# softmax(mask(q k^T d^-1/2)) v by blocks of query rows under `j <= i and
# i - j < window`; the rotate-half rotation of a head's first channels; the
# k largest of a row, ties to the lower index, by rows of 512
from benchmark.references.laguna import attention, rotate, top_k_mask
from benchmark.references.mellum import plain_frequencies
from benchmark.references.xing import gated  # W_down (silu(W_gate u) * W_up u)

# Other models' stacks, which a comparison must refuse (`forward(variant=)`,
# a traced int): the taps read the other way round (tap 0 on the current
# token); the input gate and the output gate swapped (u = G * z, y = B * c);
# a SiLU on the convolution (Mamba's and KDA's habit); no norm a head;
# rotary at base 10000; softmax scores in sigmoid's place.
(AS_PUBLISHED, TAPS_REVERSED, GATES_SWAPPED, CONV_SILU, NO_HEAD_NORM,
 BASE_10000, SOFTMAX_SCORES) = range(7)
VARIANTS = {
    "the taps read the other way round": TAPS_REVERSED,
    "the two gates swapped": GATES_SWAPPED,
    "a SiLU on the convolution": CONV_SILU,
    "rotary at base 10000": BASE_10000,
    "softmax scores in sigmoid's place": SOFTMAX_SCORES}
# Read only where the norms' inputs are away from unit scale (the CPU tests'
# seeded scales): at the modules' own draws q and k have unit mean square a
# head to 9%, and leaving the norm out moves an attention branch by 0.08-0.12
# (my chip runs, PR 65; four seeds), too near the bf16 system's limit to be
# a check of every seed.
VARIANTS_AWAY_FROM_UNIT_SCALE = {"no norm a head on q and k": NO_HEAD_NORM}
# What each variant changes first: the mixer of a kind of layer (read by the
# mixer branches from the first layer of that kind on) or the routing
# weights (read by the first routed layer's router's gradient).
CHANGES = {TAPS_REVERSED: "conv", GATES_SWAPPED: "conv", CONV_SILU: "conv",
           NO_HEAD_NORM: "full", BASE_10000: "full",
           SOFTMAX_SCORES: "routing"}
RENORM_EPS = 1e-6  # the model's; the program's 1e-20 is a listed departure


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def gated_conv(blocks, taps, variant=AS_PUBLISHED):
    """G * conv(B * z) of the three column blocks [L, 3 C] under the taps
    [3, C]: the convolution as an explicit sum over shifted copies of u, tap
    j on the token len(taps) - 1 - j behind, zeros before the sequence."""
    L, C = blocks.shape[0], taps.shape[1]
    b, g, z = (blocks[:, i * C:(i + 1) * C] for i in range(3))
    b, g = (jnp.where(variant == GATES_SWAPPED, g, b),
            jnp.where(variant == GATES_SWAPPED, b, g))
    u = b * z
    n = taps.shape[0]
    w = jnp.where(variant == TAPS_REVERSED, _f32(taps)[::-1], _f32(taps))
    c = sum(w[j] * jnp.concatenate(
        [jnp.zeros((n - 1 - j, C), u.dtype), u[:L - (n - 1 - j)]])
        for j in range(n))
    return g * jnp.where(variant == CONV_SILU, jax.nn.silu(c), c)


def conv_branch(h, a, variant=AS_PUBLISHED):
    """(G * conv(B * z)) W_out [L, C] of the normed input h [L, C] under the
    block's `attn` parameters `a` (a conv layer's)."""
    return gated_conv(h @ _f32(a["in_proj"]["kernel"]), a["conv_kernel"],
                      variant) @ _f32(a["out_proj"]["kernel"])


def attention_branch(h, a, arch, variant=AS_PUBLISHED):
    """concat_h(o_h) W_o [L, C] of the normed input h [L, C] under the
    block's `attn` parameters `a` (an attention layer's)."""
    L = h.shape[0]
    q = jnp.einsum("lc,chd->lhd", h, _f32(a["query"]["kernel"]))
    k = jnp.einsum("lc,chd->lhd", h, _f32(a["key"]["kernel"]))
    v = jnp.einsum("lc,chd->lhd", h, _f32(a["value"]["kernel"]))
    d = q.shape[-1]
    q = jnp.where(variant == NO_HEAD_NORM, q,
                  _rms(q, a["q_norm"]["scale"], arch["eps"]))
    k = jnp.where(variant == NO_HEAD_NORM, k,
                  _rms(k, a["k_norm"]["scale"], arch["eps"]))

    def turned(x):
        return jnp.where(variant == BASE_10000,
                         rotate(x, plain_frequencies(d, 10000.0)),
                         rotate(x, plain_frequencies(d, arch["rope_theta"])))

    o = attention(turned(q), turned(k), v, L)  # every key before it
    return jnp.einsum("lhd,hdc->lc", o, _f32(a["out"]["kernel"]))


def routing_weights(u, p, arch, follow=None, variant=AS_PUBLISHED):
    """(w [T, E] f32, own [T, E] bool, margin [T]) of a routed layer on u
    [T, C]: each token's weight on every expert (0 off its set), over ALL E
    experts. `own` is the reference's own top-k of the scores plus the
    selection bias. `follow` [T, E] bool: the sets a SYSTEM chose, computed
    with in place of `own` (the weights are still the reference's scores of
    them); `margin` then says how near a tie each such choice was: the
    reference's k-th largest score less the least one followed, over the
    k-th largest (0 where the sets agree)."""
    logits = u @ _f32(p["router"])
    s = jnp.where(variant == SOFTMAX_SCORES, jax.nn.softmax(logits, axis=-1),
                  jax.nn.sigmoid(logits))
    choice = s + jax.lax.stop_gradient(_f32(p["select_bias"]))
    own = top_k_mask(choice, arch["top_k"])
    chosen = own if follow is None else follow
    kth = jnp.min(jnp.where(own, choice, jnp.inf), axis=-1)
    margin = (kth - jnp.min(jnp.where(chosen, choice, jnp.inf),
                            axis=-1)) / kth
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    return w * arch["route_scale"], own, margin


def routed_ffn(u, p, arch, held, follow=None, variant=AS_PUBLISHED):
    """(y [T, C], own, margin) of a routed layer as the rank that holds the
    experts `held` = (first, count) computes it: the router over all E, the
    held experts' results alone added (a loop over them, each computed for
    every position). `p` holds the held experts' matrices ([count, ..]: a
    rank's tree) or all E of them (the whole model's tree, of which the
    held are taken); held = (0, E) on the whole tree is the uncut layer."""
    w, own, margin = routing_weights(u, p, arch, follow, variant)
    first, count = held
    mats = [p[name] if p[name].shape[0] == count
            else p[name][first:first + count]
            for name in ("w_gate", "w_up", "w_down")]

    @jax.checkpoint  # a gradient keeps an expert's inputs, not its products
    def weighed(w_gate, w_up, w_down, col):
        return col[:, None] * gated(u, w_gate, w_up, w_down)

    def one(y, expert):
        return y + weighed(*expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        *mats, jnp.moveaxis(w[:, first:first + count], 1, 0)))
    return y, own, margin


def forward(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """One sequence `tokens` [L]: a dict of ``states`` [layers, L, C] (every
    block's output), ``mixer`` [layers, L, C] (every block's first branch,
    the convolution's or the attention's W_out output before the residual
    add), ``chosen`` [routed layers, L, E] bool (the reference's own top-k),
    ``margin`` [routed layers, L] (`routing_weights`; all 0 without `follow`
    [routed layers, L, E] bool, a system's chosen sets to compute with),
    ``held_rows`` [routed layers] (assignments on the held experts, of the
    sets computed with), ``logits`` [L, vocab], ``nll`` [L] (each row's
    cross-entropy against the next token, the sequence closed on itself) and
    ``loss``, their mean. `arch`: kinds (a tuple of "conv" | "full" a
    layer), dense (how many leading layers keep the dense feed-forward),
    eps, rope_theta, top_k, route_scale, held (first, count). `variant` (a
    traced int) computes ANOTHER model's stack, which a comparison must
    refuse (the names above)."""
    eps = arch["eps"]
    first, count = arch["held"]
    table = _f32(params["embed"]["embedding"])
    x = table[tokens]

    def layer(x, p, given, kind, routed):
        h = _rms(x, p["norm1"]["scale"], eps)
        branch = conv_branch(h, p["attn"], variant) if kind == "conv" \
            else attention_branch(h, p["attn"], arch, variant)
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
    states, mixer, routing = [], [], []
    for i, kind in enumerate(arch["kinds"]):
        routed = i >= arch["dense"]
        given = None if follow is None or not routed \
            else follow[i - arch["dense"]]
        x, branch, *rest = layer(x, params["block_%d" % i], given, kind,
                                 routed)
        states.append(x)
        mixer.append(branch)
        if routed:
            routing.append(rest)
    chosen, margins, rows = (jnp.stack(t) for t in zip(*routing))
    hid = _rms(x, params["norm_f"]["scale"], eps)
    logits = hid @ table.T  # the head IS the embedding
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.roll(tokens, -1)[:, None], axis=-1)[:, 0]
    return {"states": jnp.stack(states), "mixer": jnp.stack(mixer),
            "chosen": chosen, "margin": margins, "held_rows": rows,
            "logits": logits, "nll": nll, "loss": jnp.mean(nll)}


def gradient(params, tokens, arch, variant=AS_PUBLISHED, follow=None):
    """The loss's gradient by every parameter, of one sequence (`variant`,
    `follow`: as `forward`). The embedding's is the lookup's plus the
    head's: one table."""
    return jax.grad(lambda p: forward(p, tokens, arch, variant,
                                      follow)["loss"])(
        jax.tree_util.tree_map(_f32, params))
