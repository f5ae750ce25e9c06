"""Plain reference for SDAR-30B-A3B's block-diffusion training step (the
language model of `sdar_moe`: Qwen3-MoE's layer; the objective: BD3-LM,
Arriola et al., arXiv:2503.09573, which SDAR's training follows), on the
parameter tree that `models.Transformer` builds for it.

Straightforward `jax.numpy` in float32, written from the equations below and
not from the program's code: a dense [2L, 2L] mask made from the rule's three
clauses (a block of query rows at a time, so that 8192 rows fit), every held
expert computed densely for every position and masked by the top-k weights,
the noise drawn here from the same key; no kernel, no sort, no grouped
matmul, no chunked loss, no bf16. Call it under
`jax.default_matmul_precision("highest")`.

    x0 [L] the data; blocks of b tokens; per block t ~ U(t_min, 1); a token
    of the block becomes MASK with probability t: x_t.
    ids = [x_t ; x0]  (2L rows),  pos = [0..L-1 ; 0..L-1]
    N(i) = i < L,  B(i) = (i mod L) // b;  row i sees row j iff
        (N(i) & N(j) & B(i) = B(j)) | (N(i) & ~N(j) & B(j) < B(i))
        | (~N(i) & ~N(j) & B(j) <= B(i))

    x     = E[ids]
    h     = rms(x) g1
    q     = h W_q [2L, H, d],  k = h W_k,  v = h W_v [2L, G, d]
    q, k  = rot(rms_d(q) g_q, pos), rot(rms_d(k) g_k, pos)
            (rms over the d of ONE head; g_q, g_k [d] shared by the heads;
             rotate-half pairs i, i + d/2; base theta)
    a     = x + softmax_j(mask(q_h . k_{h // (H/G)} d^-1/2)) v  W_o
    u     = rms(a) g2
    p     = softmax_E(u W_r)          (E = all published experts)
    S     = the k experts of largest p (ties: the lower index)
    w_e   = p_e / sum_{e in S} p_e                     (norm_topk_prob)
    x'    = a + sum_{e in S, e HELD} w_e W_down,e (silu(u W_gate,e) * u W_up,e)
            (the experts [first, first + count) are held; what the others
             would add is left out, as in the program)
    lb    = E * sum_e f_e P_e over the 2L rows, f_e = |{i: e in S_i}| / 2L
    hid   = rms(x_N) g_f, the NOISY half's rows only
    nll_l = logsumexp(hid_l W_head) - (hid_l W_head)[x0_l]      (no shift)
    loss  = 1/L sum_{l masked} nll_l / t_{B(l)}  +  w_lb * mean_layers lb
"""

import jax
import jax.numpy as jnp

Q_ROWS = 1024  # query rows of the dense mask and scores made at a time


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, pos, base):
    """x [R, H, D] at positions pos [R]: pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def noise(key, L, block, t_min):
    """(t [L // block], masked [L] bool) of one sequence from its key: the
    key split in two, the blocks' levels uniform in [t_min, 1), then a
    uniform a token, masked where it lies under its block's level."""
    k_level, k_token = jax.random.split(key)
    t = t_min + (1.0 - t_min) * jax.random.uniform(
        k_level, (L // block,), jnp.float32)
    u = jax.random.uniform(k_token, (L,), jnp.float32)
    return t, u < jnp.repeat(t, block)


def visible(rows, cols, L, block, variant=0):
    """[R, C] bool from row and column indices [R], [C]: the rule's three
    clauses. `variant` (a traced int) swaps in ANOTHER model's mask, which a
    comparison must refuse: 1 the causal triangle over the 2L rows, 2 the
    clean half left out (a noisy row sees its own block and nothing
    else)."""
    i, j = rows[:, None], cols[None, :]
    n_i, n_j = i < L, j < L
    b_i, b_j = (i % L) // block, (j % L) // block
    own = n_i & n_j & (b_i == b_j)
    earlier_clean = n_i & ~n_j & (b_j < b_i)
    clean = ~n_i & ~n_j & (b_j <= b_i)
    rule = own | earlier_clean | clean
    return jnp.where(variant == 1, i >= j,
                     jnp.where(variant == 2, own | clean, rule))


def attention(q, k, v, L, block, variant):
    """softmax(mask(q k^T d^-1/2)) v by blocks of `Q_ROWS` query rows: q
    [R, H, d], k and v [R, G, d], query head h on kv head h // (H / G)."""
    R, H, d = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = min(Q_ROWS, R)
    cols = jnp.arange(R)

    @jax.checkpoint
    def some_rows(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * d ** -0.5
        seen = visible(start + jnp.arange(rows), cols, L, block, variant)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(some_rows, jnp.arange(0, R, rows))
    return out.reshape(R, H, d)


def top_k_mask(p, k):
    """[T, E] bool: the k largest of each row, ties to the lower index,
    from each entry's rank."""
    idx = jnp.arange(p.shape[-1])
    ahead = (p[:, None, :] > p[:, :, None]) | (
        (p[:, None, :] == p[:, :, None]) & (idx[None, None, :]
                                            < idx[None, :, None]))
    return jnp.sum(ahead, axis=-1) < k


def routed_ffn(u, p_moe, k, held, follow=None):
    """(y [T, D], own [T, E] bool, load-balancing term, margin [T]) of one
    layer on u [T, D]; the router over all E, the experts `held` = (first,
    count) computed. `own` is the reference's own top-k. `follow` [T, E]
    bool: the sets a SYSTEM chose, computed with in place of `own` (the
    weights are still the reference's probabilities of them), so that a
    near-tie the system's precision decided otherwise does not send the
    two down different paths; `margin` then says how near a tie each such
    choice was: the reference's k-th largest probability less the least
    probability followed, over the k-th largest (0 where the sets agree;
    near 1 where a set was not chosen by probability at all)."""
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
    w_held = w[:, first:first + count]
    gate = jnp.einsum("td,edf->tef", u, f32(p_moe["w_gate"]))
    up = jnp.einsum("td,edf->tef", u, f32(p_moe["w_up"]))
    y = jnp.einsum("tef,efd->td", jax.nn.silu(gate) * up * w_held[:, :, None],
                   f32(p_moe["w_down"]))
    frac = jnp.sum(chosen, axis=0) / u.shape[0]
    return y, own, E * jnp.sum(frac * jnp.mean(p, axis=0)), margin


def forward(params, tokens, key, arch, variant=0, follow=None):
    """One sequence `tokens` [L] with the noise of `key`: a dict of
    ``states`` [layers, 2L, hidden] (every block's output, both halves),
    ``chosen`` [layers, 2L, E] bool (the reference's own top-k), ``margin``
    [layers, 2L] (`routed_ffn`; all 0 without `follow` [layers, 2L, E]
    bool, a system's chosen sets to compute with), ``nll`` [L] (each noisy
    row's cross-entropy against its own token), ``masked`` [L] bool, ``t``
    [L / b], ``ce`` (the weighted sum), ``ce_unit_weights`` (the same rows at
    weight 1 for 1 / t: another objective), ``balance`` (mean over the
    layers) and ``loss`` = ce + arch["balance_weight"] * balance. `arch`:
    num_layers, eps, rope_base, top_k, held, block, mask_id, t_min,
    balance_weight."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    L, block, eps = tokens.shape[0], arch["block"], arch["eps"]
    t, masked = noise(key, L, block, arch["t_min"])
    ids = jnp.concatenate([jnp.where(masked, arch["mask_id"], tokens),
                           tokens])
    pos = jnp.concatenate([jnp.arange(L), jnp.arange(L)])
    x = f32(params["embed"]["embedding"])[ids]

    @jax.checkpoint
    def layer(x, p, given):
        a = p["attn"]
        h = _rms(x, f32(p["norm1"]["scale"]), eps)
        q = jnp.einsum("ld,dhk->lhk", h, f32(a["query"]["kernel"]))
        k = jnp.einsum("ld,dhk->lhk", h, f32(a["key"]["kernel"]))
        v = jnp.einsum("ld,dhk->lhk", h, f32(a["value"]["kernel"]))
        q = _rotary(_rms(q, f32(a["q_norm"]["scale"]), eps), pos,
                    arch["rope_base"])
        k = _rotary(_rms(k, f32(a["k_norm"]["scale"]), eps), pos,
                    arch["rope_base"])
        o = attention(q, k, v, L, block, variant)
        x = x + jnp.einsum("qhk,hkd->qd", o, f32(a["out"]["kernel"]))
        u = _rms(x, f32(p["norm2"]["scale"]), eps)
        y, own, balance, margin = routed_ffn(
            u, p["moe_mlp"], arch["top_k"], arch["held"], given)
        return x + y, own, balance, margin

    states, chosen, balance, margins = [], [], [], []
    for i in range(arch["num_layers"]):
        x, c, b, m = layer(x, params["block_%d" % i],
                           None if follow is None else follow[i])
        states.append(x)
        chosen.append(c)
        balance.append(b)
        margins.append(m)
    hid = _rms(x[:L], f32(params["norm_f"]["scale"]), eps)
    logits = hid @ f32(params["lm_head"]["kernel"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[:, None], axis=-1)[:, 0]
    ce = jnp.sum(jnp.where(masked, nll / jnp.repeat(t, block), 0.0)) / L
    mean_balance = sum(balance) / len(balance)
    return {"states": jnp.stack(states), "chosen": jnp.stack(chosen),
            "margin": jnp.stack(margins), "nll": nll, "masked": masked,
            "t": t, "ce": ce,
            "ce_unit_weights": jnp.sum(jnp.where(masked, nll, 0.0)) / L,
            "balance": mean_balance,
            "loss": ce + arch["balance_weight"] * mean_balance}


def gradient(params, tokens, key, arch, variant=0, follow=None):
    """The loss's gradient by every parameter, of one sequence: the first
    step's gradient as the reference has it (`variant`, `follow`: as
    `forward`)."""
    return jax.grad(lambda p: forward(p, tokens, key, arch, variant,
                                      follow)["loss"])(
        jax.tree_util.tree_map(lambda t: jnp.asarray(t, jnp.float32), params))
