"""Builder for Mellum2 configurations (JetBrains, `model_type` `mellum`):
`models.Transformer` as Qwen3-MoE's layer (grouped-query attention with a
norm over each head's q and k, softmax-routed gated experts of which this
chip HOLDS a part, no shared one) with an attention KIND a layer
(`attention_types`): window layers under the causal band the flash kernels
take by rule (`ops.BandMask`) on the plain rotation, full layers under the
causal triangle on YaRN's; trained through `parallel.make_train_step` on the
next token's cross-entropy (the chunked vocabulary loss) plus the router's
balancing term, AdamW inside a linear warm-up.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/mellum.py` outside the
window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, one sequence of 8192, the seeded state of the configuration (my
# chip runs, PR 52: nine runs of the benchmark, a seed each; PERF.md s6). As
# `builders/sdar.py`, whose layer this is:
# the reference computes every layer with the experts the SYSTEM's top-8 chose
# (`references/mellum.py::routed_ffn(follow=)`) and says how often its own
# top-8 differs and how near a tie each such choice was, so EVERY position is
# compared. Each limit that guards the PRECISION lies between two readings:
# the largest the bf16 system gave over its seeds, and the least the same
# system gave with every matrix rounded to fp8's precision (e4m3), which
# `verify` computes in every run and which has to come out as not correct:
#   layers x positions whose top-8 set is not the reference's own: bf16
#     0.170..0.174, fp8 0.837..0.842 (a sixth of all choices lie within
#     bf16's reach of a tie).
#   how far from a tie the farthest such choice lies (the reference's 8th
#     largest probability less the least one chosen, over the former): bf16
#     0.131..0.169, fp8 0.693..0.796.
#   a block's state, every position, max |sys - ref| / max |ref|: bf16
#     3.1e-2..4.2e-2 in every block (the first block's sharp attention sets
#     it, the seven smooth ones pass it on), fp8 0.260..0.349.
#   a block's attention branch (W_o's output before the residual add: what
#     the layer's KIND decides), likewise: bf16 9.4e-3..3.8e-2 (the first,
#     sharp block 3.2e-2..3.8e-2; a smooth block's output is a small mean, at
#     0.009..0.025 of its own largest entry), fp8 0.086..0.339 (the least in
#     a smooth window layer).
#   a row's cross-entropy, nats: bf16 0.125..0.232, fp8 0.99..1.26.
# These guard something else and do NOT separate the precisions:
#   the first gradient leaf by leaf where the flash kernels write it (the
#     four attention matrices of the first and the last layer of each kind,
#     all recomputed; worst leaf's |sys - ref|_2 / |ref|_2): bf16
#     6.6e-2..6.8e-2; the same layer's in `builders/sdar.py`: fp8
#     0.46..0.63, another mask 1.2..2.5 (not computed here: two gradients
#     more a run); the limit is that builder's.
#   the first gradient's l2 norm by parameter group (the window layers'
#     attention, the full layers', routers, held experts, embedding, head),
#     relative: bf16 1.4e-4..7.9e-4: a norm over millions of entries
#     averages; it holds each group's SCALE (a term left out, a factor
#     applied twice: YaRN's 1.277 squared multiplies the full layers'
#     scores), with twelve times of room.
#   mean loss with its balancing term, relative: bf16 6.7e-6..7.0e-5, fp8
#     7.2e-7..4.3e-4: a mean near ln(vocab) over 8192 rows averages the
#     precision away; the limit is the harness's accepted cells'
#     (`builders/sdar.py`: twenty-eight times the largest seen), and holds
#     the loss's ASSEMBLY (targets, the mean, the balancing term's weight).
# What the comparison must refuse, and does (same runs), by the attention
# branches from the first layer each changes: the reference with every layer
# full (`window_matters`) 1.10..1.39, with the full layers on the plain
# rotation (`yarn_matters`) 0.191..0.239, with the full layers under the
# window 0.150..0.169, with YaRN's frequencies but its factor left off cos
# and sin 0.190..0.239: the least of them 2.3 times the limit. A window off
# by one key is below any tolerance on a state, so the band's EDGES are read
# exactly, through the timed kernels at the timed shape (`band_edges`).
TOL_FLIPPED = 0.5          # share of layers x positions whose sets differ
TOL_MARGIN = 0.35          # how far from a tie a differing choice may lie
TOL_STATE = 1e-1           # max |system - reference| / max |reference|
TOL_ATTN = 6.5e-2          # likewise, a block's attention branch
TOL_NLL = 4.5e-1           # max |system - reference|, a row's nats
TOL_LOSS = 2e-3            # relative, the mean loss with its balancing term
TOL_GRAD_NORM = 1e-2       # relative, a parameter group's gradient's l2 norm
TOL_GRAD_LEAF = 2.5e-1     # a picked leaf's |system - reference|_2 / |ref|_2

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_mellum, flops_moe
    from benchmark.builders.sdar import rounded_to_e4m3
    from benchmark.references import mellum as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import BandMask, flash_attention
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import (make_train_step, router_aux_losses,
                                      routing_stats)

    job = config["job"]
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    head_dim = config["head_dim"]
    layers = config["num_hidden_layers"]
    experts = config["published_num_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_tok"]
    expert_width = config["moe_intermediate_size"]
    vocab = config["vocab_size"]
    eps = config["rms_norm_eps"]
    window = config["sliding_window"]
    kinds = tuple(KINDS[t] for t in config["layer_types"])
    rope = config["rope_parameters"]
    yarn_keys = rope["full_attention"]
    theta = yarn_keys["rope_theta"]
    w_balance = job["router_aux_loss_coef"]
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (len(kinds) != layers or held != config["num_experts"]
            or config["model_type"] != "mellum"
            or set(config["mlp_layer_types"]) != {"sparse"}
            or len(config["mlp_layer_types"]) != layers
            or config["attention_bias"] or config["hidden_act"] != "silu"
            or not config["use_sliding_window"]
            or yarn_keys["rope_type"] != "yarn"
            or rope["sliding_attention"] != {"rope_type": "default",
                                             "rope_theta": theta}
            or config["tie_word_embeddings"]):
        raise ValueError("this builder makes routed layers only, SiLU, a "
                         "window or a full attention a layer, YaRN on the "
                         "full layers and the plain rotation of the same "
                         "base on the window layers, no bias, an untied head")
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    yarn = transformer.Yarn(
        float(yarn_keys["factor"]), float(yarn_keys["beta_fast"]),
        float(yarn_keys["beta_slow"]),
        int(yarn_keys["original_max_position_embeddings"]), mscale=1.0,
        mscale_all_dim=0.0)
    factor = (transformer.yarn_mscale(yarn.factor, yarn.mscale)
              / transformer.yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    if abs(factor - yarn_keys["attention_factor"]) > 1e-12:
        raise ValueError("the program's YaRN gives cos and sin the factor "
                         "%r, the configuration %r"
                         % (factor, yarn_keys["attention_factor"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, embed_dim=hidden,
        mlp_dim=config["intermediate_size"], moe_dim=expert_width,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rope_base=theta, rope_yarn=yarn,
        qk_norm="head", attention_types=kinds, attention_window=window,
        norm_eps=eps, moe_experts=experts, moe_every=1, moe_top_k=top_k,
        moe_capacity_factor=None, moe_gated=True,
        moe_renormalize=config["norm_topk_prob"], moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"kinds": kinds, "eps": eps, "rope_theta": theta,
            "window": window, "yarn": yarn_keys, "top_k": top_k,
            "held": (first, held), "balance_weight": w_balance}
    chunk = min(int(job["loss_chunk"]), length)
    # The rate climbs from 0 to `learning_rate` over `warmup_steps`: a window
    # at the start of a job is inside the warm-up (the file says why).
    opt = optax.adamw(optax.linear_schedule(0.0, job["learning_rate"],
                                            int(job["warmup_steps"])),
                      b1=job["adam_b1"], b2=job["adam_b2"],
                      eps=job["adam_eps"], weight_decay=job["weight_decay"])

    def objective(params, tokens, weights=None, capture=False):
        """The loss of sequences `tokens` [B, L] (`weights`: a weight a row
        in place of the mean), with the model's collections."""
        hid, state = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["intermediates"],
            **({"capture_intermediates": lambda mdl, name: isinstance(
                mdl, (transformer.Block, transformer.Attention))
                and name == "__call__"} if capture else {}))
        ce = chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
            chunk=chunk, weights=weights)
        balance, _ = router_aux_losses(state["intermediates"])
        return ce + w_balance * balance, state["intermediates"]

    def loss_fn(params, batch):
        return objective(params, batch["x"])[0]

    def forward(params, seq):
        """Of ONE sequence [L]: every block's output and every block's
        attention branch [layers, L, C] f32, each row's own cross-entropy
        [L] (the gradient of the weighted loss by a row's weight, as the
        timed loss forms it), the loss, and the step's counters."""
        x = seq[None]
        loss, inter = objective(params, x, capture=True)
        blocks = [inter["block_%d" % i] for i in range(layers)]
        states = jnp.stack([b["__call__"][0][0].astype(jnp.float32)
                            for b in blocks])
        attn = jnp.stack([b["attn"]["__call__"][0][0].astype(jnp.float32)
                          for b in blocks])
        nll = jax.grad(lambda w: objective(params, x, w)[0])(
            jnp.zeros((1, length), jnp.float32))[0]
        routing = routing_stats(inter)
        return (states, attn, nll, loss,
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "dropped": routing["dropped"]})

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    drawn = config["seeded_state"]
    qk_first = float(drawn["first_block_qk_norm_scale"])
    out_gain = float(drawn["other_blocks_attention_out_gain"])

    def init_params(key):
        """The seeded state: flax's draws, but for what the configuration's
        `seeded_state` says and why (as `sdar30b_a3b_w2048`'s: positions
        must be told apart, as a trained model tells them, or the router's
        own top-k loads this rank by the seed's luck): the first block's two
        per-head QK-norm scales at `first_block_qk_norm_scale`, the other
        blocks' attention output matrices times
        `other_blocks_attention_out_gain`."""
        params = model.init(key, jnp.zeros((1, length), jnp.int32))["params"]
        first_attn = params["block_0"]["attn"]
        for name in ("q_norm", "k_norm"):
            first_attn[name]["scale"] = jnp.full_like(
                first_attn[name]["scale"], qk_first)
        for i in range(1, layers):
            out = params["block_%d" % i]["attn"]["out"]
            out["kernel"] = out_gain * out["kernel"]
        return params

    def make_tokens(key):
        """Ids uniform over the vocabulary slice."""
        return jax.random.randint(key, (n_seq, length), 0, vocab, jnp.int32)

    def make_state(kp, kt):
        params = init_params(kp)
        return params, opt.init(params), {"x": make_tokens(kt)}

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(mesh.axis_names[0]))
    make = jax.jit(make_state, out_shardings=(rep, rep, dat))
    state = (jax.eval_shape if abstract else lambda f, *a: f(*a))(
        make, k_param, k_tok)

    # The gradient is held by parameter group (its norm) and leaf by leaf
    # where the flash kernels write it: the attention's four matrices of the
    # first and the last layer of each kind.
    compared = sorted({f(i for i, k in enumerate(kinds) if k == kind)
                       for kind in set(kinds) for f in (min, max)})

    def groups(grads):
        blocks = [grads["block_%d" % i] for i in range(layers)]
        out = {"embedding": grads["embed"], "head": grads["lm_head"],
               "routers": [b["moe_mlp"]["router"] for b in blocks],
               "held experts": [
                   {k: v for k, v in b["moe_mlp"].items() if k != "router"}
                   for b in blocks]}
        for kind in set(kinds):
            out["attention, %s layers" % kind] = [
                b["attn"] for b, k in zip(blocks, kinds) if k == kind]
        return {name: optax.global_norm(g) for name, g in out.items()}

    def picked(grads):
        return groups(grads), {
            "block_%d/%s" % (i, name):
            grads["block_%d" % i]["attn"][name]["kernel"]
            for i in compared for name in ("query", "key", "value", "out")}

    system = jax.jit(forward)
    system_grad = jax.jit(lambda p, seq: picked(jax.grad(
        lambda q: objective(q, seq[None])[0])(p)))
    ref_fn = jax.jit(lambda p, seq, variant, follow: reference.forward(
        p, seq, arch, variant, follow))
    ref_grad = jax.jit(lambda p, seq, follow: picked(
        reference.gradient(p, seq, arch, follow=follow)))

    def grad_readings(system_params, seq, ref):
        """The system's first gradient on `system_params` against `ref`, the
        reference's (group norms, leaves): each group's norm's relative
        distance, and the worst picked leaf's |sys - ref|_2 / |ref|_2."""
        norms, leaves = system_grad(system_params, seq)
        return {
            "grad_norms": {k: [float(norms[k]), float(ref[0][k])]
                           for k in sorted(norms)},
            "grad_norm": max(abs(float(norms[k]) - float(ref[0][k]))
                             / float(ref[0][k]) for k in norms),
            "grad_leaf": max(float(jnp.linalg.norm(leaves[n] - ref[1][n])
                                   / jnp.linalg.norm(ref[1][n]))
                             for n in leaves)}

    def reference_grads(params, seq, follow):
        with jax.default_matmul_precision("highest"):
            return ref_grad(params, seq, follow)

    others = {"every layer full (window_matters)": reference.ALL_FULL,
              "the full layers on the plain rotation (yarn_matters)":
              reference.PLAIN_ROTATION,
              "the full layers under the window": reference.ALL_WINDOW,
              "YaRN's factor left off cos and sin": reference.NO_FACTOR}

    def readings(seen, params, seq, system_params=None, ref_grads=None):
        """How far the system's forward `seen` (`system(...)` of its
        parameters on `seq` [L]) is from the reference on `params`: the
        errors the limits above are set on and both sides' numbers. The
        reference computes every layer with the experts the SYSTEM chose
        and says how often its own top-k differs and how near a tie each
        such choice was, so that every position is compared. With
        `system_params` (the system's own; two sets of parameters beside
        the reference's working set do not fit the chip, so a caller that
        only wants the forward hands `seen` alone), also the first gradient
        (`grad_readings`; `ref_grads`: `reference_grads` of the SAME
        system's sets) and the same system against references of ANOTHER
        stack (`others`), which the comparison must refuse: by the
        attention branches from the first layer each of them changes."""
        states, attn, nll, loss, counters = seen
        counters = dict(counters)
        follow = jnp.any(jax.nn.one_hot(
            counters.pop("chosen"), experts, dtype=jnp.bool_), axis=-2)
        counters = jax.device_get(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, reference.AS_PUBLISHED, follow)
        flipped = jnp.any(follow != ref["chosen"], axis=2)  # [layers, L]

        def err(ours, theirs):
            return [float(v) for v in
                    jnp.max(jnp.abs(ours - theirs), axis=(1, 2))
                    / jnp.max(jnp.abs(theirs), axis=(1, 2))]

        loss, ref_loss = float(loss), float(ref["loss"])
        out = {
            "loss_system": loss, "loss_reference": ref_loss,
            "reference_loss_parts": {k: float(ref[k])
                                     for k in ("ce", "balance")},
            "flipped": float(jnp.mean(flipped)),
            "flipped_by_layer": [float(v) for v in jnp.mean(flipped, axis=1)],
            "margin": float(jnp.max(ref["margin"])),
            "state": err(states, ref["states"]),
            "attn": err(attn, ref["attn"]),
            "nll": float(jnp.max(jnp.abs(nll - ref["nll"]))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "held_rows_reference": [int(v) for v in ref["held_rows"]],
            "dropped": int(counters["dropped"])}
        del ref
        if system_params is not None:
            out.update(grad_readings(
                system_params, seq,
                ref_grads or reference_grads(params, seq, follow)))
            out["against"] = {}
            for name, variant in others.items():
                with jax.default_matmul_precision("highest"):
                    theirs = ref_fn(params, seq, variant, follow)["attn"]
                # from the first layer the other stack changes on
                layer = kinds.index(
                    "window" if variant == reference.ALL_FULL else "full")
                out["against"][name] = max(err(attn, theirs)[layer:])
        return out

    def refused(r):
        """The limits of the precision that `r` breaks."""
        return [name for name, bad in (
            ("flipped", r["flipped"] > TOL_FLIPPED),
            ("margin", r["margin"] > TOL_MARGIN),
            ("state", max(r["state"]) > TOL_STATE),
            ("attn", max(r["attn"]) > TOL_ATTN),
            ("nll", r["nll"] > TOL_NLL)) if bad]

    group = heads // kv_heads
    rules = {"window": BandMask(window), "full": None}

    def band_edges():
        """Each query's OLDEST and NEWEST visible key and each key's latest
        and earliest viewer, read EXACTLY through the timed kernels at the
        timed shape, a kind of layer at a time: the scores are +-c x the
        key's position (split as 256 a + b, every operand an integer bf16
        holds), so that the softmax is one-hot on the newest or on the
        oldest visible key; v holds the key's position in bits, dO the
        query's, and the output and dV are decoded by sign. A window off by
        one key, on either side, in either direction, is a wrong integer
        here. {kind: the rows that decode wrong, of [the newest key, its
        viewer, the oldest key, its viewer]}."""
        dtype, n_bits = cfg.dtype, 16
        pos = jnp.arange(length)
        bits = jnp.pad(
            (((pos[:, None] >> jnp.arange(n_bits)) & 1) * 2 - 1).astype(dtype),
            ((0, 0), (0, head_dim - n_bits)))        # [L, D]: +-1, then 0
        pad = jnp.zeros((length, head_dim - 2), dtype)
        q = jnp.concatenate([jnp.broadcast_to(jnp.asarray(
            [[65536.0, 256.0]], dtype), (length, 2)), pad], axis=1)
        k = jnp.concatenate([jnp.stack([pos // 256, pos % 256],
                                       axis=1).astype(dtype), pad], axis=1)

        def on_heads(t, n):
            return jnp.broadcast_to(t[None, :, None, :],
                                    (1, length, n, head_dim))

        def decode(t):  # [L, D] of +-n or ~0 -> the integer, -1 for all 0
            t = t[:, :n_bits].astype(jnp.float32)  # (a key one off the
            n = jnp.sum((t > 0.5).astype(jnp.int32)  # one-hot weighs e^-22)
                        << jnp.arange(n_bits), axis=1)
            return jnp.where(jnp.any(jnp.abs(t) > 0.5, axis=1), n, -1)

        def run(sign, rule):
            out, vjp = jax.vjp(
                lambda v: flash_attention(
                    on_heads(q, heads), on_heads(sign * k, kv_heads), v,
                    **({"causal": True} if rule is None else {"mask": rule})),
                on_heads(bits, kv_heads))
            dv, = vjp(on_heads(bits, heads))
            return decode(out[0, :, 0]), decode(dv[0, :, 0])

        run = jax.jit(run, static_argnums=1)
        wrong = {}
        for kind, rule in rules.items():
            w = length if rule is None else window
            newest, viewer_of_newest = run(jnp.asarray(1.0, dtype), rule)
            oldest, viewer_of_oldest = run(jnp.asarray(-1.0, dtype), rule)
            # the newest key is the query's own, and its one viewer is
            # itself; the oldest is window - 1 back (or key 0), and key j
            # (but 0, the oldest of the first `w` queries) is the oldest of
            # query j + w - 1 alone, of nobody past the sequence's end
            want_viewer = jnp.where(pos + w - 1 < length, pos + w - 1, -1)
            wrong[kind] = [int(jnp.sum(bad)) for bad in (
                newest != pos, viewer_of_newest != pos,
                oldest != jnp.maximum(pos - w + 1, 0),
                (viewer_of_oldest != want_viewer)[1:])]
        return wrong

    forward_plans, backward_plans = ({
        kind: profile.flash_plan(
            1, heads, length, head_dim, group, cfg.dtype, b,
            mask=rule or BandMask(length))  # the triangle, by its tiles
        for kind, rule in rules.items()} for b in (False, True))
    plans = {kind: dict(forward_plans[kind], **backward_plans[kind])
             for kind in rules}

    def tiles_by_the_mask():
        """{kind: {kernel: [visited, masked, skipped]}} counted on the dense
        mask itself, a tile at a time, at the plans' blocks."""
        out = {}
        for kind, by_kernel in plans.items():
            rule = rules[kind] or BandMask(length)
            out[kind] = {}
            for name, p in by_kernel.items():
                bqp = p.block_q // group
                some, every = [], []
                for lo in range(0, length, bqp):
                    seen = rule.visible(
                        np.arange(lo, lo + bqp)[:, None],
                        np.arange(length)[None, :], np).reshape(
                            bqp, length // p.block_k, p.block_k)
                    some.append(seen.any(axis=(0, 2)))
                    every.append(seen.all(axis=(0, 2)))
                some, every = np.stack(some), np.stack(every)
                out[kind][name] = [int(kv_heads * n) for n in (
                    some.sum(), (some & ~every).sum(), (~some).sum())]
        return out

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, nothing dropped), then from the seed again
        the system's forward, loss and first gradient (the band-ruled and
        the causal flash kernels, the two rotations, grouped matmul over the
        held experts, the chunked loss, bf16) against the plain reference on
        the sequence: every block's state and attention branch, the
        routing, each row's cross-entropy, the loss with its balancing
        term, the gradient's norm by parameter group and its leaves where
        the kernels write them; the train step's first loss against the
        reference's; the band's edges through the timed kernels; the plan's
        tile counts against the mask; the same system on e4m3 matrices and
        four references of another stack, all of which the comparison must
        refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            seq = tokens[0]
            last = jax.device_get(system(final_params, seq)[4])
            del final_params
            edges = band_edges()
            params = jax.jit(init_params)(k_param)
            # The reference's gradient first, on the system's sets and
            # with nothing else alive beside the parameters.
            ref_grads = reference_grads(params, seq, jnp.any(jax.nn.one_hot(
                system(params, seq)[4]["chosen"], experts, dtype=jnp.bool_),
                axis=-2))
            r = readings(system(params, seq), params, seq, params, ref_grads)
            del ref_grads
            low = readings(system(jax.jit(rounded_to_e4m3)(params), seq),
                           params, seq)
            del params
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        counted = tiles_by_the_mask()
        planned = {kind: {name: [p.tiles_visited, p.tiles_masked,
                                 p.tiles_skipped]
                          for name, p in by_kernel.items()}
                   for kind, by_kernel in plans.items()}
        against = r["against"]
        print("INFO " + json.dumps({
            "held_share_first_step": r["held_share"],
            "held_share_first_step_summed": sum(r["held_share"]),
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "held_rows_reference_by_layer": r["held_rows_reference"],
            "flipped_share_of_layers_x_positions": r["flipped"],
            "flipped_by_layer": r["flipped_by_layer"],
            "flipped_margin": r["margin"],
            "state_err_by_block": r["state"],
            "attn_branch_err_by_block": r["attn"],
            "nll_err": r["nll"], "loss_err": r["loss"],
            "grad_norms_system_reference_by_group": r["grad_norms"],
            "grad_norm_err": r["grad_norm"],
            "grad_worst_leaf_err": r["grad_leaf"],
            "reference_loss_parts": r["reference_loss_parts"],
            "e4m3": {k: low[k] for k in ("flipped", "margin", "state",
                                         "attn", "nll", "loss")},
            "against_other_stacks_attn_branch": against,
            "band_edges_rows_wrong": edges,
            "flash_tiles_visited_masked_skipped_by_kind": planned}),
            flush=True)
        window_name, yarn_name = list(others)[:2]
        return [
            ("no assignment dropped, and the held experts take their share "
             "of them (%d of %d experts: an even share is %.4f)"
             % (held, experts, held / experts),
             r["dropped"] == 0 and int(last["dropped"]) == 0
             and all(0.0 < s < 1.0 for s in r["held_share"]),
             "held share by layer %s (summed %.4f) -> %s"
             % (["%.4f" % s for s in r["held_share"]], sum(r["held_share"]),
                ["%.4f" % float(s) for s in last["held_share"]])),
            ("the system's top-%d sets differ from the float32 reference's "
             "own in few of the %d x %d layers and positions" % (
                 top_k, layers, length),
             r["flipped"] <= TOL_FLIPPED, "share %.4f, tol %.2f (by layer "
             "%s)" % (r["flipped"], TOL_FLIPPED,
                      ["%.3f" % f for f in r["flipped_by_layer"]])),
            ("and only at a near tie: the reference's %dth largest "
             "probability less the least one the system chose, over the "
             "former" % top_k, r["margin"] <= TOL_MARGIN,
             "largest %.3e, tol %.1e" % (r["margin"], TOL_MARGIN)),
            ("every block's state agrees with the plain reference (%d "
             "blocks, every position; the reference computes with the "
             "system's sets)" % layers, max(r["state"]) <= TOL_STATE,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["state"]], TOL_STATE)),
            ("every block's attention branch agrees with the plain "
             "reference (kinds %s)" % "".join(k[0] for k in kinds),
             max(r["attn"]) <= TOL_ATTN,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["attn"]], TOL_ATTN)),
            ("window_matters: the reference with every layer full is "
             "refused by a window layer's attention branch",
             against[window_name] > TOL_ATTN, "rel err %.3e, tol %.1e"
             % (against[window_name], TOL_ATTN)),
            ("yarn_matters: the reference with the full layers on the plain "
             "rotation is refused by a full layer's attention branch",
             against[yarn_name] > TOL_ATTN, "rel err %.3e, tol %.1e"
             % (against[yarn_name], TOL_ATTN)),
            ("references of another stack are refused by a full layer's "
             "attention branch: the full layers under the window; YaRN's "
             "frequencies with its factor left off cos and sin",
             all(e > TOL_ATTN for e in against.values()),
             "rel err %s, tol %.1e"
             % (["%.3e" % e for e in list(against.values())[2:]], TOL_ATTN)),
            ("the band's edges, through the timed kernels at the timed "
             "shape: every query's oldest and newest key, every key's "
             "earliest and latest viewer (window %d; the full layers' "
             "oldest is key 0)" % window,
             not any(sum(n) for n in edges.values()),
             "rows that decode wrong %s" % edges),
            ("flash_plan counts the tiles the masks have, by kind and "
             "kernel", counted == planned,
             "visited, masked, skipped: plan %s, mask %s"
             % (planned, counted)),
            ("system cross-entropy agrees with the plain reference row by "
             "row (the rows' own losses of the chunked call)",
             r["nll"] <= TOL_NLL,
             "max abs err %.3e nats, tol %.2f" % (r["nll"], TOL_NLL)),
            ("system loss (mean cross-entropy + %g x balancing) agrees with "
             "the plain reference" % w_balance, r["loss"] <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (r["loss_system"], r["loss_reference"], r["loss"], TOL_LOSS)),
            ("the step's first loss agrees with the reference",
             err_step <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, r["loss_reference"], err_step, TOL_LOSS)),
            ("the first step's gradient has the reference's norm, parameter "
             "group by group", r["grad_norm"] <= TOL_GRAD_NORM,
             "system, reference: %s; worst rel %.3e, tol %.0e"
             % ({k: ["%.4e" % x for x in v]
                 for k, v in r["grad_norms"].items()}, r["grad_norm"],
                TOL_GRAD_NORM)),
            ("the first step's gradient agrees with the reference's leaf by "
             "leaf where the flash kernels write it (the four attention "
             "matrices of layers %s)" % compared,
             r["grad_leaf"] <= TOL_GRAD_LEAF,
             "worst leaf's |sys - ref| / |ref| %.3e, tol %.1e"
             % (r["grad_leaf"], TOL_GRAD_LEAF)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, attention branch %s, nll %.3e"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]],
                ["%.3e" % e for e in low["attn"]], low["nll"])),
        ]

    # The blocks under `block_remat` (the first k) run their forward twice:
    # its kernels execute (and their time is in the trace) twice too.
    calls = {kind: kinds.count(kind) for kind in rules}
    again = {kind: sum(1 for i, k in enumerate(kinds)
                       if k == kind and i < cfg.block_remat)
             for kind in rules}
    itemsize = jnp.dtype(job["compute_dtype"]).itemsize
    by_kind = {kind: {
        "executed_flops": sum(
            n * flops_mellum.flash_executed_flops(which, head_dim)
            for n, which in ((calls[kind], plans[kind]),
                             (again[kind], forward_plans[kind]))),
        "min_bytes": sum(
            n * flops_mellum.flash_min_bytes(which, 1, heads, kv_heads,
                                             length, head_dim, itemsize)
            for n, which in ((calls[kind], plans[kind]),
                             (again[kind], forward_plans[kind]))),
        "layers": calls[kind], "forward_again": again[kind]}
        for kind in rules}
    rows = top_k * length * held / experts  # expected on the held experts
    gmm = layers + cfg.block_remat / 3.0  # a forward is 3 of the 9
    sizes = (hidden, heads, kv_heads, head_dim, expert_width, experts, held)
    counts = {
        "model_flops_per_item": flops_mellum.model_flops_per_token(
            *sizes, top_k, vocab, kinds, length, window),
        # per step and per device, by the tiles `flash_plan` says each
        # kernel visits, a kind of layer at a time
        "flash_kernels": sorted(set().union(*plans.values())),
        "flash_tiles": {kind: {name: [p.tiles_visited, p.tiles_masked,
                                      p.tiles_skipped]
                               for name, p in by_kernel.items()}
                        for kind, by_kernel in plans.items()},
        "flash_by_kind": by_kind,
        "moe_gmm_executed_flops": gmm
        * flops_moe.gated_experts_flops(rows, hidden, expert_width),
        "moe_gmm_min_bytes": gmm
        * flops_moe.gated_experts_min_bytes(
            rows, hidden, expert_width, held, itemsize=itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_mellum.params(*sizes, vocab, layers),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify}
