"""Builder for LFM2-MoE configurations (LiquidAI, `model_type` `lfm2_moe`):
`models.Transformer` with a mixer KIND a layer (`attention_types`): a
double-gated short convolution as a mixer of its own ("conv":
`GatedShortConv`, the pass between its projections `ops.sconv.gated_conv`)
or grouped-query attention under the causal triangle ("full") at head width
64 with a norm a head and the whole head on the plain rotation; leading
dense layers, then routed layers of sigmoid-scored experts of which this
chip HOLDS a part and no shared one; the head tied to the embedding
(`tie_embeddings`); trained through `parallel.make_train_step` on the next
token's cross-entropy (the chunked vocabulary loss on the table transposed;
no balancing term), AdamW inside a linear warm-up.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/lfm2.py` outside the
window, a sequence of the batch at a time.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, two sequences of 8192, the modules' own draws (my chip runs, PR 65:
# the benchmark's own runs, a seed each; PERF.md s6 has the seeds). As
# `builders/laguna.py`: the reference computes every routed layer with the
# experts the SYSTEM's top-4 chose
# (`references/lfm2.py::routing_weights(follow=)`) and says how often its own
# top-4 differs and how near a tie each such choice was, so EVERY position is
# compared and the two gradients are of the same function. Each limit that
# guards the PRECISION lies between two readings: the largest the bf16 system
# gave over its seeds, and the least the same system gave with every matrix
# rounded to fp8's precision (e4m3), which `verify` computes in every run and
# which has to come out as not correct (four seeds):
#   routed layers x positions whose top-4 set is not the reference's own:
#     bf16 0.078..0.080, fp8 0.616..0.618.
#   how far from a tie the farthest such choice lies (the reference's 4th
#     largest score less the least one chosen, over the former): bf16
#     0.041..0.048, fp8 0.39..0.53.
#   a block's state, every position of both sequences, max |sys - ref| /
#     max |ref|, the worst block: bf16 0.031..0.037 (it grows a block at a
#     time from 0.009), fp8 0.326..0.345.
#   a block's mixer branch (the output projection's result before the
#     residual add), likewise, BY KIND of layer: a conv mixer's bf16
#     0.050..0.056 (the last block's; 0.007 in block 0), fp8 0.50..0.55; an
#     attention's bf16 0.0195..0.0216, fp8 0.20..0.29. The least any
#     reference of another model read is rotary at base 10000, 0.23..0.38
#     against the attention's limit; the conv variants read 1.4..2.1.
#   the logits of 64 sampled rows a sequence, likewise: bf16 0.033..0.035,
#     fp8 0.325..0.352.
#   the first gradient, a leaf of each kind of parameter (the first conv
#     layer's `in_proj`, taps and `out_proj`, the first attention layer's
#     `query` and `q_norm`, a dense layer's `mlp_gate`, the first routed
#     layer's router and held `w_gate`, the table), |sys - ref|_2 /
#     |ref|_2, the worst leaf: bf16 0.111..0.123 (the router; the held
#     experts 0.100, every other leaf 0.066..0.075), fp8 its worst
#     0.80..0.82 and its LEAST leaf 0.47..0.52; the router's gradient under
#     softmax scores 0.82.
TOL_FLIPPED = 0.3          # share of routed layers x positions that differ
TOL_MARGIN = 1.2e-1        # how far from a tie a differing choice may lie
TOL_STATE = 1e-1           # max |system - reference| / max |reference|
TOL_MIXER = {"conv": 1.5e-1, "full": 6e-2}  # likewise, a mixer branch by kind
TOL_LOGITS = 1e-1          # likewise, the sampled rows' logits
TOL_GRAD_LEAF = 3e-1       # a picked leaf's |system - reference|_2 / |ref|_2
# These guard something else:
#   a row's cross-entropy, nats: bf16 0.121..0.143, fp8 1.36..1.40: a
#     maximum over 2 x 8192 rows; the limit holds the rows' targets: a head
#     that reads the wrong token is several nats off.
#   mean loss, relative: bf16 2.9e-6..2.4e-5, fp8 1.9e-5..1.5e-4: a mean near
#     ln(vocab) over 16384 rows averages the precision away; the limit is
#     the harness's accepted cells' (eighty times the largest seen) and
#     holds the loss's ASSEMBLY (targets, the mean over both sequences).
TOL_NLL = 0.4              # max |system - reference|, a row's nats
TOL_LOSS = 2e-3            # relative, the mean loss

KINDS = {"conv": "conv", "full_attention": "full"}
LOGIT_ROWS = 64            # rows of a sequence whose logits are compared


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_lfm2
    from benchmark.builders.sdar import rounded_to_e4m3
    from benchmark.references import lfm2 as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import BandMask
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step, routing_stats

    job = config["job"]
    hidden = config["hidden_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim = hidden // heads  # the config has no head_dim key
    layers = config["num_hidden_layers"]
    experts = config["published_num_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_tok"]
    width, expert_width = (config["intermediate_size"],
                           config["moe_intermediate_size"])
    vocab = config["vocab_size"]
    eps = config["norm_eps"]
    taps = config["conv_L_cache"]
    kinds = tuple(KINDS[t] for t in config["layer_types"])
    dense_layers = config["num_dense_layers"]
    routed_layers = layers - dense_layers
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq % mesh.size:
        raise ValueError("batch %d does not divide over %d chips"
                         % (n_seq, mesh.size))
    if (len(kinds) != layers or held != config["num_experts"]
            or config["model_type"] != "lfm2_moe" or config["conv_bias"]
            or not config["norm_topk_prob"] or not config["use_expert_bias"]
            or not 0 < dense_layers < layers or "full" not in kinds
            or "conv" not in kinds):
        raise ValueError("this builder makes a conv or an attention mixer a "
                         "layer, leading dense layers then routed ones of "
                         "renormalised sigmoid scores with a selection bias, "
                         "no bias on a convolution, a tied head")
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, embed_dim=hidden,
        mlp_dim=width, mlp_gated=True,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], attention_types=kinds, qk_norm="head",
        rope_base=float(config["rope_theta"]), norm_eps=eps, conv_taps=taps,
        tie_embeddings=True, moe_experts=experts, moe_every=1,
        first_k_dense=dense_layers, moe_dim=expert_width, moe_top_k=top_k,
        moe_capacity_factor=None, moe_gated=True, moe_renormalize=True,
        moe_scoring="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_held=(first, held), block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"kinds": kinds, "dense": dense_layers, "eps": eps,
            "rope_theta": config["rope_theta"], "top_k": top_k,
            "route_scale": config["routed_scaling_factor"],
            "held": (first, held)}
    chunk = min(int(job["loss_chunk"]), length)
    # The rate climbs from 0 to `learning_rate` over `warmup_steps`: a window
    # at the start of a job is inside the warm-up (the file says why).
    opt = optax.adamw(optax.linear_schedule(0.0, job["learning_rate"],
                                            int(job["warmup_steps"])),
                      b1=job["adam_b1"], b2=job["adam_b2"],
                      eps=job["adam_eps"], weight_decay=job["weight_decay"])
    logit_rows = np.linspace(0, length - 1, min(LOGIT_ROWS, length)).astype(
        np.int32)

    def head_of(params):
        """The loss's kernel: the embedding table transposed (the head IS
        the embedding; the table's gradient is the lookup's + the head's)."""
        return params["embed"]["embedding"].T

    def objective(params, tokens, weights=None, capture=False):
        """(the loss of sequences `tokens` [B, L] (`weights`: a weight a row
        in place of the mean), the model's collections, the normed last
        state)."""
        hid, state = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["intermediates"],
            **({"capture_intermediates": lambda mdl, name: isinstance(
                mdl, (transformer.Block, transformer.Attention,
                      transformer.GatedShortConv))
                and name == "__call__"} if capture else {}))
        return chunked_softmax_cross_entropy(
            hid, head_of(params), jnp.roll(tokens, -1, axis=1),
            chunk=chunk, weights=weights), state["intermediates"], hid

    def loss_fn(params, batch):
        return objective(params, batch["x"])[0]

    def forward(params, tokens):
        """Of the step's batch [B, L], as the timed step sees it: every
        block's output and every block's mixer branch [layers, B, L, C] in
        the compute dtype, the logits of `logit_rows` of each sequence as
        the head forms them, each row's own cross-entropy [B, L] (the
        gradient of the weighted loss by a row's weight, as the timed loss
        forms it), the loss, and the step's counters."""
        loss, inter, hid = objective(params, tokens, capture=True)
        blocks = [inter["block_%d" % i] for i in range(layers)]
        states = jnp.stack([b["__call__"][0] for b in blocks])
        mixer = jnp.stack([b["attn"]["__call__"][0] for b in blocks])
        logits = jnp.dot(hid[:, logit_rows],
                         head_of(params).astype(hid.dtype),
                         preferred_element_type=jnp.float32)
        nll = jax.grad(lambda w: objective(params, tokens, w)[0])(
            jnp.zeros(tokens.shape, jnp.float32))
        routing = routing_stats(inter)
        return (states, mixer, logits, nll, loss,
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "held_rows_largest": jnp.max(
                     routing["assignments"][:, first:first + held], axis=1),
                 "dropped": routing["dropped"]})

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    def init_params(key):
        """The modules' own draws: this stack needs no seeded state (the
        configuration's `assumed` has the held shares that say so)."""
        return model.init(key, jnp.zeros((1, length), jnp.int32))["params"]

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

    # The gradient is held leaf by leaf, one leaf of each kind of parameter:
    # the first conv layer's `in_proj`, taps and `out_proj` (what the gated
    # pass's backward writes), the first attention layer's `query` (where
    # the flash backward writes dq) and its `q_norm`, a dense layer's
    # `mlp_gate`, of the first routed layer the router and the held experts'
    # `w_gate`, and the ONE table of embedding and head.
    conv_block = "block_%d" % kinds.index("conv")
    full_block = "block_%d" % kinds.index("full")
    routed = "block_%d" % dense_layers

    def picked(grads):
        conv, full = grads[conv_block]["attn"], grads[full_block]["attn"]
        moe = grads[routed]["moe_mlp"]
        return {
            conv_block + "/in_proj (conv)": conv["in_proj"]["kernel"],
            conv_block + "/conv_kernel (conv)": conv["conv_kernel"],
            conv_block + "/out_proj (conv)": conv["out_proj"]["kernel"],
            full_block + "/query (full)": full["query"]["kernel"],
            full_block + "/q_norm (full)": full["q_norm"]["scale"],
            "block_0/mlp_gate (a dense layer)":
            grads["block_0"]["mlp_gate"]["kernel"],
            routed + "/router": moe["router"],
            routed + "/w_gate (the held experts)": moe["w_gate"],
            "embed (the table: lookup + head)": grads["embed"]["embedding"]}

    # One program for a system's forward AND its picked gradient leaves.
    system = jax.jit(lambda p, tokens: forward(p, tokens) + (picked(jax.grad(
        lambda q: objective(q, tokens)[0])(p)),))

    def _ref(p, seq, variant, follow):
        out = reference.forward(p, seq, arch, variant, follow)
        out["logits"] = out["logits"][logit_rows]
        return out

    ref_fn = jax.jit(_ref)
    ref_grad = jax.jit(lambda p, seq, variant, follow: picked(
        reference.gradient(p, seq, arch, variant, follow)))

    def chosen_sets(counters):
        """[B, routed layers, L, E] bool from the system's chosen indices
        ([routed layers, B L, k], the batch's tokens sequence by
        sequence)."""
        sets = jnp.any(jax.nn.one_hot(counters["chosen"], experts,
                                      dtype=jnp.bool_), axis=-2)
        return jnp.moveaxis(sets.reshape(routed_layers, n_seq, length,
                                         experts), 1, 0)

    def err(ours, theirs):
        """max |ours - theirs| / max |theirs| by the leading axis."""
        ours = jnp.asarray(ours, jnp.float32)
        axes = tuple(range(1, ours.ndim))
        return [float(v) for v in jnp.max(jnp.abs(ours - theirs), axis=axes)
                / jnp.max(jnp.abs(theirs), axis=axes)]

    def leaf_err(grads, ref_grads):
        return {n: float(jnp.linalg.norm(grads[n] - ref_grads[n])
                         / jnp.linalg.norm(ref_grads[n]))
                for n in sorted(grads)}

    def reference_gradient(params, tokens, follow, variant):
        """The reference's picked leaves of the batch's mean loss: the mean
        of its sequences' gradients, a sequence at a time."""
        total = None
        for b in range(n_seq):
            with jax.default_matmul_precision("highest"):
                g = ref_grad(params, tokens[b], variant, follow[b])
            total = g if total is None else jax.tree_util.tree_map(
                jnp.add, total, g)
        return jax.tree_util.tree_map(lambda t: t / n_seq, total)

    # A variant of ANOTHER model is read where it changes the stack
    # (`reference.CHANGES`): a mixer's variant by the mixer branches of the
    # kind of layer it changes, a routing variant by the first routed
    # layer's router's gradient; on the batch's first sequence.
    routing_variants = {name: v for name, v in reference.VARIANTS.items()
                        if reference.CHANGES[v] == "routing"}
    mixer_variants = {name: v for name, v in reference.VARIANTS.items()
                      if name not in routing_variants}

    def readings(seen, params, tokens, ref_grads, other_models=False):
        """How far a system's forward and picked gradient leaves `seen`
        (`system(...)` of its parameters on `tokens` [B, L], on the host)
        are from the reference on `params`, a sequence at a time (the worst
        sequence's reading of each): the errors the limits above are set on
        and both sides' numbers. The reference computes every routed layer
        with the experts the SYSTEM chose and says how often its own top-k
        differs and how near a tie each such choice was; `ref_grads` are its
        picked leaves on the same sets. `other_models`: also the same system
        against the reference's variants, other models, each of which the
        comparison must refuse."""
        states, mixer, logits, nll, loss, counters, grads = seen
        follow = chosen_sets(counters)
        out = {"loss_system": float(loss), "grad": leaf_err(grads, ref_grads),
               "held_share": [float(v) for v in counters["held_share"]],
               "held_rows_largest": [int(v) for v in
                                     counters["held_rows_largest"]],
               "dropped": int(counters["dropped"])}
        per_seq = []
        for b in range(n_seq):
            with jax.default_matmul_precision("highest"):
                ref = ref_fn(params, tokens[b], reference.AS_PUBLISHED,
                             follow[b])
            flipped = jnp.any(follow[b] != ref["chosen"], axis=2)
            per_seq.append({
                "loss_reference": float(ref["loss"]),
                "flipped_by_layer": jnp.mean(flipped, axis=1),
                "margin": float(jnp.max(ref["margin"])),
                "state": err(states[:, b], ref["states"]),
                "mixer": err(mixer[:, b], ref["mixer"]),
                "logits": max(err(logits[b][None], ref["logits"][None])),
                "nll": float(jnp.max(jnp.abs(nll[b] - ref["nll"]))),
                "held_rows": ref["held_rows"]})
            del ref

        def worst(key):  # by block, the worst sequence's
            return [max(vals) for vals in zip(*(s[key] for s in per_seq))]

        by_layer = sum(s["flipped_by_layer"] for s in per_seq) / n_seq
        out.update({
            "loss_reference": sum(s["loss_reference"] for s in per_seq)
            / n_seq,
            "flipped": float(jnp.mean(by_layer)),
            "flipped_by_layer": [float(v) for v in by_layer],
            "margin": max(s["margin"] for s in per_seq),
            "state": worst("state"), "mixer": worst("mixer"),
            "logits": max(s["logits"] for s in per_seq),
            "nll": max(s["nll"] for s in per_seq),
            "held_rows_reference": [int(v) for v in sum(
                s["held_rows"] for s in per_seq)]})
        out["mixer_by_kind"] = {kind: max(e for e, k in zip(out["mixer"],
                                                            kinds)
                                          if k == kind) for kind in set(kinds)}
        out["loss"] = abs(out["loss_system"] - out["loss_reference"]) \
            / abs(out["loss_reference"])
        if other_models:
            out["against"] = {}
            for name, variant in mixer_variants.items():
                with jax.default_matmul_precision("highest"):
                    theirs = ref_fn(params, tokens[0], variant,
                                    follow[0])["mixer"]
                out["against"][name] = max(
                    e for e, k in zip(err(mixer[:, 0], theirs), kinds)
                    if k == reference.CHANGES[variant])
                del theirs
            for name, variant in routing_variants.items():
                theirs = reference_gradient(params, tokens, follow, variant)
                out["against"][name] = leaf_err(grads, theirs)[
                    routed + "/router"]
                del theirs
        return out

    def refused(r):
        """The limits of the precision that `r` breaks."""
        return [name for name, bad in (
            ("flipped", r["flipped"] > TOL_FLIPPED),
            ("margin", r["margin"] > TOL_MARGIN),
            ("state", max(r["state"]) > TOL_STATE),
            ("mixer", any(e > TOL_MIXER[k]
                          for k, e in r["mixer_by_kind"].items())),
            ("logits", r["logits"] > TOL_LOGITS),
            ("grad", max(r["grad"].values()) > TOL_GRAD_LEAF)) if bad]

    group = heads // kv_heads
    # the plans of the cell's ONE flash call, per device (the batch's
    # sequences of this chip side by side), with the triangle's tiles
    # counted as a band of the whole sequence
    per_chip = n_seq // mesh.size
    forward_plan, backward_plan = (profile.flash_plan(
        per_chip, heads, length, head_dim, group, cfg.dtype, b,
        mask=BandMask(length)) for b in (False, True))
    plans = dict(forward_plan, **backward_plan)
    tiles = {name: [p.tiles_visited, p.tiles_masked, p.tiles_skipped]
             for name, p in plans.items()}
    plan_lines = {name: {
        "path": p.path, "held": p.held, "blocks": [p.block_q, p.block_k],
        "grid": list(p.grid), "resident_bytes": p.resident_bytes,
        "vmem_bytes": p.vmem_bytes,
        "tiles_visited_masked_skipped": tiles[name]}
        for name, p in plans.items()}

    # The blocks under `block_remat` (the first k) run their forward twice:
    # its kernels execute (and their time is in the trace) twice too.
    conv_layers, full_layers = kinds.count("conv"), kinds.count("full")
    again = {kind: sum(1 for i, k in enumerate(kinds)
                       if k == kind and i < cfg.block_remat)
             for kind in ("conv", "full")}
    itemsize = jnp.dtype(job["compute_dtype"]).itemsize
    tokens_a_chip = per_chip * length
    rows = top_k * tokens_a_chip * held / experts  # expected, a layer
    routed_again = max(0, cfg.block_remat - dense_layers)
    gmm = routed_layers + routed_again / 3.0  # a forward is 3 of the 9

    def gmm_counts(layer_rows):
        """The grouped matmuls' operations and least bytes over a step, on
        `layer_rows` rows a routed layer."""
        return {
            "moe_gmm_executed_flops": gmm * flops_lfm2.gated_experts_flops(
                layer_rows, hidden, expert_width),
            "moe_gmm_min_bytes": gmm * flops_lfm2.gated_experts_min_bytes(
                layer_rows, hidden, expert_width, held, itemsize=itemsize,
                matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
            "moe_gmm_rows_a_layer": layer_rows}

    sizes = (hidden, heads, kv_heads, head_dim, width, expert_width, experts,
             held)
    counts = {
        "model_flops_per_item": flops_lfm2.model_flops_per_token(
            *sizes, top_k, vocab, kinds, dense_layers, length),
        # per step and per device, by the tiles `flash_plan` says each
        # kernel visits
        "flash_kernels": sorted(plans),
        "flash_tiles": {"full": tiles},
        "flash_plan": plan_lines,
        "flash_by_kind": {"full": {
            "executed_flops": sum(
                n * flops_lfm2.flash_executed_flops(which, head_dim)
                for n, which in ((full_layers, plans),
                                 (again["full"], forward_plan))),
            "min_bytes": sum(
                n * flops_lfm2.flash_min_bytes(which, per_chip, heads,
                                               kv_heads, length, head_dim,
                                               itemsize)
                for n, which in ((full_layers, plans),
                                 (again["full"], forward_plan))),
            "layers": full_layers, "forward_again": again["full"]}},
        "sconv_plan": profile.sconv_plan(per_chip, length, hidden, taps,
                                         cfg.dtype),
        "sconv_gate_min_bytes": flops_lfm2.gate_step_min_bytes(
            tokens_a_chip, hidden, taps, conv_layers, again["conv"],
            itemsize),
        "params": flops_lfm2.params(*sizes, taps, vocab, kinds,
                                    dense_layers),
    }
    # on the rows EXPECTED until `verify` has the run's own count
    counts.update(gmm_counts(rows))

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, the largest held expert's rows, nothing
        dropped; the grouped matmuls' counts in `counts` are put on the rows
        they COUNTED), then from the seed again the system's forward, loss
        and first gradient on the step's own batch (the gated pass, the
        causal flash kernels at group 4 and head width 64, the norm a head,
        grouped matmul over the held experts, the chunked loss on the table
        transposed, bf16) against the plain reference, a sequence at a time:
        every block's state and mixer branch, the sampled rows' logits, the
        routing, each row's cross-entropy, the loss, a gradient leaf of each
        kind of parameter; the train step's first loss against the
        reference's; the same system on e4m3 matrices and the reference's
        variants of another model, all of which the comparison must
        refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            last = jax.device_get(system(final_params, tokens)[5])
            del final_params
            # The caller still holds the trained parameters (3.9 GB), so
            # ONE more set at a time, and a system's results (1.4 GB in the
            # compute dtype) wait on the HOST: the system on e4m3 matrices
            # first, from the seed in one program, then the seeded
            # parameters themselves; then the reference's gradient on the
            # bf16 system's sets; then the reference's forwards, a
            # sequence and a system's results at a time.
            low_seen = jax.device_get(system(jax.jit(
                lambda key: rounded_to_e4m3(init_params(key)))(k_param),
                tokens))
            params = jax.jit(init_params)(k_param)
            seen = jax.device_get(system(params, tokens))
            ref_grads = reference_gradient(params, tokens,
                                           chosen_sets(seen[5]),
                                           reference.AS_PUBLISHED)
            r = readings(seen, params, tokens, ref_grads, other_models=True)
            del seen
            low = readings(low_seen, params, tokens, ref_grads)
            del params, ref_grads, low_seen
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        counted = float(sum(last["held_share"])) / routed_layers \
            * top_k * tokens_a_chip
        counts.update(gmm_counts(counted))
        against = r["against"]
        limit = {name: TOL_MIXER[reference.CHANGES[mixer_variants[name]]]
                 if name in mixer_variants else TOL_GRAD_LEAF
                 for name in against}
        print("INFO " + json.dumps({
            "held_share_first_step": r["held_share"],
            "held_share_first_step_summed": sum(r["held_share"]),
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "held_share_after_the_window_summed": float(
                sum(last["held_share"])),
            "largest_held_experts_rows_first_step": r["held_rows_largest"],
            "largest_held_experts_rows_after_the_window": [
                int(v) for v in last["held_rows_largest"]],
            "held_rows_reference_by_layer": r["held_rows_reference"],
            "moe_gmm_rows_a_layer_counted_after_the_window": counted,
            "moe_gmm_rows_a_layer_expected": rows,
            "flipped_share_of_layers_x_positions": r["flipped"],
            "flipped_by_layer": r["flipped_by_layer"],
            "flipped_margin": r["margin"],
            "state_err_by_block": r["state"],
            "mixer_branch_err_by_block": r["mixer"],
            "mixer_branch_err_by_kind": r["mixer_by_kind"],
            "logits_err_sampled_rows": r["logits"],
            "nll_err": r["nll"], "loss_err": r["loss"],
            "grad_err_by_leaf": r["grad"],
            "e4m3": {k: low[k] for k in ("flipped", "margin", "state",
                                         "mixer", "logits", "nll", "loss",
                                         "grad")},
            "against_other_models": against}), flush=True)
        return [
            ("no assignment dropped, and the held experts take their share "
             "of them (%d of %d experts: an even share is %.4f)"
             % (held, experts, held / experts),
             r["dropped"] == 0 and int(last["dropped"]) == 0
             and all(0.0 < s < 1.0 for s in r["held_share"]),
             "held share by routed layer %s (summed %.4f) -> %s; the "
             "largest held expert's rows %s -> %s"
             % (["%.4f" % s for s in r["held_share"]], sum(r["held_share"]),
                ["%.4f" % float(s) for s in last["held_share"]],
                r["held_rows_largest"],
                [int(v) for v in last["held_rows_largest"]])),
            ("the system's top-%d sets differ from the float32 reference's "
             "own in few of the %d x %d x %d routed layers and positions" % (
                 top_k, routed_layers, n_seq, length),
             r["flipped"] <= TOL_FLIPPED, "share %.4f, tol %.2f (by layer "
             "%s)" % (r["flipped"], TOL_FLIPPED,
                      ["%.3f" % f for f in r["flipped_by_layer"]])),
            ("and only at a near tie: the reference's %dth largest score "
             "less the least one the system chose, over the former" % top_k,
             r["margin"] <= TOL_MARGIN,
             "largest %.3e, tol %.1e" % (r["margin"], TOL_MARGIN)),
            ("every block's state agrees with the plain reference (%d "
             "blocks, every position of both sequences; the reference "
             "computes with the system's sets)" % layers,
             max(r["state"]) <= TOL_STATE,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["state"]], TOL_STATE)),
            ("every block's mixer branch agrees with the plain reference "
             "(kinds %s: the gated convolution of %d taps; %d heads on %d "
             "at width %d, the norm a head, the rotation)"
             % ("".join(k[0] for k in kinds), taps, heads, kv_heads,
                head_dim),
             all(e <= TOL_MIXER[k] for k, e in r["mixer_by_kind"].items()),
             "max rel err by block %s, by kind %s, tol %s"
             % (["%.3e" % e for e in r["mixer"]],
                {k: "%.3e" % e for k, e in r["mixer_by_kind"].items()},
                TOL_MIXER)),
            ("the logits of %d sampled rows a sequence agree with the plain "
             "reference (the head is the table)" % len(logit_rows),
             r["logits"] <= TOL_LOGITS,
             "max rel err %.3e, tol %.1e" % (r["logits"], TOL_LOGITS)),
            ("system cross-entropy agrees with the plain reference row by "
             "row (the rows' own losses of the chunked call)",
             r["nll"] <= TOL_NLL,
             "max abs err %.3e nats, tol %.2f" % (r["nll"], TOL_NLL)),
            ("system loss (mean cross-entropy over both sequences) agrees "
             "with the plain reference", r["loss"] <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (r["loss_system"], r["loss_reference"], r["loss"], TOL_LOSS)),
            ("the step's first loss agrees with the reference",
             err_step <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, r["loss_reference"], err_step, TOL_LOSS)),
            ("the first step's gradient agrees with the reference's, a leaf "
             "of each kind of parameter", max(r["grad"].values())
             <= TOL_GRAD_LEAF, "|sys - ref| / |ref| by leaf %s, tol %.1e"
             % ({k: "%.3e" % v for k, v in r["grad"].items()},
                TOL_GRAD_LEAF)),
            ("references of another model are refused, each by the limit of "
             "what it changes (a mixer branch; the router's gradient)",
             all(e > limit[name] for name, e in against.items()),
             "rel err %s; limits %s (a mixer branch, by kind), %.1e "
             "(gradient)" % ({k: "%.3e" % v for k, v in against.items()},
                             TOL_MIXER, TOL_GRAD_LEAF)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, mixer branch %s, logits %.3e, nll %.3e, "
             "gradient %s"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]],
                ["%.3e" % e for e in low["mixer"]], low["logits"],
                low["nll"], {k: "%.3e" % v for k, v in low["grad"].items()})),
        ]

    print("INFO " + json.dumps({"flash_plan": plan_lines,
                                "sconv_plan": counts["sconv_plan"]}),
          flush=True)
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "system": system, "init_params": init_params,
            "make_tokens": make_tokens}
