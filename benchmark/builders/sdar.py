"""Builder for SDAR configurations: `models.Transformer` as Qwen3-MoE's layer
(grouped-query attention with a norm over each head's q and k, softmax-routed
gated experts of which this chip HOLDS a part, no shared one) under
BLOCK-DIFFUSION training: every step runs the stack once on a noisy and a
clean copy of the sequence side by side (`models.block_diffusion_batch`),
under the block mask the flash kernels take by rule
(`ops.BlockDiffusionMask`), with a 1/t-weighted loss over the masked rows of
the noisy half (`ops.losses.chunked_softmax_cross_entropy(weights=)`) plus
the router's balancing term over all 2L positions; trained through
`parallel.make_train_step` with AdamW.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns, and `readings`, the comparison itself, for
`examples/ouro_reference_sweep.py --workload sdar30b_1chip`; `verify` holds
the system to `references/sdar.py` outside the window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, one data sequence of 4096 (8192 positions), block 4, the seeded
# state of the configuration (my chip runs, PR 38: a sweep of six seeds with
# the builder's own `readings` and `verify`, then six of the benchmark's runs
# on six more; PERF.md s6). The reference computes every layer with the
# experts the SYSTEM's top-8 chose (`references/sdar.py::routed_ffn(follow=)`)
# and says how often its own top-8 differs and how near a tie each such choice
# was, so EVERY position is compared. Each limit that guards the PRECISION
# lies between two readings: the largest the bf16 system gave, and the least
# the same system gave with every matrix rounded to fp8's precision (e4m3),
# which has to come out as not correct, and does in every run (`verify`
# computes it) by each of them:
#   layers x positions whose top-8 set is not the reference's own: bf16
#     0.202..0.208, fp8 0.889..0.895 (a fifth of all choices lie within bf16's
#     reach of a tie: one sharp attention block's 3e-2 on the stream).
#   how far from a tie the farthest such choice lies (the reference's 8th
#     largest probability less the least one chosen, over the former): bf16
#     0.122..0.153, fp8 0.678..0.780. A set not chosen by probability reads
#     near 1.
#   a block's state on both halves, every position, max |sys - ref| / max
#     |ref|: bf16 3.3e-2..4.1e-2 in every block (the first block's sharp
#     attention sets it, the seven smooth ones pass it on), fp8 0.27..0.33.
#   a masked row's cross-entropy, nats: bf16 0.105..0.144, fp8 0.82..1.20. It
#     also holds the rows' targets: a head that reads the wrong token is
#     several nats off.
#   the first gradient leaf by leaf where the mask-ruled kernels write it
#     (the first, recomputed, and the last block's q, k, v and output
#     matrices; worst leaf's |sys - ref|_2 / |ref|_2): bf16 5.8e-2..7.0e-2,
#     fp8 0.46..0.63; the reference under the causal mask 1.2..1.3, without
#     the clean half 1.8..2.5: a wrong dK/dV on the cut tiles is a leaf's
#     matter, and the limit lies under the least of the three.
# Two do NOT separate the precisions and guard something else:
#   the first gradient's l2 norm, relative: bf16 1.2e-3..1.8e-2, fp8
#     1.5e-3..0.12, the causal mask 2.7e-2..0.22, without the clean half
#     0.31..0.69. A norm over 835 M entries averages; it holds the
#     gradient's SCALE (a term left out, a weight a row applied twice).
#   mean loss with its balancing term, relative: bf16 1.7e-6..2.0e-4, fp8
#     1.0e-4..1.6e-3. A mean over 2000 masked rows averages the precision
#     away; it holds the loss's ASSEMBLY: with weights 1 for 1 / t it is 1.0
#     off.
# What the comparison must refuse, and does (same runs): the reference under
# the causal mask is 1.09..1.37 from the system's states, with the clean half
# left out 1.33..1.54.
TOL_FLIPPED = 0.5          # share of layers x positions whose sets differ
TOL_MARGIN = 0.35          # how far from a tie a differing choice may lie
TOL_STATE = 1e-1           # max |system - reference| / max |reference|
TOL_NLL = 4e-1             # max |system - reference|, a masked row's nats
TOL_LOSS = 2e-3            # relative, the mean loss with its balancing term
TOL_GRAD_NORM = 5e-2       # relative, the first step's gradient's l2 norm
TOL_GRAD_LEAF = 2.5e-1     # a picked leaf's |system - reference|_2 / |ref|_2


def rounded_to_e4m3(params):
    """Every matrix rounded to e4m3's precision (four significant bits,
    round to nearest even) on the bits of its f32: a convert to
    `float8_e4m3fn` and back is folded away by the TPU's compiler."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        keep = jnp.uint32(0xFFF00000)             # 3 of 23 mantissa bits
        bits = (bits + jnp.uint32((1 << 19) - 1)
                + ((bits >> 20) & jnp.uint32(1))) & keep
        return jax.lax.bitcast_convert_type(bits, x.dtype)

    return jax.tree_util.tree_map(
        lambda x: rounded(x) if x.ndim >= 2 else x, params)


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_moe, flops_sdar
    from benchmark.references import sdar as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import BlockDiffusionMask
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
    block = int(traffic["block_length"])
    t_min = float(job["t_min"])
    mask_id = vocab - 1  # the slice's last id, never drawn as data
    w_balance = job["router_aux_loss_coef"]
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (held != config["num_experts"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1 or config["attention_bias"]
            or config["hidden_act"] != "silu" or config["use_sliding_window"]
            or config["rope_scaling"] is not None
            or config["tie_word_embeddings"]):
        raise ValueError("this builder makes routed layers only, SiLU, full "
                         "attention, plain rotary, no bias, an untied head")
    if 2 * length > config["max_position_embeddings"]:
        raise ValueError("2 x seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    rule = BlockDiffusionMask(length, block)
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, embed_dim=hidden,
        mlp_dim=config["intermediate_size"], moe_dim=expert_width,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rope_base=config["rope_theta"],
        qk_norm="head", attention_mask=rule, norm_eps=eps,
        moe_experts=experts, moe_every=1, moe_top_k=top_k,
        moe_capacity_factor=None, moe_gated=True,
        moe_renormalize=config["norm_topk_prob"], moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"num_layers": layers, "eps": eps,
            "rope_base": config["rope_theta"], "top_k": top_k,
            "held": (first, held), "block": block, "mask_id": mask_id,
            "t_min": t_min, "balance_weight": w_balance}
    chunk = min(int(job["loss_chunk"]), length)
    # The rate climbs from 0 to `learning_rate` over `warmup_steps`: a window
    # at the start of a job is inside the warm-up (the file says why).
    opt = optax.adamw(optax.linear_schedule(0.0, job["learning_rate"],
                                            int(job["warmup_steps"])),
                      b1=job["adam_b1"],
                      b2=job["adam_b2"], eps=job["adam_eps"],
                      weight_decay=job["weight_decay"])

    def objective(params, tokens, keys, weights=None, capture=False):
        """The loss of sequences `tokens` [B, L] under the noise of `keys`
        [B, 2] (`weights`: the rows' weights in place of the batch's own),
        with the doubled batch and the model's collections."""
        bd = models.block_diffusion_batch(keys, tokens, block, mask_id, t_min)
        hid, state = model.apply(
            {"params": params}, bd["ids"], bd["positions"],
            return_hidden=True, mutable=["intermediates"],
            **({"capture_intermediates": lambda mdl, name: isinstance(
                mdl, transformer.Block) and name == "__call__"}
               if capture else {}))
        ce = chunked_softmax_cross_entropy(
            models.block_diffusion_noisy_half(hid),
            params["lm_head"]["kernel"], bd["targets"], chunk=chunk,
            weights=bd["weights"] if weights is None else weights)
        balance, _ = router_aux_losses(state["intermediates"])
        return ce + w_balance * balance, (bd, state["intermediates"])

    def loss_fn(params, batch):
        return objective(params, batch["x"], batch["key"])[0]

    def forward(params, seq, key):
        """Of ONE sequence [L] under `key`: every block's output [layers,
        2L, C] f32, each noisy row's own cross-entropy [L] (the gradient of
        the weighted loss by a row's weight, as the timed loss forms it),
        the loss, and the step's counters."""
        x, keys = seq[None], key[None]
        loss, (bd, inter) = objective(params, x, keys, capture=True)
        states = jnp.stack([
            inter["block_%d" % i]["__call__"][0][0].astype(jnp.float32)
            for i in range(layers)])
        nll = jax.grad(lambda w: objective(params, x, keys, w)[0])(
            jnp.zeros((1, length), jnp.float32))[0]
        routing = routing_stats(inter)
        return (states, nll, loss,
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "dropped": routing["dropped"],
                 "bd": models.block_diffusion_stats(bd, t_min)})

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    drawn = config["seeded_state"]
    qk_first = float(drawn["first_block_qk_norm_scale"])
    out_gain = float(drawn["other_blocks_attention_out_gain"])

    def init_params(key):
        """The seeded state: flax's draws, but for what the configuration's
        `seeded_state` says and why (positions must be told apart, as a
        trained model tells them, or the router's own top-k sends them all
        to the same experts): the first block's two per-head QK-norm scales
        at `first_block_qk_norm_scale`, the other blocks' attention output
        matrices times `other_blocks_attention_out_gain`."""
        params = model.init(key, jnp.zeros((1, 2 * length), jnp.int32))[
            "params"]
        first = params["block_0"]["attn"]
        for name in ("q_norm", "k_norm"):
            first[name]["scale"] = jnp.full_like(first[name]["scale"],
                                                 qk_first)
        for i in range(1, layers):
            out = params["block_%d" % i]["attn"]["out"]
            out["kernel"] = out_gain * out["kernel"]
        return params

    def make_tokens(key):
        """Ids uniform over the slice WITHOUT its last id, the mask's."""
        return jax.random.randint(key, (n_seq, length), 0, vocab - 1,
                                  jnp.int32)

    def make_noise_keys(key):
        """The batch's noise: one key a sequence, from the tokens' key."""
        return jax.random.split(jax.random.fold_in(key, 1), n_seq)

    def make_state(kp, kt):
        params = init_params(kp)
        return params, opt.init(params), {"x": make_tokens(kt),
                                          "key": make_noise_keys(kt)}

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(mesh.axis_names[0]))
    make = jax.jit(make_state, out_shardings=(rep, rep, dat))
    state = (jax.eval_shape if abstract else lambda f, *a: f(*a))(
        make, k_param, k_tok)

    # The gradient is held leaf by leaf where the mask-ruled kernels write
    # it: the attention's four matrices of the first block (recomputed: its
    # forward kernel runs twice) and of the last.
    def picked(grads):
        return optax.global_norm(grads), {
            "block_%d/%s" % (i, name):
            grads["block_%d" % i]["attn"][name]["kernel"]
            for i in (0, layers - 1)
            for name in ("query", "key", "value", "out")}

    system = jax.jit(forward)
    system_grad = jax.jit(lambda p, seq, key: picked(jax.grad(
        lambda q: objective(q, seq[None], key[None])[0])(p)))
    ref_fn = jax.jit(lambda p, seq, key, variant, follow: reference.forward(
        p, seq, key, arch, variant, follow))
    ref_grad = jax.jit(lambda p, seq, key, variant, follow: picked(
        reference.gradient(p, seq, key, arch, variant, follow)))

    def chosen_sets(p, seq, key):
        """[layers, 2L, E] bool: the experts the system's top-k chose."""
        return jnp.any(jax.nn.one_hot(
            system(p, seq, key)[3]["chosen"], experts, dtype=jnp.bool_),
            axis=-2)

    def grad_readings(system_params, seq, key, ref):
        """The system's first gradient on `system_params` against each of
        `ref`, the reference's (norm, leaves) (`reference_grads`: its own,
        then where asked the causal mask's and the one without the clean
        half): [the norm's relative distance, the worst picked leaf's
        |sys - ref|_2 / |ref|_2] each, under the names below."""
        norm, leaves = system_grad(system_params, seq, key)
        out = {"grad_norm_system": float(norm),
               "grad_norm_reference": float(ref[0][0])}
        for name, theirs in zip(("grad", "grad_against_causal_mask",
                                 "grad_against_no_clean_half"), ref):
            out[name] = [
                abs(float(norm) - float(theirs[0])) / float(theirs[0]),
                max(float(jnp.linalg.norm(leaves[n] - theirs[1][n])
                          / jnp.linalg.norm(theirs[1][n])) for n in leaves)]
        out["grad_norm"], out["grad_leaf"] = out.pop("grad")
        return out

    def reference_grads(params, seq, key, follow, variants=(0, 1, 2)):
        """The reference's (norm, leaves) under its own mask, the causal
        one and the one without the clean half, on the sets `follow`."""
        with jax.default_matmul_precision("highest"):
            return [ref_grad(params, seq, key, v, follow) for v in variants]

    def readings(system_params, params, seq, key=None, others=True,
                 ref_grads=None):
        """How far the system on `system_params` is from the reference on
        `params`, one sequence `seq` [L] under the noise of `key`: the
        errors the limits above are set on and both sides' numbers. The
        reference computes every layer with the experts the SYSTEM chose
        and says how often its own top-k differs and how near a tie each
        such choice was (`references/sdar.py::routed_ffn`), so that every
        position is compared. With `others`, also the first gradient
        (`grad_readings`; `ref_grads`: `reference_grads` of the SAME
        system's sets) and the same system against references of ANOTHER
        model (the causal mask; the clean half left out; weights 1 for
        1 / t), which the comparison must refuse."""
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(0), 1)
        states, nll, loss, counters = system(system_params, seq, key)
        follow = jnp.any(jax.nn.one_hot(
            counters.pop("chosen"), experts, dtype=jnp.bool_), axis=-2)
        counters = jax.device_get(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, key, 0, follow)
            causal = ref_fn(params, seq, key, 1, follow)["states"] \
                if others else None
            no_clean = ref_fn(params, seq, key, 2, follow)["states"] \
                if others else None
        flipped = jnp.any(follow != ref["chosen"], axis=2)  # [layers, 2L]
        masked = ref["masked"]

        def state_err(theirs, rows=None):
            err = jnp.max(jnp.abs(states - theirs), axis=-1)  # [layers, 2L]
            if rows is not None:
                err = jnp.where(rows[None], err, 0.0)
            return [float(v) for v in jnp.max(err, axis=1)
                    / jnp.max(jnp.abs(theirs), axis=(1, 2))]

        loss, ref_loss = float(loss), float(ref["loss"])
        unit = float(ref["ce_unit_weights"]
                     + arch["balance_weight"] * ref["balance"])
        out = {
            "loss_system": loss, "loss_reference": ref_loss,
            "reference_loss_parts": {k: float(ref[k])
                                     for k in ("ce", "balance")},
            "flipped": float(jnp.mean(flipped)),
            "flipped_by_layer": [float(v) for v in jnp.mean(flipped, axis=1)],
            "flipped_positions": float(jnp.mean(jnp.any(flipped, axis=0))),
            "margin": float(jnp.max(ref["margin"])),
            "state": state_err(ref["states"]),
            "nll": float(jnp.max(jnp.where(
                masked, jnp.abs(nll - ref["nll"]), 0.0))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "dropped": int(counters["dropped"]),
            "bd": {k: v.tolist() for k, v in counters["bd"].items()},
            "masked_reference": int(jnp.sum(masked))}
        if others:
            out.update(
                grad_readings(system_params, seq, key, ref_grads
                              or reference_grads(params, seq, key, follow)),
                against_causal_mask=max(state_err(causal)),
                against_no_clean_half=max(state_err(
                    no_clean, jnp.arange(2 * length) < length)),
                against_unit_weights=abs(loss - unit) / abs(unit))
        return out

    def refused(r):
        """The limits of the precision that `r` breaks."""
        return [name for name, bad in (
            ("flipped", r["flipped"] > TOL_FLIPPED),
            ("margin", r["margin"] > TOL_MARGIN),
            ("state", max(r["state"]) > TOL_STATE),
            ("nll", r["nll"] > TOL_NLL),
            ("grad_leaf", r["grad_leaf"] > TOL_GRAD_LEAF)) if bad]

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, nothing dropped), then from the seed again
        the system's forward, loss and first gradient (the mask-ruled flash
        kernels, grouped matmul over the held experts, the weighted chunked
        loss, bf16) against the plain reference on the sequence and its
        noise: every block's state on both halves, the routing, each masked
        row's cross-entropy, the loss with its balancing term, the
        gradient's norm; the train step's first loss against the
        reference's; the same system on e4m3 matrices and three references
        of another model, all of which the comparison must refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            keys = jax.jit(make_noise_keys)(k_tok)
            last = jax.device_get(system(final_params, tokens[0],
                                         keys[0])[3])
            del final_params
            params = jax.jit(init_params)(k_param)
            at = (tokens[0], keys[0])
            r = readings(params, params, *at, ref_grads=reference_grads(
                params, *at, chosen_sets(params, *at)))
            # The same on e4m3 matrices; two sets of parameters and a
            # gradient do not fit, so each side's is made alone.
            low_params = jax.jit(rounded_to_e4m3)(params)
            low = readings(low_params, params, *at, others=False)
            low_follow = chosen_sets(low_params, *at)
            del low_params
            low_ref = reference_grads(params, *at, low_follow, (0,))
            del params
            low.update(grad_readings(
                jax.jit(lambda k: rounded_to_e4m3(init_params(k)))(k_param),
                *at, low_ref))
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        bd = r["bd"]
        print("INFO " + json.dumps({
            "held_share_first_step": r["held_share"],
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "block_diffusion_stats": bd,
            "flipped_share_of_layers_x_positions": r["flipped"],
            "flipped_by_layer": r["flipped_by_layer"],
            "flipped_positions_share": r["flipped_positions"],
            "flipped_margin": r["margin"],
            "state_err_by_block": r["state"],
            "nll_err_masked_rows": r["nll"], "loss_err": r["loss"],
            "grad_norm": [r["grad_norm_system"], r["grad_norm_reference"]],
            "grad_norm_err": r["grad_norm"],
            "grad_worst_leaf_err": r["grad_leaf"],
            "reference_loss_parts": r["reference_loss_parts"],
            "e4m3": {k: low[k] for k in ("flipped", "flipped_positions",
                                         "margin", "state", "nll", "loss",
                                         "grad_norm", "grad_leaf")},
            "against_causal_mask": r["against_causal_mask"],
            "against_no_clean_half": r["against_no_clean_half"],
            "against_unit_weights": r["against_unit_weights"],
            "grad_norm_and_leaf_against_causal_mask":
            r["grad_against_causal_mask"],
            "grad_norm_and_leaf_against_no_clean_half":
            r["grad_against_no_clean_half"]}), flush=True)
        return [
            ("no assignment dropped, and the held experts take their share "
             "of them (%d of %d experts: an even share is %.4f)"
             % (held, experts, held / experts),
             r["dropped"] == 0 and int(last["dropped"]) == 0
             and all(0.0 < s < 1.0 for s in r["held_share"]),
             "held share by layer %s -> %s"
             % (["%.4f" % s for s in r["held_share"]],
                ["%.4f" % float(s) for s in last["held_share"]])),
            ("the noise is the reference's (block_diffusion_stats: masked + "
             "kept = %d, no block's t outside [%g, 1])" % (length, t_min),
             bd["masked"][0] + bd["kept"][0] == length
             and bd["masked"][0] == r["masked_reference"]
             and bd["t_outside"] == 0,
             "masked %d (the reference %d), kept %d, mean t %.4f, blocks "
             "with nothing masked %d" % (bd["masked"][0],
                                         r["masked_reference"],
                                         bd["kept"][0], bd["t_mean"],
                                         bd["empty_blocks"])),
            ("the system's top-%d sets differ from the float32 reference's "
             "own in few of the %d x %d layers and positions" % (
                 top_k, layers, 2 * length),
             r["flipped"] <= TOL_FLIPPED, "share %.4f, tol %.2f (by layer "
             "%s; positions differing in some layer %.4f)"
             % (r["flipped"], TOL_FLIPPED,
                ["%.3f" % f for f in r["flipped_by_layer"]],
                r["flipped_positions"])),
            ("and only at a near tie: the reference's %dth largest "
             "probability less the least one the system chose, over the "
             "former" % top_k, r["margin"] <= TOL_MARGIN,
             "largest %.3e, tol %.1e" % (r["margin"], TOL_MARGIN)),
            ("every block's state agrees with the plain reference on both "
             "halves (%d blocks, every position; the reference computes "
             "with the system's sets)" % layers,
             max(r["state"]) <= TOL_STATE,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["state"]], TOL_STATE)),
            ("system cross-entropy agrees with the plain reference masked "
             "row by masked row (the rows' own losses of the weighted "
             "call)", r["nll"] <= TOL_NLL,
             "max abs err %.3e nats, tol %.0e" % (r["nll"], TOL_NLL)),
            ("system loss (1/t-weighted masked rows + %g x balancing) agrees "
             "with the plain reference" % w_balance, r["loss"] <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (r["loss_system"], r["loss_reference"], r["loss"], TOL_LOSS)),
            ("the step's first loss agrees with the reference",
             err_step <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, r["loss_reference"], err_step, TOL_LOSS)),
            ("the first step's gradient has the reference's norm",
             r["grad_norm"] <= TOL_GRAD_NORM,
             "%.6e vs %.6e, rel %.3e, tol %.0e"
             % (r["grad_norm_system"], r["grad_norm_reference"],
                r["grad_norm"], TOL_GRAD_NORM)),
            ("the first step's gradient agrees with the reference's leaf by "
             "leaf where the mask-ruled kernels write it (the first and the "
             "last block's four attention matrices)",
             r["grad_leaf"] <= TOL_GRAD_LEAF,
             "worst leaf's |sys - ref| / |ref| %.3e, tol %.1e"
             % (r["grad_leaf"], TOL_GRAD_LEAF)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, nll %.3e, the gradient's worst leaf %.3e"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]], low["nll"],
                low["grad_leaf"])),
            ("references of another model are refused, by the states and "
             "by the gradient: the causal mask, the clean half left out; by "
             "the loss: weights 1 for 1 / t",
             r["against_causal_mask"] > TOL_STATE
             and r["against_no_clean_half"] > TOL_STATE
             and r["grad_against_causal_mask"][1] > TOL_GRAD_LEAF
             and r["grad_against_no_clean_half"][1] > TOL_GRAD_LEAF
             and r["against_unit_weights"] > TOL_LOSS,
             "state rel err %.3e and %.3e (tol %.1e); the gradient's worst "
             "leaf %.3e and %.3e (tol %.1e); loss rel %.3e (tol %.0e)"
             % (r["against_causal_mask"], r["against_no_clean_half"],
                TOL_STATE, r["grad_against_causal_mask"][1],
                r["grad_against_no_clean_half"][1], TOL_GRAD_LEAF,
                r["against_unit_weights"], TOL_LOSS)),
        ]

    positions = 2 * length
    group = heads // kv_heads
    forward_plan, backward_plan = (profile.flash_plan(
        1, heads, positions, head_dim, group, cfg.dtype, b, mask=rule)
        for b in (False, True))
    plans = dict(forward_plan, **backward_plan)
    # The blocks under `block_remat` run their forward twice: its kernels
    # execute (and their time is in the trace) twice too.
    again = cfg.block_remat
    rows = top_k * positions * held / experts  # expected on the held experts
    gmm = layers + again / 3.0  # a forward is 3 of the 9
    sizes = (hidden, heads, kv_heads, head_dim, expert_width, experts, held)
    counts = {
        "model_flops_per_item": flops_sdar.model_flops_per_token(
            *sizes, top_k, vocab, layers, length, block),
        # per step and per device, by the tiles `flash_plan` says each
        # kernel visits
        "flash_kernels": list(plans),
        "flash_tiles": {name: [p.tiles_visited, p.tiles_masked,
                               p.tiles_skipped] for name, p in plans.items()},
        "flash_executed_flops": sum(
            n * flops_sdar.flash_executed_flops(which, head_dim)
            for n, which in ((layers, plans), (again, forward_plan))),
        "flash_min_bytes": sum(
            n * flops_sdar.flash_min_bytes(which, 1, heads, kv_heads,
                                           positions, head_dim)
            for n, which in ((layers, plans), (again, forward_plan))),
        "moe_gmm_executed_flops": gmm
        * flops_moe.gated_experts_flops(rows, hidden, expert_width),
        "moe_gmm_min_bytes": gmm
        * flops_moe.gated_experts_min_bytes(
            rows, hidden, expert_width, held,
            itemsize=jnp.dtype(job["compute_dtype"]).itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_sdar.params(*sizes, vocab, layers),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "readings": readings, "make_state": make,
            "init_params": init_params, "make_tokens": make_tokens,
            "make_noise_key": lambda k_tok: make_noise_keys(k_tok)[0]}
