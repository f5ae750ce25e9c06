"""Builder for Kimi-Linear configurations (Moonshot, `model_type`
`kimi_linear`): `models.Transformer` whose layers are Kimi Delta Attention
three in four (`attention_types` "kda": the chunked gated delta rule of
`ops/kda.py`) and latent attention WITHOUT position in the fourth
(`kv_lora_rank` with `rotary=False`; the flash kernels' scores of two
products), one leading dense layer and routed layers of sigmoid-scored
experts of which this chip HOLDS a part beside one shared expert; trained
through `parallel.make_train_step` on the next token's cross-entropy (the
chunked vocabulary loss; no balancing term), AdamW inside a linear warm-up.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/kimi.py` outside the
window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, one sequence of 8192 (my chip runs, PR 58: the benchmark's own
# runs on the seeded state, six seeds: 1, 2, 3, 4, 2147483659, 5800000117;
# PERF.md s6). As
# `builders/kanana.py`: the reference computes every routed layer with the
# experts the SYSTEM's top-8 chose and says how often its own top-8 differs
# and how near a tie each such choice was, so EVERY position is compared and
# the two gradients are of the same function. Each limit that guards the
# PRECISION lies between two readings: the largest the bf16 system gave over
# its seeds, and the least the same system gave with every matrix rounded to
# fp8's precision (e4m3), which `verify` computes in every run and which has
# to come out as not correct:
#   routed layers x positions whose top-8 set is not the reference's own:
#     bf16 0.185..0.189, fp8 0.901..0.906.
#   how far from a tie the farthest such choice lies (the reference's 8th
#     largest score + bias less the least one chosen, over the former): bf16
#     0.017..0.022, fp8 0.251..0.295.
#   a block's state, every position, max |sys - ref| / max |ref|, the worst
#     block: bf16 0.028..0.033, fp8 0.295..0.322.
#   a block's first branch (the KDA mixer's or latent attention's output
#     before the residual add), likewise: bf16 0.042..0.046, fp8
#     0.413..0.471.
#   the first step's gradient at the last layer of each kind, |sys - ref|_2
#     / |ref|_2 a leaf. The mixers' and the dense feed-forward's leaves,
#     the worst: bf16 0.078..0.124; fp8's LEAST leaf 0.279..0.297. The last
#     ROUTED layer's router and held experts read higher on both sides (a
#     held expert sees 256 rows of 8192 and the router's gradient is a
#     difference of renormalised weights): bf16 0.287..0.306 (the router; the
#     experts 0.218..0.234), fp8's least 0.811..0.830: a limit of their own.
TOL_FLIPPED = 0.5          # share of layers x positions whose sets differ
TOL_MARGIN = 8e-2          # how far from a tie a differing choice may lie
TOL_STATE = 1e-1           # max |system - reference| / max |reference|
TOL_MIXER = 1.3e-1         # likewise, a block's first branch (KDA | latent)
TOL_GRAD_LEAF = 1.8e-1     # a picked leaf's |system - reference|_2 / |ref|_2
TOL_GRAD_ROUTED = 5e-1     # likewise, the last routed layer's two leaves
# The chunked recurrence alone, on the first KDA layer's own q, k, v, g, beta
# at [8192, 32, 128] (bf16 operands under f32 decays, solve and carry)
# against the token-by-token f32 recurrence on the same numbers: the output
# and the final state, max |chunked - sequential| / max |sequential|
# (0.0024..0.0046; the same call on operands rounded to e4m3 is not made:
# the limit holds the chunked form's assembly, four times the reading).
TOL_KDA = 2e-2
# A row's cross-entropy (bf16 0.31..0.46 nats, fp8 1.12..1.24: a maximum
# over 8192 rows whose tail moves by the seed; the limit lies between the two
# and also holds the rows' targets: a head that reads the wrong token is
# several nats off), and the mean loss, which does NOT separate the
# precisions (bf16 7e-6..4e-5, fp8 2e-4: a mean near ln(vocab) over 8192
# rows averages the precision away; the limit is the harness's accepted
# cells' and holds the loss's ASSEMBLY).
# What the comparison must refuse, and does (same runs): the reference
# without the shared expert is 2.31..2.35 from the system's states, the
# reference with alpha = 1 (the plain delta rule) 1.40..1.53.
TOL_NLL = 0.8              # max |system - reference|, a row's nats
TOL_LOSS = 2e-3            # relative, the mean loss
TOL_OTHER = 0.25           # a reference of ANOTHER model must be this far off
KDA_SUB = 16               # `ops.kda.kda_chunked`'s sub-block, its default


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_kanana, flops_kimi, flops_moe
    from benchmark.builders.sdar import rounded_to_e4m3
    from benchmark.references import kimi as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops.kda import kda_chunked
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step, routing_stats

    job = config["job"]
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    routed_layers = layers - dense_layers
    experts = config["published_num_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_token"]
    vocab = config["vocab_size"]
    eps = config["rms_norm_eps"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kv_rank = config["kv_lora_rank"]
    width, expert_width = (config["intermediate_size"],
                           config["moe_intermediate_size"])
    shared_width = config["num_shared_experts"] * expert_width
    linear = config["linear_attn_config"]
    kda_dim, taps = linear["head_dim"], linear["short_conv_kernel_size"]
    kinds = tuple("kda" if i + 1 in linear["kda_layers"] else "full"
                  for i in range(layers))
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (held != config["num_experts"] or config["q_lora_rank"] is not None
            or config["rope_scaling"] is not None
            or not config["mla_use_nope"]
            or config["moe_router_activation_func"] != "sigmoid"
            or config["num_expert_group"] != 1 or config["topk_group"] != 1
            or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1
            or config["num_nextn_predict_layers"]
            or heads != config["num_key_value_heads"]
            or heads != linear["num_heads"]
            or sorted(linear["kda_layers"] + linear["full_attn_layers"])
            != list(range(1, layers + 1))):
        raise ValueError("this builder makes KDA layers and latent "
                         "attention with direct queries and no position, "
                         "both of num_attention_heads heads, sigmoid-routed "
                         "experts with no group limit, SiLU, no prediction "
                         "module and an untied head")
    if length > config["model_max_length"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length, config["model_max_length"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        embed_dim=hidden, mlp_dim=width, mlp_gated=True,
        max_seq_len=config["model_max_length"],
        attention=job["attention"], rotary=False, attention_types=kinds,
        kda_head_dim=kda_dim, kda_conv=taps, kda_chunk=int(job["kda_chunk"]),
        norm_eps=eps, kv_lora_rank=kv_rank, q_lora_rank=None,
        qk_nope_dim=nope, qk_rope_dim=rope, v_head_dim=vd,
        moe_experts=experts, moe_every=1, first_k_dense=dense_layers,
        moe_dim=expert_width, moe_top_k=top_k, moe_capacity_factor=None,
        moe_gated=True, moe_renormalize=config["moe_renormalize"],
        moe_scoring="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_dim=shared_width, moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"kinds": kinds, "first_k_dense": dense_layers, "eps": eps,
            "kda_heads": heads, "kda_head_dim": kda_dim,
            "kda_chunk": int(job["kda_chunk"]),
            "nope": nope, "rope": rope, "top_k": top_k,
            "norm_topk_prob": config["moe_renormalize"],
            "route_scale": config["routed_scaling_factor"],
            "held": (first, held)}
    chunk = min(int(job["loss_chunk"]), length)
    # The rate climbs from 0 to `learning_rate` over `warmup_steps`: a window
    # at the start of a job is inside the warm-up (the file says why).
    opt = optax.adamw(optax.linear_schedule(0.0, job["learning_rate"],
                                            int(job["warmup_steps"])),
                      b1=job["adam_b1"], b2=job["adam_b2"],
                      eps=job["adam_eps"], weight_decay=job["weight_decay"])
    mixers = (transformer.KimiDeltaAttention, transformer.LatentAttention)

    def objective(params, tokens, weights=None, capture=False):
        """The loss of sequences `tokens` [B, L] (`weights`: a weight a row
        in place of the mean), with the model's collections."""
        hid, state = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["intermediates"],
            **({"capture_intermediates": lambda mdl, name: isinstance(
                mdl, (transformer.Block,) + mixers)
                and name == "__call__"} if capture else {}))
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
            chunk=chunk, weights=weights), state["intermediates"]

    def loss_fn(params, batch):
        return objective(params, batch["x"])[0]

    def forward(params, seq):
        """Of ONE sequence [L]: every block's output and every block's first
        branch [layers, L, C] f32, each row's own cross-entropy [L] (the
        gradient of the weighted loss by a row's weight, as the timed loss
        forms it), the loss, and the step's counters."""
        x = seq[None]
        loss, inter = objective(params, x, capture=True)
        blocks = [inter["block_%d" % i] for i in range(layers)]
        states = jnp.stack([b["__call__"][0][0].astype(jnp.float32)
                            for b in blocks])
        mixer = jnp.stack([b["attn"]["__call__"][0][0].astype(jnp.float32)
                           for b in blocks])
        nll = jax.grad(lambda w: objective(params, x, w)[0])(
            jnp.zeros((1, length), jnp.float32))[0]
        routing = routing_stats(inter)
        return (states, mixer, nll, loss,
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "held_rows_max": jnp.max(
                     routing["assignments"][:, first:first + held], axis=1),
                 "dropped": routing["dropped"],
                 "kda_state_max": models.kda_stats(inter)})

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    out_gain = float(config.get("seeded_state", {}).get(
        "mixer_out_gain", 1.0))

    def init_params(key):
        """flax's draws, but for what the configuration's `seeded_state`
        says and why (if it has one): every block's mixer output matrix
        times `mixer_out_gain`."""
        params = model.init(key, jnp.zeros((1, length), jnp.int32))["params"]
        if out_gain != 1.0:
            for i, kind in enumerate(kinds):
                out = params["block_%d" % i]["attn"][
                    "out_proj" if kind == "kda" else "out"]
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

    # The gradient is held at the LAST layer of each kind: the last KDA
    # mixer (its in-projection by part, so that none hides behind another's
    # norm; the decay's own leaves; the convolution), the last latent layer
    # (`kv_a`, `q`), the dense feed-forward, the last routed one (the held
    # experts and the router).
    last_kda = max(i for i, k in enumerate(kinds) if k == "kda")
    last_full = max(i for i, k in enumerate(kinds) if k == "full")
    inner = heads * kda_dim

    def picked(grads):
        kda = grads["block_%d" % last_kda]["attn"]
        w_in = kda["in_proj"]["kernel"]
        full = grads["block_%d" % last_full]["attn"]
        moe = grads["block_%d" % (layers - 1)]["moe_mlp"]
        return {"kda/q": w_in[:, :inner], "kda/k": w_in[:, inner:2 * inner],
                "kda/v": w_in[:, 2 * inner:3 * inner],
                "kda/f_down": w_in[:, 3 * inner:3 * inner + kda_dim],
                "kda/z_down": w_in[:, 3 * inner + kda_dim:
                                   3 * inner + 2 * kda_dim],
                "kda/beta": w_in[:, 3 * inner + 2 * kda_dim:],
                "kda/f_up": kda["f_up"]["kernel"],
                "kda/A_log": kda["A_log"], "kda/dt_bias": kda["dt_bias"],
                "kda/conv": kda["conv_kernel"],
                "kda/out": kda["out_proj"]["kernel"],
                "latent/kv_a": full["kv_a"]["kernel"],
                "latent/q": full["q"]["kernel"],
                "dense/mlp_gate": grads["block_0"]["mlp_gate"]["kernel"],
                "routed/w_gate": moe["w_gate"],
                "routed/router": moe["router"]}

    # One program for a system's forward AND its picked gradient leaves: the
    # forward is shared.
    system = jax.jit(lambda p, seq: forward(p, seq) + (picked(jax.grad(
        lambda q: objective(q, seq[None])[0])(p)),))
    ref_fn = jax.jit(lambda p, seq, follow, shared, decay: reference.forward(
        p, seq, arch, follow, shared, decay))
    ref_grad = jax.jit(lambda p, seq, follow: picked(
        reference.gradient(p, seq, arch, follow)))

    @jax.jit
    def recurrence_err(params, seq):
        """The chunked recurrence against the sequential one on the FIRST
        layer's own inputs (the reference's f32 q, k, v, g, beta of the
        embedded sequence; q, k, v rounded to the compute dtype for both):
        (output, final state) max |chunked - sequential| / max
        |sequential|."""
        p = params["block_0"]
        x = reference._f32(params["embed"]["embedding"])[seq]
        q, k, v, g, beta, _ = reference.kda_inputs(
            reference._rms(x, p["norm1"]["scale"], eps), p["attn"], arch)
        q, k, v = (t.astype(cfg.dtype) for t in (q, k, v))
        o, S, _ = kda_chunked(q[None], k[None], v[None], g[None], beta[None],
                              cfg.kda_chunk)
        with jax.default_matmul_precision("highest"):
            ro, rS, _ = reference.kda_recurrence(
                *(t.astype(jnp.float32) for t in (q, k, v)), g, beta)

        def err(a, b):
            return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))

        return err(o[0], ro), err(S[0], rS)

    def chosen_sets(counters):
        """[routed layers, L, E] bool from the system's chosen indices."""
        return jnp.any(jax.nn.one_hot(counters["chosen"], experts,
                                      dtype=jnp.bool_), axis=-2)

    def readings(seen, params, seq, ref_grads, other_models=False):
        """How far a system's forward and picked gradient leaves `seen`
        (`system(...)` of its parameters on `seq` [L]) are from the
        reference on `params`: the errors the limits above are set on and
        both sides' numbers. The reference computes every routed layer with
        the experts the SYSTEM chose and says how often its own top-k
        differs and how near a tie each such choice was; `ref_grads` are its
        picked leaves on the same sets. `other_models`: also the same system
        against the reference WITHOUT the shared expert and against the
        reference with alpha = 1 (the plain delta rule), other models, which
        the comparison must refuse."""
        states, mixer, nll, loss, counters, grads = seen
        follow = chosen_sets(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, follow, 1.0, 1.0)
        flipped = jnp.any(follow != ref["chosen"], axis=2)  # [routed, L]

        def err(ours, theirs):
            return [float(v) for v in
                    jnp.max(jnp.abs(ours - theirs), axis=(1, 2))
                    / jnp.max(jnp.abs(theirs), axis=(1, 2))]

        loss, ref_loss = float(loss), float(ref["loss"])
        out = {
            "loss_system": loss, "loss_reference": ref_loss,
            "flipped": float(jnp.mean(flipped)),
            "flipped_by_layer": [float(v) for v in jnp.mean(flipped, axis=1)],
            "margin": float(jnp.max(ref["margin"])),
            "state": err(states, ref["states"]),
            "mixer": err(mixer, ref["mixer"]),
            "nll": float(jnp.max(jnp.abs(nll - ref["nll"]))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "held_rows_max": [int(v) for v in counters["held_rows_max"]],
            "held_rows_reference": [int(v) for v in ref["held_rows"]],
            "dropped": int(counters["dropped"]),
            "kda_state_max": float(counters["kda_state_max"]),
            "kda_state_max_reference": float(ref["kda_state_max"]),
            "grad": {n: float(jnp.linalg.norm(grads[n] - ref_grads[n])
                              / jnp.linalg.norm(ref_grads[n]))
                     for n in sorted(grads)}}
        del ref, mixer
        if other_models:
            with jax.default_matmul_precision("highest"):
                out["against_no_shared_expert"] = max(err(
                    states, ref_fn(params, seq, follow, 0.0, 1.0)["states"]))
                out["against_plain_delta_rule"] = max(err(
                    states, ref_fn(params, seq, follow, 1.0, 0.0)["states"]))
        return out

    def grad_worst(r, routed):
        """The largest error among the routed layer's leaves, or the
        others'."""
        return max(v for k, v in r["grad"].items()
                   if k.startswith("routed/") == routed)

    def refused(r):
        """The limits of the precision that `r` breaks."""
        return [name for name, bad in (
            ("flipped", r["flipped"] > TOL_FLIPPED),
            ("margin", r["margin"] > TOL_MARGIN),
            ("state", max(r["state"]) > TOL_STATE),
            ("mixer", max(r["mixer"]) > TOL_MIXER),
            ("grad", grad_worst(r, False) > TOL_GRAD_LEAF
             or grad_worst(r, True) > TOL_GRAD_ROUTED)) if bad]

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, nothing dropped, the largest KDA state),
        then from the seed again the chunked recurrence against the
        sequential one at the timed shape, and the system's forward, loss
        and first gradient (the chunked KDA, the flash kernels under scores
        of two products with nothing rotated, grouped matmul over the held
        experts, the chunked loss, bf16) against the plain reference on the
        sequence: every block's state and first branch, the routing, each
        row's cross-entropy, the loss, the gradient at the last layer of
        each kind; the train step's first loss against the reference's; the
        same system on e4m3 matrices, a reference without the shared expert
        and a reference with alpha = 1, all of which the comparison must
        refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            seq = tokens[0]
            last = jax.device_get(system(final_params, seq)[4])
            del final_params
            # The caller still holds the trained parameters, so ONE more set
            # at a time: the system on e4m3 matrices first, from the seed in
            # one program, then the seeded parameters themselves. A system's
            # results (1.6 GB: two [layers, L, C] f32 arrays and the picked
            # leaves) wait on the HOST: beside two sets of parameters and a
            # KDA block's backward pass they do not fit the chip.
            low_seen = jax.device_get(system(jax.jit(
                lambda key: rounded_to_e4m3(init_params(key)))(k_param), seq))
            params = jax.jit(init_params)(k_param)
            kda_o, kda_s = (float(v) for v in recurrence_err(params, seq))
            seen = jax.device_get(system(params, seq))
            with jax.default_matmul_precision("highest"):
                ref_grads = ref_grad(params, seq, chosen_sets(seen[4]))
            r = readings(seen, params, seq, ref_grads, other_models=True)
            del seen
            low = readings(low_seen, params, seq, ref_grads)
            del params, ref_grads, low_seen
        no_shared = r["against_no_shared_expert"]
        no_decay = r["against_plain_delta_rule"]
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        print("INFO " + json.dumps({
            "kda_state_max": {
                "first_step": r["kda_state_max"],
                "reference": r["kda_state_max_reference"],
                "after_the_window": float(last["kda_state_max"])},
            "held_share_first_step": r["held_share"],
            "held_share_first_step_summed": sum(r["held_share"]),
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "largest_held_experts_rows_by_layer": r["held_rows_max"],
            "largest_held_experts_rows_after_the_window": [
                int(v) for v in last["held_rows_max"]],
            "held_rows_reference_by_layer": r["held_rows_reference"],
            "dropped": r["dropped"],
            "flipped_share_of_layers_x_positions": r["flipped"],
            "flipped_by_layer": r["flipped_by_layer"],
            "flipped_margin": r["margin"],
            "kda_chunked_vs_sequential": {"output": kda_o, "state": kda_s},
            "state_err_by_block": r["state"],
            "mixer_branch_err_by_block": r["mixer"],
            "nll_err": r["nll"], "loss_err": r["loss"],
            "grad_err_by_leaf": r["grad"],
            "e4m3": {k: low[k] for k in ("flipped", "margin", "state",
                                         "mixer", "nll", "loss", "grad")},
            "against_no_shared_expert": no_shared,
            "against_plain_delta_rule": no_decay,
            "flash_plan": counts["flash_plan"]}), flush=True)
        return [
            ("no assignment dropped, and the held experts take their share "
             "of them (%d of %d experts: an even share is %.4f)"
             % (held, experts, held / experts),
             r["dropped"] == 0 and int(last["dropped"]) == 0
             and all(0.0 < s < 1.0 for s in r["held_share"]),
             "held share by routed layer %s (summed %.4f) -> %s"
             % (["%.4f" % s for s in r["held_share"]], sum(r["held_share"]),
                ["%.4f" % float(s) for s in last["held_share"]])),
            ("the chunked recurrence agrees with the token-by-token one at "
             "[%d, %d, %d] (the first layer's own q, k, v, g, beta)"
             % (length, heads, kda_dim), max(kda_o, kda_s) <= TOL_KDA,
             "max rel err: output %.3e, final state %.3e, tol %.1e"
             % (kda_o, kda_s, TOL_KDA)),
            ("the system's top-%d sets differ from the float32 reference's "
             "own in few of the %d x %d routed layers and positions" % (
                 top_k, routed_layers, length),
             r["flipped"] <= TOL_FLIPPED, "share %.4f, tol %.2f (by layer "
             "%s)" % (r["flipped"], TOL_FLIPPED,
                      ["%.3f" % f for f in r["flipped_by_layer"]])),
            ("and only at a near tie: the reference's %dth largest score "
             "less the least one the system chose, over the former" % top_k,
             r["margin"] <= TOL_MARGIN,
             "largest %.3e, tol %.1e" % (r["margin"], TOL_MARGIN)),
            ("every block's state agrees with the plain reference (%d "
             "blocks, every position; the reference computes with the "
             "system's sets)" % layers, max(r["state"]) <= TOL_STATE,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["state"]], TOL_STATE)),
            ("every block's first branch agrees with the plain reference "
             "(%s: the chunked KDA against the sequential recurrence, the "
             "flash forward's two unrotated products against plain softmax)"
             % "".join("K" if k == "kda" else "F" for k in kinds),
             max(r["mixer"]) <= TOL_MIXER,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["mixer"]], TOL_MIXER)),
            ("system cross-entropy agrees with the plain reference row by "
             "row (the rows' own losses of the chunked call)",
             r["nll"] <= TOL_NLL,
             "max abs err %.3e nats, tol %.2f" % (r["nll"], TOL_NLL)),
            ("system loss (mean cross-entropy) agrees with the plain "
             "reference", r["loss"] <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (r["loss_system"], r["loss_reference"], r["loss"], TOL_LOSS)),
            ("the step's first loss agrees with the reference",
             err_step <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, r["loss_reference"], err_step, TOL_LOSS)),
            ("the first step's gradient agrees with the reference's at the "
             "last layer of each kind (KDA block_%d, latent block_%d, dense "
             "block_0, routed block_%d)" % (last_kda, last_full, layers - 1),
             grad_worst(r, False) <= TOL_GRAD_LEAF
             and grad_worst(r, True) <= TOL_GRAD_ROUTED,
             "|sys - ref| / |ref| by leaf %s, tol %.1e (routed/: %.1e)"
             % ({k: "%.3e" % v for k, v in r["grad"].items()},
                TOL_GRAD_LEAF, TOL_GRAD_ROUTED)),
            ("a reference of another model is refused: no shared expert",
             no_shared > TOL_OTHER, "state rel err %.3e, must pass %.2f"
             % (no_shared, TOL_OTHER)),
            ("a reference of another model is refused: alpha = 1, the plain "
             "delta rule", no_decay > TOL_OTHER,
             "state rel err %.3e, must pass %.2f" % (no_decay, TOL_OTHER)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, first branch %s, nll %.3e, gradient %s"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]],
                ["%.3e" % e for e in low["mixer"]], low["nll"],
                {k: "%.3e" % v for k, v in low["grad"].items()})),
        ]

    forward_plans, backward_plans = (profile.flash_plan(
        1, heads, length, nope, 1, cfg.dtype, b, shared_dim=rope)
        for b in (False, True))
    plans = dict(forward_plans, **backward_plans)
    # The blocks under `block_remat` (the first k) run their forward twice:
    # its kernels execute (and their time is in the trace) twice too.
    again = list(kinds[:cfg.block_remat])
    full_layers, kda_layers = kinds.count("full"), kinds.count("kda")
    full_again, kda_again = again.count("full"), again.count("kda")
    routed_again = max(0, cfg.block_remat - dense_layers)
    rows = top_k * length * held / experts  # expected on the held experts
    gmm = routed_layers + routed_again / 3.0  # a forward is 3 of the 9
    itemsize = jnp.dtype(job["compute_dtype"]).itemsize
    shape = (1, heads, length, nope, rope, vd)
    kda_shape = (1, length, heads, kda_dim, kda_dim)
    counts = {
        "model_flops_per_item": flops_kimi.model_flops_per_token(
            hidden, heads, kda_dim, kv_rank, nope, rope, vd, width,
            expert_width, shared_width, held, experts, top_k, vocab, kinds,
            dense_layers, length),
        # per step and per device, by the kernels `flash_plan` names and the
        # tiles they compute at its blocks
        "flash_kernels": sorted(plans),
        "flash_plan": {name: {"path": p.path, "held": p.held,
                              "blocks": [p.block_q, p.block_k],
                              "grid": list(p.grid),
                              "vmem_bytes": p.vmem_bytes,
                              "resident_bytes": p.resident_bytes}
                       for name, p in plans.items()},
        "flash_executed_flops": sum(
            n * flops_kanana.flash_executed_flops(which, *shape)
            for n, which in ((full_layers, plans),
                             (full_again, forward_plans))),
        "flash_min_bytes": sum(
            n * flops_kanana.flash_min_bytes(list(which), *shape)
            for n, which in ((full_layers, plans),
                             (full_again, forward_plans))),
        # the chunked recurrence, from the shapes of its chunked form: a
        # backward pass two forwards, a recomputed block one more
        "kda_executed_flops": (3 * kda_layers + kda_again)
        * flops_kimi.kda_chunk_forward_flops(*kda_shape, cfg.kda_chunk),
        "kda_min_bytes": (kda_layers + kda_again)
        * flops_kimi.kda_chunk_min_bytes(*kda_shape, itemsize)
        + kda_layers * flops_kimi.kda_chunk_min_bytes(
            *kda_shape, itemsize, backward=True),
        # the two kernels of a sub-block's own scores: a forward a KDA
        # layer and one more where its block is recomputed, a backward each
        "kda_kernel_executed_flops": (kda_layers + kda_again)
        * flops_kimi.kda_scores_flops(*kda_shape[:4], KDA_SUB)
        + kda_layers * flops_kimi.kda_scores_flops(
            *kda_shape[:4], KDA_SUB, backward=True),
        "kda_kernel_min_bytes": (kda_layers + kda_again)
        * flops_kimi.kda_scores_min_bytes(*kda_shape[:4], KDA_SUB, itemsize)
        + kda_layers * flops_kimi.kda_scores_min_bytes(
            *kda_shape[:4], KDA_SUB, itemsize, backward=True),
        "moe_gmm_executed_flops": gmm
        * flops_moe.gated_experts_flops(rows, hidden, expert_width),
        "moe_gmm_min_bytes": gmm
        * flops_moe.gated_experts_min_bytes(
            rows, hidden, expert_width, held, itemsize=itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_kimi.params(
            hidden, heads, kda_dim, taps, kv_rank, nope, rope, vd, width,
            expert_width, shared_width, held, experts, vocab, kinds,
            dense_layers),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "system": system, "init_params": init_params,
            "make_tokens": make_tokens}
