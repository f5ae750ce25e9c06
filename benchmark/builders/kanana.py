"""Builder for Kanana-2 configurations (Kakao, `model_type` `deepseek_v3`):
`models.Transformer` with latent attention whose queries come straight from
the state (`q_lora_rank` null; the flash kernels' scores of two products at
8192 positions), one leading dense layer and routed layers of sigmoid-scored
experts of which this chip HOLDS a part beside a shared pair; trained through
`parallel.make_train_step` on the next token's cross-entropy (the chunked
vocabulary loss; `noaux_tc`: no balancing term), AdamW inside a linear
warm-up.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/kanana.py` outside the
window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, one sequence of 8192, the seeded state of the configuration (my
# chip runs, PR 56: the benchmark's own runs, a seed each; PERF.md s6 has
# every seed's numbers). As `builders/mellum.py`: the reference computes every
# routed layer with the experts the SYSTEM's top-6 chose
# (`references/kanana.py::routed_ffn(follow=)`) and says how often its own
# top-6 differs and how near a tie each such choice was, so EVERY position is
# compared and the two gradients are of the same function. Each limit that
# guards the PRECISION lies between two readings: the largest the bf16 system
# gave over its seeds, and the least the same system gave with every matrix
# rounded to fp8's precision (e4m3), which `verify` computes in every run and
# which has to come out as not correct (first two seeds of the seeded state;
# in brackets four seeds on flax's own draws, before the configuration had
# its `seeded_state`):
#   routed layers x positions whose top-6 set is not the reference's own:
#     bf16 0.102..0.103 (0.100..0.102), fp8 0.678 (0.700..0.709).
#   how far from a tie the farthest such choice lies (the reference's 6th
#     largest score + bias less the least one chosen, over the former): bf16
#     0.017..0.020 (0.015..0.016), fp8 0.184..0.216 (0.181..0.215).
#   a block's state, every position, max |sys - ref| / max |ref|, the worst
#     block: bf16 0.025..0.028 (0.019..0.020), fp8 0.238..0.262 (0.186..0.210).
#   a block's attention branch (W_o's output before the residual add),
#     likewise: bf16 0.023..0.027 (0.013..0.016), fp8 0.202..0.262
#     (0.126..0.160).
#   the first routed block's gradient by slice where the flash backward writes
#     it and nothing else does (`kv_a`'s latent and rotary columns, `q`'s
#     no-position and rotary columns: dk2 and dq2 land in the rotary ones),
#     |sys - ref|_2 / |ref|_2, the worst slice: bf16 0.066..0.067
#     (0.047..0.050); fp8's LEAST slice 0.378..0.390 (0.261..0.288).
TOL_FLIPPED = 0.3          # share of layers x positions whose sets differ
TOL_MARGIN = 6e-2          # how far from a tie a differing choice may lie
TOL_STATE = 8e-2           # max |system - reference| / max |reference|
TOL_ATTN = 7e-2            # likewise, a block's attention branch
TOL_GRAD_LEAF = 1.6e-1     # a picked slice's |system - reference|_2 / |ref|_2
# These guard something else and do NOT separate the precisions:
#   a row's cross-entropy, nats: bf16 0.45..0.60 (0.24..0.38), fp8 0.94..0.99
#     (0.77..0.82): a maximum over 8192 rows whose tail moves by the seed more
#     than by the precision. The limit, twice the largest seen, holds the
#     rows' targets: a head that reads the wrong token is several nats off.
#   mean loss, relative: bf16 1.2e-5..5.3e-5, fp8 9.1e-6..3.6e-4: a mean near
#     ln(vocab) over 8192 rows averages the precision away; the limit is the
#     harness's accepted cells' (38 times the largest seen) and holds the
#     loss's ASSEMBLY (targets, the mean).
# What the comparison must refuse, and does (same runs): the reference
# without the shared pair is 2.3..2.4 (0.97..1.14) from the system's states.
TOL_NLL = 1.2              # max |system - reference|, a row's nats
TOL_LOSS = 2e-3            # relative, the mean loss
TOL_OTHER = 0.25           # a reference of ANOTHER model must be this far off


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_kanana, flops_moe
    from benchmark.builders.sdar import rounded_to_e4m3
    from benchmark.references import kanana as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step, routing_stats

    job = config["job"]
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    routed_layers = layers - dense_layers
    experts = config["published_n_routed_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_tok"]
    vocab = config["vocab_size"]
    eps = config["rms_norm_eps"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kv_rank = config["kv_lora_rank"]
    width, expert_width = (config["intermediate_size"],
                           config["moe_intermediate_size"])
    shared_width = config["n_shared_experts"] * expert_width
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (held != config["n_routed_experts"] or config["q_lora_rank"] is not None
            or config["rope_scaling"] is not None
            or config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1
            or heads != config["num_key_value_heads"]
            or nope + rope != config["qk_head_dim"]):
        raise ValueError("this builder makes latent attention with direct "
                         "queries on the plain rotation, sigmoid-routed "
                         "experts with no group limit, SiLU, no bias and an "
                         "untied head")
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        embed_dim=hidden, mlp_dim=width, mlp_gated=True,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rope_base=config["rope_theta"],
        norm_eps=eps, kv_lora_rank=kv_rank, q_lora_rank=None,
        qk_nope_dim=nope, qk_rope_dim=rope, v_head_dim=vd,
        moe_experts=experts, moe_every=1, first_k_dense=dense_layers,
        moe_dim=expert_width, moe_top_k=top_k, moe_capacity_factor=None,
        moe_gated=True, moe_renormalize=config["norm_topk_prob"],
        moe_scoring="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_dim=shared_width, moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"num_layers": layers, "first_k_dense": dense_layers, "eps": eps,
            "nope": nope, "rope": rope, "rope_theta": config["rope_theta"],
            "top_k": top_k, "norm_topk_prob": config["norm_topk_prob"],
            "route_scale": config["routed_scaling_factor"],
            "held": (first, held)}
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
                mdl, (transformer.Block, transformer.LatentAttention))
                and name == "__call__"} if capture else {}))
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
            chunk=chunk, weights=weights), state["intermediates"]

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

    out_gain = float(config.get("seeded_state", {}).get(
        "attention_out_gain", 1.0))

    def init_params(key):
        """flax's draws, but for what the configuration's `seeded_state`
        says and why: every block's attention output matrix times
        `attention_out_gain`."""
        params = model.init(key, jnp.zeros((1, length), jnp.int32))["params"]
        for i in range(layers):
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

    # The gradient is held where the flash backward writes it and nothing
    # else does: the first routed block's `kv_a` (its rotary columns take
    # dk2, the shared key's gradient summed over the heads) and `q` (its
    # rotary columns take dq2), each slice apart so that neither hides
    # behind the other's norm.
    probed = "block_%d" % dense_layers

    def picked(grads):
        a = grads[probed]["attn"]
        kv_a, q = a["kv_a"]["kernel"], a["q"]["kernel"]
        return {"kv_a/latent": kv_a[:, :kv_rank],
                "kv_a/rope": kv_a[:, kv_rank:],
                "q/nope": q[..., :nope], "q/rope": q[..., nope:]}

    # One program for a system's forward AND its picked gradient slices: a
    # run compiles every program anew (the cell's entries do not fit the
    # machine's compile cache), and the forward is shared.
    system = jax.jit(lambda p, seq: forward(p, seq) + (picked(jax.grad(
        lambda q: objective(q, seq[None])[0])(p)),))
    ref_fn = jax.jit(lambda p, seq, follow, shared: reference.forward(
        p, seq, arch, follow, shared))
    ref_grad = jax.jit(lambda p, seq, follow: picked(
        reference.gradient(p, seq, arch, follow)))

    def chosen_sets(counters):
        """[routed layers, L, E] bool from the system's chosen indices."""
        return jnp.any(jax.nn.one_hot(counters["chosen"], experts,
                                      dtype=jnp.bool_), axis=-2)

    def readings(seen, params, seq, ref_grads, other_model=False):
        """How far a system's forward and picked gradient slices `seen`
        (`system(...)` of its parameters on `seq` [L]) are from the
        reference on `params`: the errors the limits above are set on and
        both sides' numbers. The reference
        computes every routed layer with the experts the SYSTEM chose and
        says how often its own top-k differs and how near a tie each such
        choice was; `ref_grads` are its picked slices on the same sets. (The
        system's own parameters are not taken: two sets beside the
        reference's working set do not fit the chip.) `other_model`: also
        the same system against the reference WITHOUT the shared pair,
        another model, which the comparison must refuse."""
        states, attn, nll, loss, counters, grads = seen
        follow = chosen_sets(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, follow, 1.0)
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
            "attn": err(attn, ref["attn"]),
            "nll": float(jnp.max(jnp.abs(nll - ref["nll"]))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "held_rows_reference": [int(v) for v in ref["held_rows"]],
            "dropped": int(counters["dropped"]),
            "grad": {n: float(jnp.linalg.norm(grads[n] - ref_grads[n])
                              / jnp.linalg.norm(ref_grads[n]))
                     for n in sorted(grads)}}
        del ref, attn
        if other_model:
            with jax.default_matmul_precision("highest"):
                no_shared = ref_fn(params, seq, follow, 0.0)["states"]
            out["against_no_shared_pair"] = max(err(states, no_shared))
        return out

    def refused(r):
        """The limits of the precision that `r` breaks."""
        return [name for name, bad in (
            ("flipped", r["flipped"] > TOL_FLIPPED),
            ("margin", r["margin"] > TOL_MARGIN),
            ("state", max(r["state"]) > TOL_STATE),
            ("attn", max(r["attn"]) > TOL_ATTN),
            ("grad", max(r["grad"].values()) > TOL_GRAD_LEAF)) if bad]

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, nothing dropped), then from the seed again
        the system's forward, loss and first gradient (the flash kernels
        under scores of two products at the timed shape, grouped matmul over
        the held experts, the chunked loss, bf16) against the plain
        reference on the sequence: every block's state and attention branch,
        the routing, each row's cross-entropy, the loss, the first routed
        block's `kv_a` and `q` gradients by slice; the train step's first
        loss against the reference's; the same system on e4m3 matrices and a
        reference without the shared pair, both of which the comparison must
        refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            seq = tokens[0]
            last = jax.device_get(system(final_params, seq)[4])
            del final_params
            # The caller still holds the trained parameters, so ONE more set
            # at a time: the system on e4m3 matrices first, from the seed in
            # one program, then the seeded parameters themselves; then the
            # reference's gradient on the bf16 system's sets; then the
            # reference's forwards, a system's results at a time.
            low_seen = system(jax.jit(lambda key: rounded_to_e4m3(
                init_params(key)))(k_param), seq)
            params = jax.jit(init_params)(k_param)
            seen = system(params, seq)
            with jax.default_matmul_precision("highest"):
                ref_grads = ref_grad(params, seq, chosen_sets(seen[4]))
            r = readings(seen, params, seq, ref_grads, other_model=True)
            del seen
            low = readings(low_seen, params, seq, ref_grads)
            del params, ref_grads, low_seen
        other = r["against_no_shared_pair"]
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
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
            "grad_err_by_slice_of_%s" % probed: r["grad"],
            "e4m3": {k: low[k] for k in ("flipped", "margin", "state",
                                         "attn", "nll", "loss", "grad")},
            "against_no_shared_pair": other,
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
            ("every block's attention branch agrees with the plain "
             "reference (latent attention, direct queries, the flash "
             "forward's two products)", max(r["attn"]) <= TOL_ATTN,
             "max rel err by block %s, tol %.1e"
             % (["%.3e" % e for e in r["attn"]], TOL_ATTN)),
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
            ("the first step's gradient agrees with the reference's where "
             "the flash backward writes it (%s's kv_a and q, the rotary "
             "columns, which take dk2 and dq2, apart)" % probed,
             max(r["grad"].values()) <= TOL_GRAD_LEAF,
             "|sys - ref| / |ref| by slice %s, tol %.1e"
             % ({k: "%.3e" % v for k, v in r["grad"].items()},
                TOL_GRAD_LEAF)),
            ("a reference of another model is refused: no shared pair",
             other > TOL_OTHER, "state rel err %.3e, must pass %.2f"
             % (other, TOL_OTHER)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, attention branch %s, nll %.3e, gradient %s"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]],
                ["%.3e" % e for e in low["attn"]], low["nll"],
                {k: "%.3e" % v for k, v in low["grad"].items()})),
        ]

    forward_plans, backward_plans = (profile.flash_plan(
        1, heads, length, nope, 1, cfg.dtype, b, shared_dim=rope)
        for b in (False, True))
    plans = dict(forward_plans, **backward_plans)
    # The blocks under `block_remat` (the first k) run their forward twice:
    # its kernels execute (and their time is in the trace) twice too.
    again = cfg.block_remat
    routed_again = max(0, again - dense_layers)
    rows = top_k * length * held / experts  # expected on the held experts
    gmm = routed_layers + routed_again / 3.0  # a forward is 3 of the 9
    itemsize = jnp.dtype(job["compute_dtype"]).itemsize
    sizes = (hidden, heads, kv_rank, nope, rope, vd, width, expert_width,
             shared_width, held, experts)
    shape = (1, heads, length, nope, rope, vd)
    counts = {
        "model_flops_per_item": flops_kanana.model_flops_per_token(
            *sizes, top_k, vocab, dense_layers, routed_layers, length),
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
            for n, which in ((layers, plans), (again, forward_plans))),
        "flash_min_bytes": sum(
            n * flops_kanana.flash_min_bytes(list(which), *shape)
            for n, which in ((layers, plans), (again, forward_plans))),
        "moe_gmm_executed_flops": gmm
        * flops_moe.gated_experts_flops(rows, hidden, expert_width),
        "moe_gmm_min_bytes": gmm
        * flops_moe.gated_experts_min_bytes(
            rows, hidden, expert_width, held, itemsize=itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_kanana.params(*sizes, vocab, dense_layers,
                                      routed_layers),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "system": system, "init_params": init_params,
            "make_tokens": make_tokens}
