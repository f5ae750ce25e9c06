"""Builder for Nemotron-H configurations (NVIDIA-Nemotron-3-Super-120B-A12B):
`models.Transformer` under a layer pattern of single-mixer layers (Mamba-2
with the chunked scan of `ops/ssd.py`, grouped-query flash attention with no
rotary, LatentMoE: sigmoid-routed relu2 experts in a latent of which this
chip HOLDS a part, beside a shared expert on the state itself), trained
through `parallel.make_train_step` on the next token's cross-entropy (the
chunked vocabulary loss, AdamW inside a linear warm-up).

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns, and `readings`, the comparison itself; `verify` holds the system
to `references/nemo3.py` outside the window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths (the 2-way head share), one sequence of 4096 (my chip runs, PR 48:
# eleven runs of the benchmark, a seed each; PERF.md §6). A token is FLIPPED
# where, in some routed layer, the HELD experts among its chosen 22 are not
# the reference's: with 22 of 512 chosen nearly every token has some near tie
# at the 22nd place that bf16 decides the other way, but only a tie that
# involves one of the 8 experts held here changes what this rank computes
# (the weights' sum moves by the difference of two near-tied scores).
# Flipped tokens are counted, their share bounded, and left out of the
# comparisons of the tokens routed alike. Each limit that guards the
# PRECISION lies between two readings: the largest the bf16 system gave, and
# the least the same system gave with every matrix rounded to fp8's precision
# (e4m3: four significant bits), which `verify` computes in every run and
# which has to come out as not correct by one of them, and in these runs
# does by each of the three:
#   flipped share:               bf16 0.032..0.048      fp8 0.236..0.325
#   a layer's state, tokens routed alike, max |sys - ref| / max |ref|, at
#   layers 1, 3, 6, 9, 11 of 11:
#       bf16 5.6e-3..6.5e-3, 1.5e-2..2.1e-2, 2.2e-2..2.8e-2, 2.8e-2..3.7e-2,
#            2.9e-2..3.8e-2
#       fp8  5.9e-2..6.7e-2, 8.1e-2..1.0e-1, 1.0e-1..1.2e-1, 1.1e-1..1.4e-1,
#            1.2e-1..1.5e-1
#     The limit is 3e-2 at the first layer and 4.5e-3 more a layer: over the
#     largest seen by 1.6 times or more, under fp8's least by 1.6 times or
#     more, at every layer.
#   cross-entropy of a token, tokens routed alike, nats:
#       bf16 0.062..0.117      fp8 0.424..0.540
# The others guard something else and do NOT separate the two:
#   last state of the flipped tokens: bf16 0.073..0.101, fp8 0.130..0.164: a
#     held expert's share of a routed layer's output given or taken; the
#     limit, 3 times the largest seen, catches a fault confined to the
#     near-tie tokens that is larger than a swap.
#   mean loss, relative: bf16 2.2e-6..4.4e-5, fp8 7.5e-6..1.9e-4: a mean near
#     ln(vocab) over 4096 rows averages the precision away; the limit is the
#     dense LM cell's (`builders/transformer.py`), 4.5 times the largest
#     seen: it holds the loss's assembly (targets, the mean).
#   gradients of the last Mamba-2, attention and LatentMoE layer, a matrix's
#     |g_sys - g_ref| / |g_ref| (Frobenius): 0.033..0.052 at most outside
#     the routed path; 0.234..0.373 on the routed path's own matrices
#     (`ROUTED_PATH`), which ~1400 of 90112 rows train, so that the 3-5% of
#     tokens that give or take a row move them by a quarter. They hold the
#     backward's assembly (the scan's transposed products, the cut buffer's
#     fill-up, the kernels' vjps); limits twice the largest seen.
# What the comparison must refuse, and does (same runs): the reference
# without the shared expert is 1.54..1.79 from the system's states.
TOL_FLIPPED = 0.12         # share of tokens flipped in any routed layer
TOL_STATE = 3e-2           # max |system - reference| / max |reference|, the
TOL_STATE_A_LAYER = 4.5e-3  # first layer's state; this much more a layer
TOL_NLL = 2.5e-1           # max |system - reference|, a token's nats
TOL_STATE_FLIPPED = 0.3    # as TOL_STATE, the flipped tokens' last state
TOL_LOSS = 2e-4            # relative, the mean loss
TOL_GRAD = 1e-1            # |g_system - g_reference| / |g_reference|
#                            (Frobenius) of each matrix of the last layer of
#                            each kind; the routed path's (`ROUTED_PATH`):
TOL_GRAD_HELD = 7.5e-1

KINDS = {"M": "ssm", "*": "attn", "E": "moe"}
# What the routed path's rows alone train: a flipped token gives or takes a
# whole row of the ~1400 that reach the held experts.
ROUTED_PATH = ("'router'", "'latent_in'", "'latent_out'", "'w_in'",
               "'w_out'")


def fp8(tree):
    """Every matrix of `tree` rounded to e4m3's precision (four significant
    bits, round to nearest even) on the bits of its f32: a convert to
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
        lambda x: rounded(x) if x.ndim >= 2 else x, tree)


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops, flops_nemo3
    from benchmark.references import nemo3 as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step, routing_stats

    job = config["job"]
    hidden = config["hidden_size"]
    pattern = tuple(KINDS[c] for c in config["hybrid_override_pattern"])
    layers = config["num_hidden_layers"]
    heads, kv_heads, head_dim = (config["num_attention_heads"],
                                 config["num_key_value_heads"],
                                 config["head_dim"])
    ssm = (config["mamba_num_heads"], config["mamba_head_dim"],
           config["n_groups"], config["ssm_state_size"],
           config["conv_kernel"])
    chunk_ssd = config["chunk_size"]
    experts = config["published_n_routed_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_tok"]
    latent, width, shared = (config["moe_latent_size"],
                             config["moe_intermediate_size"],
                             config["moe_shared_expert_intermediate_size"])
    vocab = config["vocab_size"]
    eps = config["layer_norm_epsilon"]
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (len(pattern) != layers or held != config["n_routed_experts"]
            or config["model_type"] != "nemotron_h"
            or config["expand"] * hidden
            != config["published_mamba_num_heads"] * config["mamba_head_dim"]
            or config["mlp_hidden_act"] != "relu2"
            or config["mamba_hidden_act"] != "silu"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["n_shared_experts"] != 1
            or config["num_nextn_predict_layers"] != 0
            or config["attention_bias"] or config["mlp_bias"]
            or config["mamba_proj_bias"] or not config["use_conv_bias"]
            or config["tie_word_embeddings"] or config["residual_in_fp32"]
            or config["norm_eps"] != eps
            or config["sliding_window"] is not None):
        raise ValueError("this builder makes a pattern of M, * and E "
                         "layers: Mamba-2 with expand x hidden inner "
                         "channels and a convolution bias, attention with "
                         "no bias and no window, sigmoid-routed relu2 "
                         "experts with no group limit and one shared "
                         "expert, an untied head, no prediction module")
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, embed_dim=hidden,
        mlp_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rotary=False, norm_eps=eps,
        layer_types=pattern, ssm_heads=ssm[0], ssm_head_dim=ssm[1],
        ssm_groups=ssm[2], ssm_state=ssm[3], ssm_conv=ssm[4],
        ssm_chunk=chunk_ssd,
        ssm_dt_init=(config["time_step_min"], config["time_step_max"],
                     config["time_step_floor"]),
        moe_experts=experts, moe_dim=width, moe_top_k=top_k,
        moe_capacity_factor=None, moe_gated=False,
        moe_renormalize=config["norm_topk_prob"], moe_scoring="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_dim=shared, moe_shared_gated=False, moe_act="relu2",
        moe_latent_dim=latent, moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"pattern": pattern, "eps": eps, "ssm_heads": ssm[0],
            "ssm_head_dim": ssm[1], "ssm_groups": ssm[2],
            "ssm_state": ssm[3], "top_k": top_k,
            "norm_topk_prob": config["norm_topk_prob"],
            "route_scale": float(config["routed_scaling_factor"]),
            "held": (first, held)}
    # The last layer of each kind: what the gradients are compared on (the
    # backward pass below them is not needed, on either side).
    compared = sorted(max(i for i, k in enumerate(pattern) if k == kind)
                      for kind in set(pattern))
    chunk = min(int(job["loss_chunk"]), length)
    # The rate climbs from 0 to `learning_rate` over `warmup_steps`: a
    # window is the job's first ~140 steps (the configuration's `assumed`).
    opt = optax.adamw(optax.linear_schedule(0.0, job["learning_rate"],
                                            int(job["warmup_steps"])),
                      b1=job["adam_b1"], b2=job["adam_b2"],
                      eps=job["adam_eps"], weight_decay=job["weight_decay"])

    def head_loss(params, hid, x, weights=None):
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(x, -1, axis=1),
            chunk=chunk, weights=weights)

    def loss_fn(params, batch):
        hid = model.apply({"params": params}, batch["x"], batch["pos"],
                          return_hidden=True)
        return head_loss(params, hid, batch["x"])

    def forward(params, seq):
        """Of ONE sequence [L]: every layer's state [layers, L, D] f32, each
        row's own cross-entropy [L] (the gradient of the weighted loss by a
        row's weight, as the timed loss forms it), the loss, and the step's
        counters."""
        x = seq[None]
        hid, state = model.apply(
            {"params": params}, x, None, return_hidden=True,
            mutable=["intermediates"],
            capture_intermediates=lambda mdl, name: isinstance(
                mdl, transformer.Block) and name == "__call__")
        inter = state["intermediates"]
        states = jnp.stack([
            inter["block_%d" % i]["__call__"][0][0].astype(jnp.float32)
            for i in range(layers)])
        nll = jax.grad(lambda w: head_loss(params, hid, x, w))(
            jnp.zeros((1, length), jnp.float32))[0]
        routing = routing_stats(inter)
        return (states, nll, head_loss(params, hid, x),
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "held_rows_largest": jnp.max(
                     routing["assignments"][:, first:first + held]),
                 "dropped": routing["dropped"],
                 "state_max": models.ssd_stats(inter)})

    def split(params):
        """(the compared layers' parameters, the rest)."""
        names = ["block_%d" % i for i in compared]
        return ({n: params[n] for n in names},
                {n: v for n, v in params.items() if n not in names})

    def system_grads(params, seq):
        mine, rest = split(params)
        batch = {"x": seq[None], "pos": None}
        return jax.grad(lambda m: loss_fn(dict(rest, **m), batch))(mine)

    def reference_grads(params, seq):
        mine, rest = split(params)
        return jax.grad(lambda m: reference.loss(dict(rest, **m), seq,
                                                 arch))(mine)

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    def init_params(key):
        return model.init(key, jnp.zeros((1, length), jnp.int32))["params"]

    def make_tokens(key):
        return jax.random.randint(key, (n_seq, length), 0, vocab, jnp.int32)

    def make_state(kp, kt):
        params = init_params(kp)
        tokens = make_tokens(kt)
        pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None],
                               tokens.shape)
        return params, opt.init(params), {"x": tokens, "pos": pos}

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(mesh.axis_names[0]))
    make = jax.jit(make_state, out_shardings=(rep, rep, dat))
    state = (jax.eval_shape if abstract else lambda f, *a: f(*a))(
        make, k_param, k_tok)

    system = jax.jit(forward)
    ref_fn = jax.jit(lambda p, seq, shared: reference.forward(
        p, seq, arch, shared=shared))

    def readings(system_params, params, seq, gradients=True):
        """How far the system on `system_params` is from the reference on
        `params`, one sequence `seq` [L]: the errors the limits above are
        set on, both sides' counters, and the same system against the
        reference of ANOTHER model (no shared expert), which the comparison
        must refuse."""
        states, nll, loss, counters = system(system_params, seq)
        counters = jax.device_get(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, 1.0)
            no_shared = ref_fn(params, seq, 0.0)["states"]
        # [routed layers, L, held]: the HELD experts each token chose
        sys_held = jnp.any(jax.nn.one_hot(
            counters["chosen"], experts, dtype=jnp.bool_),
            axis=-2)[..., first:first + held]
        ref_held = ref["chosen"][..., first:first + held]
        flipped = jnp.any(sys_held != ref_held, axis=(0, 2))

        def state_err(theirs, mask):
            err = jnp.max(jnp.abs(states - theirs), axis=-1)  # [layers, L]
            top = jnp.max(jnp.abs(theirs), axis=(1, 2))
            return [float(v) for v in jnp.max(
                jnp.where(mask[None], err, 0.0), axis=1) / top]

        loss, ref_loss = float(loss), float(ref["loss"])
        out = {
            "loss_system": loss, "loss_reference": ref_loss,
            "flipped": float(jnp.mean(flipped)),
            "state": state_err(ref["states"], ~flipped),
            "state_flipped": state_err(ref["states"], flipped)[-1],
            "nll": float(jnp.max(jnp.where(
                flipped, 0.0, jnp.abs(nll - ref["nll"])))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "held_rows_largest": int(counters["held_rows_largest"]),
            "held_rows_reference": [int(v) for v in ref["held_rows"]],
            "dropped": int(counters["dropped"]),
            "state_max": float(counters["state_max"]),
            "state_max_reference": float(ref["state_max"]),
            "against_no_shared_expert": max(state_err(no_shared, ~flipped))}
        del ref, no_shared, states
        if gradients:
            mine = jax.jit(system_grads)(system_params, seq)
            with jax.default_matmul_precision("highest"):
                theirs = jax.jit(reference_grads)(params, seq)
            errs = jax.tree_util.tree_map(
                lambda a, b: float(jnp.linalg.norm((a - b).ravel())
                                   / jnp.linalg.norm(b.ravel())),
                mine, theirs)
            out["grad"] = {
                jax.tree_util.keystr(path): e for path, e in
                jax.tree_util.tree_flatten_with_path(errs)[0]
                # no gradient reaches the selection bias
                if "select_bias" not in jax.tree_util.keystr(path)}
        return out

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, the largest held expert's rows, the scan's
        largest state), then from the seed again the system's forward (the
        chunked scan, flash at a head group of 16, the rows' kernels and
        grouped matmuls over the held experts, the chunked loss, bf16)
        against the plain reference on the sequence: every layer's state,
        the routing, each token's cross-entropy, the loss; the gradients of
        the last layer of each kind; the train step's first loss against
        the reference's; and two comparisons that must fail: a reference
        without the shared expert, and the system with its matrices in
        fp8's precision."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            last = jax.device_get(system(final_params, tokens[0])[3])
            del final_params
            params = jax.jit(init_params)(k_param)
            r = readings(params, params, tokens[0])
            low = readings(jax.jit(fp8)(params), params, tokens[0],
                           gradients=False)
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        limits = [TOL_STATE + TOL_STATE_A_LAYER * i
                  for i in range(len(r["state"]))]
        grad = {routed: max(e for k, e in r["grad"].items()
                            if any(n in k for n in ROUTED_PATH) == routed)
                for routed in (False, True)}
        print("INFO " + json.dumps({
            "held_share_first_step": r["held_share"],
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "held_rows_largest_first_step": r["held_rows_largest"],
            "held_rows_largest_after_the_window":
            int(last["held_rows_largest"]),
            "held_rows_reference_by_layer": r["held_rows_reference"],
            "ssd_state_max_first_step": r["state_max"],
            "ssd_state_max_reference": r["state_max_reference"],
            "ssd_state_max_after_the_window": float(last["state_max"]),
            "flipped_tokens_share": r["flipped"],
            "state_err_by_layer_agreeing_tokens": r["state"],
            "state_err_flipped_tokens": r["state_flipped"],
            "nll_err_agreeing_tokens": r["nll"], "loss_err": r["loss"],
            "grad_err": r["grad"],
            "against_no_shared_expert": r["against_no_shared_expert"],
            "fp8": {k: low[k] for k in ("flipped", "state", "nll", "loss",
                                        "state_flipped")}}), flush=True)
        return [
            ("no assignment dropped, and the held experts take their share "
             "of them (%d of %d experts: %.4f under even routing)"
             % (held, experts, held / experts),
             r["dropped"] == 0 and int(last["dropped"]) == 0
             and all(0.0 < s < 1.0 for s in r["held_share"]),
             "held share by routed layer %s -> %s; the largest held "
             "expert's rows %d -> %d"
             % (["%.4f" % s for s in r["held_share"]],
                ["%.4f" % float(s) for s in last["held_share"]],
                r["held_rows_largest"], int(last["held_rows_largest"]))),
            ("the scan's carried state stays finite, and as large as the "
             "sequential scan's",
             0.5 <= r["state_max"] / r["state_max_reference"] <= 2.0,
             "max |S| %.4e against the reference's %.4e; after the window "
             "%.4e" % (r["state_max"], r["state_max_reference"],
                       float(last["state_max"]))),
            ("routings flipped against the float32 reference stay rare "
             "(tokens whose HELD experts among the top-%d differ in any "
             "routed layer)" % top_k,
             r["flipped"] <= TOL_FLIPPED, "share %.4f, tol %.2f"
             % (r["flipped"], TOL_FLIPPED)),
            ("every layer's state agrees with the plain reference (%d "
             "layers, the tokens routed alike)" % layers,
             all(e <= t for e, t in zip(r["state"], limits)),
             "max rel err by layer %s, tol %.1e + %.1e a layer"
             % (["%.3e" % e for e in r["state"]], TOL_STATE,
                TOL_STATE_A_LAYER)),
            ("a flipped routing moves a token's last state by one held "
             "expert's share and no more",
             r["state_flipped"] <= TOL_STATE_FLIPPED,
             "max rel err %.3e, tol %.1f" % (r["state_flipped"],
                                             TOL_STATE_FLIPPED)),
            ("system cross-entropy agrees with the plain reference token by "
             "token (the rows' own losses of the chunked call, the tokens "
             "routed alike)", r["nll"] <= TOL_NLL,
             "max abs err %.3e nats, tol %.2f" % (r["nll"], TOL_NLL)),
            ("system loss agrees with the plain reference",
             r["loss"] <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (r["loss_system"], r["loss_reference"], r["loss"], TOL_LOSS)),
            ("the step's first loss agrees with the reference",
             err_step <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, r["loss_reference"], err_step, TOL_LOSS)),
            ("the gradients of the last layer of each kind agree with the "
             "reference's (layers %s)" % compared,
             grad[False] <= TOL_GRAD and grad[True] <= TOL_GRAD_HELD,
             "largest rel err %.3e, on the routed path's matrices %.3e "
             "(tol %.2f and %.2f)"
             % (grad[False], grad[True], TOL_GRAD, TOL_GRAD_HELD)),
            ("what must be refused is: a reference with no shared expert, "
             "and the system with its matrices in fp8's precision",
             r["against_no_shared_expert"] > limits[-1]
             and (any(e > t for e, t in zip(low["state"], limits))
                  or low["flipped"] > TOL_FLIPPED or low["nll"] > TOL_NLL),
             "no shared expert: state rel err %.3e (the widest tol %.2e); "
             "fp8: flipped %.4f, nll %.3e, state by layer %s"
             % (r["against_no_shared_expert"], limits[-1], low["flipped"],
                low["nll"], ["%.3e" % e for e in low["state"]])),
        ]

    kinds = {k: pattern.count(k) for k in KINDS.values()}
    # The layers under `block_remat` (the first k) run their forward twice:
    # its kernels execute (and their time is in the trace) twice too.
    again = {k: sum(1 for i, t in enumerate(pattern)
                    if t == k and i < cfg.block_remat) for k in kinds}
    group = heads // kv_heads
    forward_k, backward_k = (list(profile.flash_plan(
        1, heads, length, head_dim, group, cfg.dtype, b))
        for b in (False, True))
    kernels = forward_k + backward_k
    rows = top_k * length * held / experts  # expected on the held experts
    # a forward is 2 of an ungated layer's 6 grouped matmuls
    gmm = kinds["moe"] + again["moe"] / 3.0
    itemsize = jnp.dtype(job["compute_dtype"]).itemsize
    sizes = (pattern, hidden, vocab)
    moe = (experts, held, latent, width, shared)
    attn = (heads, kv_heads, head_dim)
    counts = {
        "model_flops_per_item": flops_nemo3.model_flops_per_token(
            *sizes, length, ssm, attn, moe, top_k, chunk_ssd),
        # per step and per device, by the kernels `flash_plan` names
        "flash_kernels": kernels,
        "flash_executed_flops": sum(
            n * flops.flash_executed_flops(names, 1, heads, length, head_dim)
            for n, names in ((kinds["attn"], kernels),
                             (again["attn"], forward_k))),
        "flash_min_bytes": sum(
            n * flops.flash_min_bytes(names, 1, heads, kv_heads, length,
                                      head_dim, itemsize)
            for n, names in ((kinds["attn"], kernels),
                             (again["attn"], forward_k))),
        "moe_gmm_executed_flops": gmm * flops_nemo3.ungated_experts_flops(
            rows, latent, width),
        "moe_gmm_min_bytes": gmm * flops_nemo3.ungated_experts_min_bytes(
            rows, latent, width, held, itemsize,
            jnp.dtype(job["param_dtype"]).itemsize),
        # the scan: a forward, its two gradient products a product, and the
        # forward again where the layer is recomputed
        "ssd_executed_flops": (3 * kinds["ssm"] + again["ssm"])
        * n_seq // mesh.size * flops_nemo3.ssd_forward_flops(
            length, ssm[0], ssm[1], ssm[2], ssm[3], chunk_ssd),
        "ssd_min_bytes": (3 * kinds["ssm"] + again["ssm"])
        * n_seq // mesh.size * flops_nemo3.ssd_min_bytes(
            length, ssm[0], ssm[1], ssm[2], ssm[3], itemsize),
        "params": flops_nemo3.params(*sizes, ssm, attn, moe),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "readings": readings,
            "init_params": init_params, "make_tokens": make_tokens}
