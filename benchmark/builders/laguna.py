"""Builder for Laguna configurations (poolside, `model_type` `laguna`):
`models.Transformer` with an attention KIND a layer (`attention_types`) whose
kinds differ by more than their mask (`attention_shapes`): full layers of 48
query heads under the causal triangle, half of each head on YaRN's rotation
at base 500000; window layers of 64 query heads under the causal band the
flash kernels take by rule (`ops.BandMask`), the whole head on the plain
rotation at base 10000; both on the same 8 kv heads, a sigmoid gate a head
on the attention's output (`attention_gate`); one leading dense layer, then
routed layers of sigmoid-scored experts of which this chip HOLDS a part
beside a shared one; trained through `parallel.make_train_step` on the next
token's cross-entropy (the chunked vocabulary loss; no balancing term), AdamW
inside a linear warm-up.

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/laguna.py` outside the
window.
"""

import json

# Agreement of the bf16 system with the float32 reference at the published
# widths, one sequence of 8192, the seeded state of the configuration (my
# chip runs, PR 62: the benchmark's own runs, a seed each; PERF.md s6 has
# every seed's numbers). As `builders/kanana.py`: the reference computes every
# routed layer with the experts the SYSTEM's top-8 chose
# (`references/laguna.py::routing_weights(follow=)`) and says how often its own
# top-8 differs and how near a tie each such choice was, so EVERY position is
# compared and the two gradients are of the same function. Each limit that
# guards the PRECISION lies between two readings: the largest the bf16 system
# gave over its seeds, and the least the same system gave with every matrix
# rounded to fp8's precision (e4m3), which `verify` computes in every run and
# which has to come out as not correct (nine seeds):
#   routed layers x positions whose top-8 set is not the reference's own:
#     bf16 0.144..0.150, fp8 0.794..0.801.
#   how far from a tie the farthest such choice lies (the reference's 8th
#     largest score less the least one chosen, over the former): bf16
#     0.013..0.023, fp8 0.166..0.228.
#   a block's state, every position, max |sys - ref| / max |ref|, the worst
#     block: bf16 0.025..0.034 (it grows a block at a time from 0.011), fp8
#     0.240..0.301.
#   a block's attention branch (W_o's output before the residual add: what
#     the layer's KIND decides), likewise: bf16 0.023..0.035 (the second full
#     layer's, block 4, in most seeds), fp8 0.209..0.266; the LEAST any
#     reference of another model read is a window of twice the keys,
#     0.088..0.120 (the first rows of a sequence, which see few keys, set
#     max |ref| and a window does not move them; the others read 0.55..1.07).
#     The limit stands seven deviations above the bf16 readings' mean and
#     four below that variant's.
#   the logits of 64 sampled rows, likewise: bf16 0.025..0.037, fp8
#     0.234..0.291.
#   the first gradient, a leaf of each kind of parameter (a full and a window
#     layer's `query` and `gate`, the dense layer's `mlp_gate`, the first
#     routed layer's router, held experts' `w_gate` and `shared_gate`),
#     |sys - ref|_2 / |ref|_2, the worst leaf: bf16 0.104..0.135 (the router;
#     the held experts 0.093..0.100, every other leaf 0.047..0.052), fp8 its
#     worst 0.602..0.637 and its LEAST leaf 0.316..0.319; the router's
#     gradient under routing weights of another model 0.893..1.558.
TOL_FLIPPED = 0.4          # share of routed layers x positions that differ
TOL_MARGIN = 7e-2          # how far from a tie a differing choice may lie
TOL_STATE = 8e-2           # max |system - reference| / max |reference|
TOL_ATTN = 6e-2            # likewise, a block's attention branch
TOL_LOGITS = 8e-2          # likewise, the sampled rows' logits
TOL_GRAD_LEAF = 2.5e-1     # a picked leaf's |system - reference|_2 / |ref|_2
# These guard something else:
#   a row's cross-entropy, nats: bf16 0.172..0.235, fp8 0.85..1.22: a maximum
#     over 8192 rows; the limit, 2.5 times the largest seen, holds the rows'
#     targets: a head that reads the wrong token is several nats off.
#   mean loss, relative: bf16 0..2.0e-5, fp8 0..5e-4: a mean near
#     ln(vocab) over 8192 rows averages the precision away; the limit is the
#     harness's accepted cells' (a hundred times the largest seen) and holds
#     the loss's ASSEMBLY (targets, the mean).
TOL_NLL = 0.6              # max |system - reference|, a row's nats
TOL_LOSS = 2e-3            # relative, the mean loss

KINDS = {"sliding_attention": "window", "full_attention": "full"}
LOGIT_ROWS = 64            # rows of the sequence whose logits are compared


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops_laguna, flops_moe
    from benchmark.builders.sdar import rounded_to_e4m3
    from benchmark.references import laguna as reference
    from horovod_tpu import models, profile
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import BandMask
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step, routing_stats

    job = config["job"]
    hidden = config["hidden_size"]
    kv_heads = config["num_key_value_heads"]
    head_dim = config["head_dim"]
    layers = config["num_hidden_layers"]
    experts = config["published_num_experts"]
    first, held = config["held_experts"]
    top_k = config["num_experts_per_tok"]
    width, expert_width, shared_width = (
        config["intermediate_size"], config["moe_intermediate_size"],
        config["shared_expert_intermediate_size"])
    vocab = config["vocab_size"]
    eps = config["rms_norm_eps"]
    window = config["sliding_window"]
    kinds = tuple(KINDS[t] for t in config["layer_types"])
    per_layer = config["num_attention_heads_per_layer"]
    heads = {kind: per_layer[kinds.index(kind)] for kind in set(kinds)}
    rope = config["rope_parameters"]
    full_keys, window_keys = rope["full_attention"], rope["sliding_attention"]
    ffn = config["mlp_layer_types"]
    dense_layers = ffn.index("sparse")
    routed_layers = layers - dense_layers
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference is of one sequence): batch %d on %d "
                         "chips" % (n_seq, mesh.size))
    if (len(kinds) != layers or len(per_layer) != layers or len(ffn) != layers
            or set(kinds) != {"full", "window"}
            or any(n != heads[k] for n, k in zip(per_layer, kinds))
            or heads["full"] != config["num_attention_heads"]
            or set(ffn[dense_layers:]) != {"sparse"}
            or held != config["num_experts"]
            or config["model_type"] != "laguna" or config["gating"] is not True
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["moe_apply_router_weight_on_input"]
            or full_keys["rope_type"] != "yarn"
            or window_keys["rope_type"] != "default"
            or window_keys["partial_rotary_factor"] != 1
            or full_keys["partial_rotary_factor"]
            != config["partial_rotary_factor"]):
        raise ValueError("this builder makes a full or a window attention a "
                         "layer at the kind's own head count, a gate a head, "
                         "YaRN on a part of the full layers' heads and the "
                         "plain rotation of the window layers' whole heads, "
                         "leading dense layers then routed ones, no bias, an "
                         "untied head")
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    yarn = transformer.Yarn(
        float(full_keys["factor"]), float(full_keys["beta_fast"]),
        float(full_keys["beta_slow"]),
        int(full_keys["original_max_position_embeddings"]), mscale=1.0,
        mscale_all_dim=0.0)
    factor = (transformer.yarn_mscale(yarn.factor, yarn.mscale)
              / transformer.yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    if abs(factor - full_keys["attention_factor"]) > 1e-12:
        raise ValueError("the program's YaRN gives cos and sin the factor "
                         "%r, the configuration %r"
                         % (factor, full_keys["attention_factor"]))
    rotary_dim = int(full_keys["partial_rotary_factor"] * head_dim)
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads["full"],
        num_kv_heads=kv_heads, head_dim=head_dim, embed_dim=hidden,
        mlp_dim=width, mlp_gated=True,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], attention_types=kinds,
        attention_window=window,
        attention_shapes=(
            ("full", models.AttentionShape(
                heads["full"], float(full_keys["rope_theta"]), rotary_dim,
                yarn)),
            ("window", models.AttentionShape(
                heads["window"], float(window_keys["rope_theta"])))),
        attention_gate="head", norm_eps=eps, moe_experts=experts,
        moe_every=1, first_k_dense=dense_layers, moe_dim=expert_width,
        moe_top_k=top_k, moe_capacity_factor=None, moe_gated=True,
        moe_renormalize=True, moe_scoring="sigmoid",
        moe_route_scale=float(config["moe_routed_scaling_factor"]),
        moe_shared_dim=shared_width, moe_held=(first, held),
        block_remat=int(job["block_remat"]),
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    arch = {"kinds": kinds, "dense": dense_layers, "eps": eps, "heads": heads,
            "full": full_keys, "window": window_keys,
            "sliding_window": window, "top_k": top_k,
            "route_scale": config["moe_routed_scaling_factor"],
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

    def objective(params, tokens, weights=None, capture=False):
        """(the loss of sequences `tokens` [B, L] (`weights`: a weight a row
        in place of the mean), the model's collections, the normed last
        state)."""
        hid, state = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["intermediates"],
            **({"capture_intermediates": lambda mdl, name: isinstance(
                mdl, (transformer.Block, transformer.Attention))
                and name == "__call__"} if capture else {}))
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1),
            chunk=chunk, weights=weights), state["intermediates"], hid

    def loss_fn(params, batch):
        return objective(params, batch["x"])[0]

    def forward(params, seq):
        """Of ONE sequence [L]: every block's output and every block's
        attention branch [layers, L, C] f32, the logits of `logit_rows` as
        the head forms them, each row's own cross-entropy [L] (the gradient
        of the weighted loss by a row's weight, as the timed loss forms it),
        the loss, and the step's counters."""
        x = seq[None]
        loss, inter, hid = objective(params, x, capture=True)
        blocks = [inter["block_%d" % i] for i in range(layers)]
        states = jnp.stack([b["__call__"][0][0].astype(jnp.float32)
                            for b in blocks])
        attn = jnp.stack([b["attn"]["__call__"][0][0].astype(jnp.float32)
                          for b in blocks])
        logits = jnp.dot(hid[0, logit_rows],
                         params["lm_head"]["kernel"].astype(hid.dtype),
                         preferred_element_type=jnp.float32)
        nll = jax.grad(lambda w: objective(params, x, w)[0])(
            jnp.zeros((1, length), jnp.float32))[0]
        routing = routing_stats(inter)
        return (states, attn, logits, nll, loss,
                {"chosen": routing["chosen"],
                 "held_share": routing["held_share"],
                 "dropped": routing["dropped"]})

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))

    out_gain = float(config["seeded_state"]["attention_out_gain"])

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

    # The gradient is held leaf by leaf, one leaf of each kind of parameter:
    # the first full layer's and the first window layer's `query` (where the
    # flash backward of each call writes dq) and `gate`, the dense layer's
    # `mlp_gate`, and of the first routed layer the router, the held experts'
    # `w_gate` and the shared expert's `shared_gate`.
    probe = {kind: "block_%d" % kinds.index(kind) for kind in heads}
    routed = "block_%d" % dense_layers

    def picked(grads):
        out = {}
        for kind, block in probe.items():
            a = grads[block]["attn"]
            out["%s/query (%s)" % (block, kind)] = a["query"]["kernel"]
            out["%s/gate (%s)" % (block, kind)] = a["gate"]["kernel"]
        out["block_0/mlp_gate (the dense layer)"] = \
            grads["block_0"]["mlp_gate"]["kernel"]
        moe = grads[routed]["moe_mlp"]
        out[routed + "/router"] = moe["router"]
        out[routed + "/w_gate (the held experts)"] = moe["w_gate"]
        out[routed + "/shared_gate"] = moe["shared_gate"]["kernel"]
        return out

    # One program for a system's forward AND its picked gradient leaves.
    system = jax.jit(lambda p, seq: forward(p, seq) + (picked(jax.grad(
        lambda q: objective(q, seq[None])[0])(p)),))

    def _ref(p, seq, variant, follow):
        out = reference.forward(p, seq, arch, variant, follow)
        out["logits"] = out["logits"][logit_rows]
        return out

    ref_fn = jax.jit(_ref)
    ref_grad = jax.jit(lambda p, seq, variant, follow: picked(
        reference.gradient(p, seq, arch, variant, follow)))

    def chosen_sets(counters):
        """[routed layers, L, E] bool from the system's chosen indices."""
        return jnp.any(jax.nn.one_hot(counters["chosen"], experts,
                                      dtype=jnp.bool_), axis=-2)

    def err(ours, theirs):
        """max |ours - theirs| / max |theirs| by the leading axis."""
        axes = tuple(range(1, ours.ndim))
        return [float(v) for v in jnp.max(jnp.abs(ours - theirs), axis=axes)
                / jnp.max(jnp.abs(theirs), axis=axes)]

    def leaf_err(grads, ref_grads):
        return {n: float(jnp.linalg.norm(grads[n] - ref_grads[n])
                         / jnp.linalg.norm(ref_grads[n]))
                for n in sorted(grads)}

    # A variant of ANOTHER model is read where it changes the stack
    # (`reference.CHANGES`): an attention variant by the attention branches
    # from the first layer of the kind it changes on, a routing variant by
    # the first routed layer's router's gradient.
    routing_variants = {name: v for name, v in reference.VARIANTS.items()
                        if reference.CHANGES[v] == "routing"}
    attention_variants = {name: v for name, v in reference.VARIANTS.items()
                          if name not in routing_variants}

    def readings(seen, params, seq, ref_grads, other_models=False):
        """How far a system's forward and picked gradient leaves `seen`
        (`system(...)` of its parameters on `seq` [L]) are from the reference
        on `params`: the errors the limits above are set on and both sides'
        numbers. The reference computes every routed layer with the experts
        the SYSTEM chose and says how often its own top-k differs and how
        near a tie each such choice was; `ref_grads` are its picked leaves on
        the same sets. (The system's own parameters are not taken: two sets
        beside the reference's working set do not fit the chip.)
        `other_models`: also the same system against the reference's
        variants, other models, each of which the comparison must refuse."""
        states, attn, logits, nll, loss, counters, grads = seen
        follow = chosen_sets(counters)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, seq, reference.AS_PUBLISHED, follow)
        flipped = jnp.any(follow != ref["chosen"], axis=2)  # [routed, L]
        loss, ref_loss = float(loss), float(ref["loss"])
        by_block = err(attn, ref["attn"])
        out = {
            "loss_system": loss, "loss_reference": ref_loss,
            "flipped": float(jnp.mean(flipped)),
            "flipped_by_layer": [float(v) for v in jnp.mean(flipped, axis=1)],
            "margin": float(jnp.max(ref["margin"])),
            "state": err(states, ref["states"]),
            "attn": by_block,
            "attn_by_kind": {kind: max(e for e, k in zip(by_block, kinds)
                                       if k == kind) for kind in heads},
            "logits": max(err(logits[None], ref["logits"][None])),
            "nll": float(jnp.max(jnp.abs(nll - ref["nll"]))),
            "loss": abs(loss - ref_loss) / abs(ref_loss),
            "held_share": [float(v) for v in counters["held_share"]],
            "held_rows_reference": [int(v) for v in ref["held_rows"]],
            "dropped": int(counters["dropped"]),
            "grad": leaf_err(grads, ref_grads)}
        del ref
        if other_models:
            out["against"] = {}
            for name, variant in attention_variants.items():
                with jax.default_matmul_precision("highest"):
                    theirs = ref_fn(params, seq, variant, follow)["attn"]
                layer = kinds.index(reference.CHANGES[variant])
                out["against"][name] = max(err(attn, theirs)[layer:])
                del theirs
            for name, variant in routing_variants.items():
                with jax.default_matmul_precision("highest"):
                    theirs = ref_grad(params, seq, variant, follow)
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
            ("attn", max(r["attn"]) > TOL_ATTN),
            ("logits", r["logits"] > TOL_LOGITS),
            ("grad", max(r["grad"].values()) > TOL_GRAD_LEAF)) if bad]

    group = {kind: n // kv_heads for kind, n in heads.items()}
    rules = {"window": BandMask(window), "full": None}
    forward_plans, backward_plans = ({
        kind: profile.flash_plan(
            1, heads[kind], length, head_dim, group[kind], cfg.dtype, b,
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
                bqp = p.block_q // group[kind]
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

    def plan_lines():
        """What `flash_plan` says of the cell's two calls, for people."""
        return {kind: {name: {
            "path": p.path, "held": p.held, "blocks": [p.block_q, p.block_k],
            "grid": list(p.grid), "vmem_bytes": p.vmem_bytes,
            "tiles_visited_masked_skipped": [
                p.tiles_visited, p.tiles_masked, p.tiles_skipped],
            "cut_k": p.cut_k, "subtiles_visited_masked": [
                p.subtiles_visited, p.subtiles_masked]}
            for name, p in by_kernel.items()}
            for kind, by_kernel in plans.items()}

    def verify(final_params, first_loss):
        """After the window, on one device: the counters of the trained
        parameters (held share, nothing dropped), then from the seed again
        the system's forward, loss and first gradient (the causal flash
        kernels at group 6 and the band-ruled ones at group 8, the two
        rotations, the gate, grouped matmul over the held experts, the
        chunked loss, bf16) against the plain reference on the sequence:
        every block's state and attention branch, the sampled rows' logits,
        the routing, each row's cross-entropy, the loss, a gradient leaf of
        each kind of parameter; the train step's first loss against the
        reference's; the plan's tile counts against the mask; the same
        system on e4m3 matrices and the reference's variants of another
        model, all of which the comparison must refuse."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            seq = tokens[0]
            last = jax.device_get(system(final_params, seq)[5])
            del final_params
            # The caller still holds the trained parameters (4.2 GiB), so
            # ONE more set at a time, and a system's results (1.3 GiB) wait
            # on the HOST: the system on e4m3 matrices first, from the seed
            # in one program, then the seeded parameters themselves; then the
            # reference's gradient on the bf16 system's sets (4.4 GiB beside
            # the parameters, by a compile for the described chip); then the
            # reference's forwards (2.7), a system's results at a time.
            low_seen = jax.device_get(system(jax.jit(
                lambda key: rounded_to_e4m3(init_params(key)))(k_param), seq))
            params = jax.jit(init_params)(k_param)
            seen = jax.device_get(system(params, seq))
            with jax.default_matmul_precision("highest"):
                ref_grads = ref_grad(params, seq, reference.AS_PUBLISHED,
                                     chosen_sets(seen[5]))
            r = readings(seen, params, seq, ref_grads, other_models=True)
            del seen
            low = readings(low_seen, params, seq, ref_grads)
            del params, ref_grads, low_seen
        err_step = abs(first_loss - r["loss_reference"]) \
            / abs(r["loss_reference"])
        counted = tiles_by_the_mask()
        planned = {kind: {name: [p.tiles_visited, p.tiles_masked,
                                 p.tiles_skipped]
                          for name, p in by_kernel.items()}
                   for kind, by_kernel in plans.items()}
        against = r["against"]
        limit = {name: TOL_ATTN if name in attention_variants
                 else TOL_GRAD_LEAF for name in against}
        print("INFO " + json.dumps({
            "held_share_first_step": r["held_share"],
            "held_share_first_step_summed": sum(r["held_share"]),
            "held_share_after_the_window": [float(v)
                                            for v in last["held_share"]],
            "held_share_after_the_window_summed": float(
                sum(last["held_share"])),
            "held_rows_reference_by_layer": r["held_rows_reference"],
            "flipped_share_of_layers_x_positions": r["flipped"],
            "flipped_by_layer": r["flipped_by_layer"],
            "flipped_margin": r["margin"],
            "state_err_by_block": r["state"],
            "attn_branch_err_by_block": r["attn"],
            "attn_branch_err_by_kind": r["attn_by_kind"],
            "logits_err_sampled_rows": r["logits"],
            "nll_err": r["nll"], "loss_err": r["loss"],
            "grad_err_by_leaf": r["grad"],
            "e4m3": {k: low[k] for k in ("flipped", "margin", "state",
                                         "attn", "logits", "nll", "loss",
                                         "grad")},
            "against_other_models": against}), flush=True)
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
             "reference (kinds %s: %d and %d heads on %d, the gate, the two "
             "rotations)" % ("".join(k[0] for k in kinds), heads["full"],
                             heads["window"], kv_heads),
             max(r["attn"]) <= TOL_ATTN,
             "max rel err by block %s, by kind %s, tol %.1e"
             % (["%.3e" % e for e in r["attn"]],
                {k: "%.3e" % e for k, e in r["attn_by_kind"].items()},
                TOL_ATTN)),
            ("the logits of %d sampled rows agree with the plain reference"
             % len(logit_rows), r["logits"] <= TOL_LOGITS,
             "max rel err %.3e, tol %.1e" % (r["logits"], TOL_LOGITS)),
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
            ("the first step's gradient agrees with the reference's, a leaf "
             "of each kind of parameter", max(r["grad"].values())
             <= TOL_GRAD_LEAF, "|sys - ref| / |ref| by leaf %s, tol %.1e"
             % ({k: "%.3e" % v for k, v in r["grad"].items()},
                TOL_GRAD_LEAF)),
            ("flash_plan counts the tiles the masks have, by kind and "
             "kernel", counted == planned,
             "visited, masked, skipped: plan %s, mask %s"
             % (planned, counted)),
            ("references of another model are refused, each by the limit of "
             "what it changes (an attention branch; the router's gradient)",
             all(e > limit[name] for name, e in against.items()),
             "rel err %s; limits %.1e (attention branch), %.1e (gradient)"
             % ({k: "%.3e" % v for k, v in against.items()}, TOL_ATTN,
                TOL_GRAD_LEAF)),
            ("the same system on matrices rounded to e4m3 is refused",
             bool(refused(low)), "breaks %s: flipped %.4f at a margin up to "
             "%.3e, state %s, attention branch %s, logits %.3e, nll %.3e, "
             "gradient %s"
             % (refused(low) or "no limit", low["flipped"], low["margin"],
                ["%.3e" % e for e in low["state"]],
                ["%.3e" % e for e in low["attn"]], low["logits"],
                low["nll"], {k: "%.3e" % v for k, v in low["grad"].items()})),
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
            n * flops_laguna.flash_executed_flops(which, head_dim)
            for n, which in ((calls[kind], plans[kind]),
                             (again[kind], forward_plans[kind]))),
        "min_bytes": sum(
            n * flops_laguna.flash_min_bytes(which, 1, heads[kind], kv_heads,
                                             length, head_dim, itemsize)
            for n, which in ((calls[kind], plans[kind]),
                             (again[kind], forward_plans[kind]))),
        "layers": calls[kind], "forward_again": again[kind]}
        for kind in rules}
    rows = top_k * length * held / experts  # expected on the held experts
    routed_again = max(0, cfg.block_remat - dense_layers)
    gmm = routed_layers + routed_again / 3.0  # a forward is 3 of the 9
    sizes = (hidden, heads, kv_heads, head_dim, width, expert_width,
             shared_width, experts, held)
    counts = {
        "model_flops_per_item": flops_laguna.model_flops_per_token(
            *sizes, top_k, vocab, kinds, dense_layers, length, window),
        # per step and per device, by the tiles `flash_plan` says each
        # kernel visits, a kind of layer at a time, at the kind's own heads
        "flash_kernels": sorted(set().union(*plans.values())),
        "flash_tiles": {kind: {name: [p.tiles_visited, p.tiles_masked,
                                      p.tiles_skipped]
                               for name, p in by_kernel.items()}
                        for kind, by_kernel in plans.items()},
        "flash_plan": plan_lines(),
        "flash_by_kind": by_kind,
        "moe_gmm_executed_flops": gmm
        * flops_moe.gated_experts_flops(rows, hidden, expert_width),
        "moe_gmm_min_bytes": gmm
        * flops_moe.gated_experts_min_bytes(
            rows, hidden, expert_width, held, itemsize=itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_laguna.params(*sizes, vocab, kinds, dense_layers),
    }
    print("INFO " + json.dumps({"flash_plan_by_kind": counts["flash_plan"]}),
          flush=True)
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify, "system": system, "init_params": init_params,
            "make_tokens": make_tokens}
