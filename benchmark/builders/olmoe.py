"""Builder for OLMoE configurations: `models.Transformer` with QK-norm and a
dropless routed feed-forward of gated experts in every layer, trained
through `parallel.make_train_step` (flash attention, chunked vocabulary
loss, the load-balancing loss and the router z-loss at the weights the
configuration states, AdamW).

`build(config, traffic, mesh, seed)` returns what `builders/transformer.py`
returns; `verify` holds the system to `references/olmoe.py` outside the
window and to "no assignment dropped".
"""

import json

# Agreement of the bf16 system with the float32 reference on seeded random
# weights at the published widths, one sequence of 4096 (my chip runs, PR 26:
# 32 seeds of the forward alone and the benchmark's own runs; PERF.md §6).
#
# A token whose top-8 set differs from the reference's in some layer (a near
# tie in the router, decided the other way by bf16 inputs) is a FLIPPED
# token: it is counted, its share is bounded, and it is left out of the
# comparisons that hold the tokens routed alike; the tolerance is not
# widened to swallow the flips. The three limits that guard the PRECISION
# each lie between two readings: the largest the bf16 system gave, and the
# least the same system gave with every matrix rounded to fp8's precision
# (e4m3: four significant bits), which has to come out as not correct, and
# does on all three.
#   flipped share:          bf16 0.077 .. 0.128    fp8 0.57 .. 0.76
#   hidden, agreeing:       bf16 8.9e-3 .. 1.6e-2  fp8 6.2e-2 .. 1.0e-1
#   cross-entropy of a token, agreeing, nats:
#                           bf16 2.5e-2 .. 4.1e-2  fp8 0.20 .. 0.26
# The other limits guard something else and do NOT separate the two:
#   hidden, flipped:        bf16 5.2e-2 .. 8.0e-2  fp8 9.1e-2 .. 1.24e-1
#     A flipped token swaps one near-tied expert's share for another's; the
#     limit, twice the largest reading, catches a fault confined to the
#     near-tie tokens, which the comparisons above leave out.
#   mean loss, relative:    bf16 1.5e-6 .. 5e-5    fp8 5e-6 .. 4.6e-4
#     A mean near ln(vocab) over 4096 tokens averages the precision away.
#     It holds the loss's ASSEMBLY: the balancing term at its weight is
#     1.4e-2 of the loss and the z term 1.8e-3, so a lost or misweighted
#     auxiliary term, or a wrong mean over the batch, fails it.
TOL_FLIPPED = 0.2          # share of tokens flipped in any layer
TOL_HIDDEN = 3e-2          # max |system - reference| / max |reference|
TOL_NLL = 8e-2             # max |system - reference|, a token's nats
TOL_HIDDEN_FLIPPED = 0.15  # as TOL_HIDDEN, over the flipped tokens
TOL_LOSS = 2e-4            # relative, the mean loss with its auxiliary terms


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops, flops_moe
    from benchmark.references import olmoe as reference
    from horovod_tpu import models, profile
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import (make_train_step, router_aux_losses,
                                      routing_stats)

    job = config["job"]
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    width = config["intermediate_size"]       # of ONE expert
    experts = config["num_experts"]
    top_k = config["num_experts_per_tok"]
    vocab = config["vocab_size"]
    eps = config["rms_norm_eps"]
    head_dim = hidden // heads
    length = int(traffic["seq_len"])
    n_seq = int(traffic["batch"])
    if n_seq != mesh.size:
        raise ValueError("this builder runs one sequence a chip (the "
                         "reference forms the router's losses over one "
                         "sequence): batch %d on %d chips"
                         % (n_seq, mesh.size))
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        num_kv_heads=None if kv_heads == heads else kv_heads,
        embed_dim=hidden, mlp_dim=width,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rope_base=config["rope_theta"],
        qk_norm=True, norm_eps=eps,
        moe_experts=experts, moe_every=1, moe_top_k=top_k,
        moe_capacity_factor=None, moe_gated=True,
        moe_renormalize=config["norm_topk_prob"],
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    chunk = min(int(job["loss_chunk"]), length)
    w_balance = job["router_aux_loss_coef"]
    w_z = job["router_z_loss_coef"]
    opt = optax.adamw(job["learning_rate"], b1=job["adam_b1"],
                      b2=job["adam_b2"], eps=job["adam_eps"],
                      weight_decay=job["weight_decay"])

    def forward(params, x, pos=None):
        """(hidden, the loss with its auxiliary terms, routing statistics)
        of sequences x [B, L]."""
        hid, state = model.apply({"params": params}, x, pos,
                                 return_hidden=True,
                                 mutable=["intermediates"])
        ce = chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], jnp.roll(x, -1, axis=1),
            chunk=chunk)
        balance, z = router_aux_losses(state["intermediates"])
        return (hid, ce + w_balance * balance + w_z * z,
                routing_stats(state["intermediates"]))

    def loss_fn(params, batch):
        return forward(params, batch["x"], batch["pos"])[1]

    def token_nll(params, hid, seq):
        """[L]: the system's cross-entropy of each position of ONE sequence
        by itself (the chunked loss on a chunk of one token)."""
        return jax.vmap(lambda h, t: chunked_softmax_cross_entropy(
            h[None, None], params["lm_head"]["kernel"], t[None, None],
            chunk=1))(hid, jnp.roll(seq, -1))

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

    def verify(final_params, first_loss):
        """After the window, on one device: the routing of the trained
        parameters (nothing dropped, how uneven), then from the seed again
        the system's forward (flash, grouped matmul, chunked loss, bf16)
        against the plain reference on the first sequence, and the train
        step's first loss against the reference's over every sequence."""
        dev = mesh.devices.flat[0]
        assigned = top_k * length
        with jax.default_device(dev):
            tokens = jax.jit(make_tokens)(k_tok)
            system = jax.jit(lambda p, seq: forward(p, seq[None]))
            stats_last = jax.device_get(system(final_params, tokens[0])[2])
            del final_params
            params = jax.jit(init_params)(k_param)
            sys_hid, sys_loss, stats = system(params, tokens[0])
            sys_nll = jax.jit(token_nll)(params, sys_hid[0], tokens[0])
            sys_hid = sys_hid[0].astype(jnp.float32)
            stats = jax.device_get(stats)
            ref = jax.jit(lambda p, seq: reference.hidden_and_loss(
                p, seq, layers, config["rope_theta"], eps=eps, top_k=top_k,
                renormalize=config["norm_topk_prob"],
                balance_weight=w_balance, z_weight=w_z))
            with jax.default_matmul_precision("highest"):
                ref_out = [ref(params, tokens[i]) for i in range(n_seq)]
            ref_hid, ref_loss0, parts = ref_out[0]
            ref_loss = sum(float(o[1]) for o in ref_out) / n_seq
            # [layers, L, E]: the system's chosen sets as the reference's
            sys_chosen = jnp.any(jax.nn.one_hot(
                stats["chosen"], experts, dtype=jnp.bool_), axis=-2)
            flipped = jnp.any(sys_chosen != parts["chosen"], axis=(0, 2))
            share = float(jnp.mean(flipped))
            err = jnp.max(jnp.abs(sys_hid - ref_hid), axis=-1)
            scale = float(jnp.max(jnp.abs(ref_hid)))
            err_agree = float(jnp.max(jnp.where(flipped, 0.0, err))) / scale
            err_flipped = float(jnp.max(jnp.where(flipped, err, 0.0))) / scale
            err_nll = float(jnp.max(jnp.where(
                flipped, 0.0, jnp.abs(sys_nll - parts["nll"]))))
        ref_loss0 = float(ref_loss0)
        err_seq = abs(float(sys_loss) - ref_loss0) / abs(ref_loss0)
        err_step = abs(first_loss - ref_loss) / abs(ref_loss)

        def routing(s):
            a = s["assignments"]
            return {"assignments_a_layer": [int(v) for v in a.sum(axis=1)],
                    "dropped": int(s["dropped"]),
                    "largest_expert_over_mean": float(
                        a.max() / (assigned / experts)),
                    "smallest_expert": int(a.min())}

        first, last = routing(stats), routing(stats_last)
        print("INFO " + json.dumps({
            "routing_first_step": first, "routing_after_the_window": last,
            "flipped_tokens_share": share,
            "hidden_err_agreeing_tokens": err_agree,
            "hidden_err_flipped_tokens": err_flipped,
            "nll_err_agreeing_tokens": err_nll,
            "reference_loss_parts": {k: float(parts[k]) for k in (
                "cross_entropy", "load_balance", "router_z")}}), flush=True)
        whole = all(n == assigned for r in (first, last)
                    for n in r["assignments_a_layer"])
        return [
            ("no assignment dropped (every layer's group sizes sum to %d, "
             "at the first step and after the window)" % assigned,
             whole and first["dropped"] == 0 and last["dropped"] == 0,
             "sums %s, %s; dropped %d, %d; largest expert over the mean "
             "%.3f -> %.3f" % (first["assignments_a_layer"],
                               last["assignments_a_layer"],
                               first["dropped"], last["dropped"],
                               first["largest_expert_over_mean"],
                               last["largest_expert_over_mean"])),
            ("routings flipped against the float32 reference stay rare "
             "(tokens whose top-%d set differs in any layer)" % top_k,
             share <= TOL_FLIPPED, "share %.4f, tol %.2f" % (share,
                                                            TOL_FLIPPED)),
            ("system forward agrees with the plain reference (hidden states "
             "of the tokens routed alike, one sequence)",
             err_agree <= TOL_HIDDEN, "max rel err %.3e, tol %.0e"
             % (err_agree, TOL_HIDDEN)),
            ("a flipped routing moves a token's hidden state by one "
             "near-tied expert's share and no more",
             err_flipped <= TOL_HIDDEN_FLIPPED, "max rel err %.3e, tol %.2f"
             % (err_flipped, TOL_HIDDEN_FLIPPED)),
            ("system cross-entropy agrees with the plain reference token "
             "by token (the tokens routed alike, one sequence)",
             err_nll <= TOL_NLL, "max abs err %.3e nats, tol %.0e"
             % (err_nll, TOL_NLL)),
            ("system loss with its auxiliary terms agrees with the plain "
             "reference (one sequence)", err_seq <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (float(sys_loss), ref_loss0, err_seq, TOL_LOSS)),
            ("the step's first loss agrees with the reference over the "
             "global batch of %d sequences" % n_seq, err_step <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, ref_loss, err_step, TOL_LOSS)),
        ]

    rows = top_k * length  # rows of a chip's grouped matmuls
    # The flash kernels a layer's call runs, as the program's own plan
    # names them for the shapes (the backward is one kernel or two).
    kernels = [k for b in (False, True) for k in profile.flash_plan(
        1, heads, length, head_dim, heads // kv_heads, cfg.dtype, b)]
    counts = {
        "model_flops_per_item": flops_moe.olmoe_model_flops_per_token(
            hidden, width, experts, top_k, vocab, layers, heads, head_dim,
            length),
        # per step and per device, by the kernels `flash_plan` names
        "flash_kernels": kernels,
        "flash_executed_flops": layers * flops.flash_executed_flops(
            kernels, 1, heads, length, head_dim),
        "flash_min_bytes": layers * flops.flash_min_bytes(
            kernels, 1, heads, kv_heads, length, head_dim),
        "moe_gmm_executed_flops": layers * flops_moe.gated_experts_flops(
            rows, hidden, width),
        "moe_gmm_min_bytes": layers * flops_moe.gated_experts_min_bytes(
            rows, hidden, width, experts,
            itemsize=jnp.dtype(job["compute_dtype"]).itemsize,
            matrix_itemsize=jnp.dtype(job["param_dtype"]).itemsize),
        "params": flops_moe.olmoe_params(hidden, width, experts, vocab,
                                         layers),
        "active_params": flops_moe.olmoe_params(hidden, width, top_k, vocab,
                                                layers, router=experts),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify}
