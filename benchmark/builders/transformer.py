"""Builder for configurations that run `models.Transformer`: a causal LM
train step through `parallel.make_train_step`, as `bench.py --fused-xent`
builds it (flash attention, chunked vocabulary loss, optax).

`build(config, traffic, mesh, seed)` returns the step, its state (made on
the device in one jitted call from the seed), the items a step finishes,
the analytic counts the per-layer metrics need, and `verify`, which holds
the system to the plain reference (`references/transformer.py`) outside
the window.
"""

# Agreement of the bf16 system with the float32 reference on seeded random
# weights: the residual stream is rounded to bf16 after every block, so the
# final hidden states differ by a few bf16 roundings (2^-8 each) — PR 21 saw
# 4-6e-3 on single kernels; through 8 layers 1.04e-2 and 1.07e-2 measured (my
# chip run, PR 22), and the losses (means over 2048 tokens and more) agreed
# to 3e-7..3e-5. The tolerances are two and seven times what was seen; an
# fp8 or int8 path would miss the first by a factor of eight or more.
TOL_HIDDEN = 2e-2   # max |system - reference| / max |reference|
TOL_LOSS = 2e-4     # relative, on a loss near ln(vocab)


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops
    from benchmark.references import transformer as reference
    from horovod_tpu import models, profile
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
    from horovod_tpu.parallel import make_train_step

    job = config["job"]
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    mlp = config["intermediate_size"]
    vocab = config["vocab_size"]
    head_dim = hidden // heads
    length = int(traffic["seq_len"])
    per_chip = int(traffic["batch"]) // mesh.size
    if length > config["max_position_embeddings"]:
        raise ValueError("seq_len %d is beyond the configuration's %d "
                         "positions" % (length,
                                        config["max_position_embeddings"]))
    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        embed_dim=hidden, mlp_dim=mlp,
        max_seq_len=config["max_position_embeddings"],
        attention=job["attention"], rope_base=config["rotary_emb_base"],
        dtype=jnp.dtype(job["compute_dtype"]))
    model = models.Transformer(cfg)
    chunk = min(int(job["loss_chunk"]), length)
    opt = getattr(optax, job["optimizer"])(job["learning_rate"])

    def loss_fn(params, batch):
        hid = model.apply({"params": params}, batch["x"], batch["pos"],
                          return_hidden=True)
        tgt = jnp.roll(batch["x"], -1, axis=1)
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"], tgt, chunk=chunk)

    step = make_train_step(loss_fn, opt, mesh)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))
    n_seq = int(traffic["batch"])

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
    # `abstract`: shapes with their shardings and no arrays, for compiling
    # the step for a chip that is described and not attached.
    state = (jax.eval_shape if abstract else lambda f, *a: f(*a))(
        make, k_param, k_tok)

    def verify(final_params, first_loss):
        """After the window, on one device, from the seed again: the
        system's forward (flash, chunked loss, bf16) against the plain
        reference on one sequence, and the train step's first loss against
        the reference's loss over the whole global batch."""
        del final_params
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            params = jax.jit(init_params)(k_param)
            tokens = jax.jit(make_tokens)(k_tok)

            @jax.jit
            def system(params, seq):
                hid = model.apply({"params": params}, seq[None],
                                  return_hidden=True)
                loss = chunked_softmax_cross_entropy(
                    hid, params["lm_head"]["kernel"],
                    jnp.roll(seq, -1)[None], chunk=chunk)
                return hid[0].astype(jnp.float32), loss

            ref = jax.jit(lambda p, seq: reference.hidden_and_loss(
                p, seq, layers, config["rotary_emb_base"]))
            sys_hid, sys_loss = system(params, tokens[0])
            with jax.default_matmul_precision("highest"):
                ref_out = [ref(params, tokens[i]) for i in range(n_seq)]
            ref_hid = ref_out[0][0]
            err_hid = float(jnp.max(jnp.abs(sys_hid - ref_hid))
                            / jnp.max(jnp.abs(ref_hid)))
            ref_loss0 = float(ref_out[0][1])
            ref_loss = sum(float(o[1]) for o in ref_out) / n_seq
        err_seq = abs(float(sys_loss) - ref_loss0) / abs(ref_loss0)
        err_step = abs(first_loss - ref_loss) / abs(ref_loss)
        return [
            ("system forward agrees with the plain reference (hidden states, "
             "one sequence)", err_hid <= TOL_HIDDEN,
             "max rel err %.3e, tol %.0e" % (err_hid, TOL_HIDDEN)),
            ("system loss agrees with the plain reference (one sequence)",
             err_seq <= TOL_LOSS, "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (float(sys_loss), ref_loss0, err_seq, TOL_LOSS)),
            ("the step's first loss agrees with the reference over the "
             "global batch of %d sequences" % n_seq, err_step <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, ref_loss, err_step, TOL_LOSS)),
        ]

    # The flash kernels a layer's call runs, as the program's own plan
    # names them for the shapes (the backward is one kernel or two).
    kernels = [k for b in (False, True) for k in profile.flash_plan(
        per_chip, heads, length, head_dim, 1, cfg.dtype, b)]
    counts = {
        "model_flops_per_item": flops.transformer_model_flops_per_token(
            hidden, mlp, vocab, layers, heads, head_dim, length),
        # per step and per device, by the kernels `flash_plan` names
        "flash_kernels": kernels,
        "flash_executed_flops": layers * flops.flash_executed_flops(
            kernels, per_chip, heads, length, head_dim),
        "flash_min_bytes": layers * flops.flash_min_bytes(
            kernels, per_chip, heads, heads, length, head_dim),
        "params": flops.transformer_params(hidden, mlp, vocab, layers),
    }
    return {"step": step, "state": state,
            "items_per_step": n_seq * length, "counts": counts,
            "verify": verify}
