"""Builder for configurations that run `models.ResNet50`: the image
classification train step through `parallel.make_train_step` as `bench.py`
and `chip_smoke.py` build it (bf16 compute, f32 parameters, SGD with
momentum, one synthetic ImageNet-shaped batch kept on the device, as
upstream Horovod's `tensorflow2_synthetic_benchmark.py` keeps it).
"""


# Agreement of the bf16 system with the float32 reference. Logits after
# training pass through 53 bf16 convolutions, each renormalised by a
# BatchNorm, so rounding does not grow with depth: 1.6e-3 and 2.0e-3 measured
# on the chip at batch 256 (my chip run, PR 22). The first loss is a mean
# over the batch of values near ln(1000): 8e-6 and 3e-5 measured. Both
# tolerances are about five times what was seen; an 8-bit path would miss
# the first by a factor of ten.
TOL_LOGITS = 1e-2   # max |system - reference| / max |reference|
TOL_LOSS = 2e-4     # relative


def build(config, traffic, mesh, seed, abstract=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import flops
    from benchmark.references import resnet as reference
    from horovod_tpu import models
    from horovod_tpu.parallel import make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    job = config["job"]
    size = config["image_size"]
    classes = config["num_classes"]
    model = getattr(models, config["model_class"])(
        num_classes=classes, dtype=jnp.dtype(job["compute_dtype"]))
    opt = optax.sgd(job["learning_rate"], momentum=job["momentum"])
    k_param, k_x, k_y = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = int(traffic["batch"])

    def make_state(kp, kx, ky):
        variables = model.init(kp, jnp.zeros((1, size, size, 3)), train=False)
        params = variables["params"]
        batch = {"x": jax.random.normal(kx, (n, size, size, 3), jnp.float32),
                 "y": jax.random.randint(ky, (n,), 0, classes)}
        return params, opt.init(params), batch, variables["batch_stats"]

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(mesh.axis_names[0]))
    make = jax.jit(make_state, out_shardings=(rep, rep, dat, rep))
    if abstract:
        # Shapes with their shardings and no arrays, for compiling the step
        # for a chip that is described and not attached; the closed-over
        # statistics become host constants of the same shapes.
        import numpy as np

        params, opt_state, batch, batch_stats = jax.eval_shape(
            make, k_param, k_x, k_y)
        batch_stats = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), batch_stats)
    else:
        params, opt_state, batch, batch_stats = make(k_param, k_x, k_y)

    def loss_fn(params, batch):
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, batch["x"],
            train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, batch["y"])

    step = make_train_step(loss_fn, opt, mesh)
    counts = {
        "model_flops_per_item": flops.resnet50_model_flops_per_image(
            size, classes),
        "params": flops.resnet50_params(size, classes),
    }
    stages = tuple(config["stage_sizes"])

    def verify(final_params, first_loss):
        """After the window, on one device: the step's first loss against
        the plain reference on the seed's parameters and the whole batch,
        and the system's forward (bf16) against the reference on the
        TRAINED parameters — at the seed's parameters the last BatchNorm
        scale of every block is zero and the blocks' branches would not be
        tested."""
        dev = mesh.devices.flat[0]
        with jax.default_device(dev):
            params0, _, data, stats = jax.jit(make_state)(k_param, k_x, k_y)
            trained = jax.tree_util.tree_map(
                lambda leaf: jax.device_put(
                    leaf.addressable_shards[0].data, dev), final_params)

            # Arrays go in as arguments: one closed over would be baked
            # into the program as a constant of its size.
            @jax.jit
            def system(p, stats, x):
                out, _ = model.apply({"params": p, "batch_stats": stats}, x,
                                     train=True, mutable=["batch_stats"])
                return out

            ref = jax.jit(lambda p, x, y: reference.loss(p, x, y, stages))
            sys_logits = system(trained, stats, data["x"])
            with jax.default_matmul_precision("highest"):
                ref_loss0, _ = ref(params0, data["x"], data["y"])
                _, ref_logits = ref(trained, data["x"], data["y"])
            err_logits = float(jnp.max(jnp.abs(sys_logits - ref_logits))
                               / jnp.max(jnp.abs(ref_logits)))
            ref_loss0 = float(ref_loss0)
        err_loss = abs(first_loss - ref_loss0) / abs(ref_loss0)
        return [
            ("the step's first loss agrees with the plain reference over the "
             "batch of %d images" % n, err_loss <= TOL_LOSS,
             "%.6f vs %.6f, rel %.3e, tol %.0e"
             % (first_loss, ref_loss0, err_loss, TOL_LOSS)),
            ("system forward agrees with the plain reference on the trained "
             "parameters (logits)", err_logits <= TOL_LOGITS,
             "max rel err %.3e, tol %.0e" % (err_logits, TOL_LOGITS)),
        ]

    return {"step": step, "state": (params, opt_state, batch),
            "items_per_step": n, "counts": counts, "verify": verify}
