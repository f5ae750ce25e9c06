"""ResNet synthetic benchmark — reference analogue:
`examples/tensorflow2_synthetic_benchmark.py:110-131` (same measurement
protocol: warmup, N rounds x M iters, `Img/sec per device` mean ± 1.96σ).

Run single chip:   python examples/jax_synthetic_benchmark.py
All local devices train over a 1-D data-parallel mesh automatically.
An example of the protocol, not the repository's yardstick: numbers that
count come from `benchmark/run.py` (cell `resnet50_b256`).
"""

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet18", "resnet34", "resnet50",
                             "resnet101", "resnet152"])
    ap.add_argument("--batch-size", type=int, default=64,
                    help="per-device batch size")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--fp32", action="store_true",
                    help="disable bf16 compute")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    devices = jax.devices()
    n = len(devices)
    model_cls = getattr(models, args.model.replace("resnet", "ResNet"))
    model = model_cls(num_classes=1000,
                      dtype=jnp.float32 if args.fp32 else jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    s = args.image_size
    variables = model.init(rng, jnp.zeros((1, s, s, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, batch["x"],
            train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, batch["y"])

    mesh = data_parallel_mesh(devices=devices)
    step = make_train_step(loss_fn, optax.sgd(0.01, momentum=0.9), mesh)

    global_batch = args.batch_size * n
    x = jax.random.normal(rng, (global_batch, s, s, 3), jnp.float32)
    y = jax.random.randint(rng, (global_batch,), 0, 1000)
    params_p, opt_state, batch = step.place(params, optax.sgd(
        0.01, momentum=0.9).init(params), {"x": x, "y": y})

    print("Model: %s, batch size/device: %d, devices: %d (%s)" %
          (args.model, args.batch_size, n, devices[0].platform))

    # float(loss) is an end-of-chain barrier: each loss depends on every
    # prior step's params.
    for _ in range(args.num_warmup_batches):
        params_p, opt_state, loss = step(params_p, opt_state, batch)
    float(loss)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params_p, opt_state, loss = step(params_p, opt_state, batch)
        float(loss)
        dt = time.perf_counter() - t0
        rate = global_batch * args.num_batches_per_iter / dt / n
        img_secs.append(rate)
        print("Iter #%d: %.1f img/sec per device" % (i, rate))

    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    print("Img/sec per device: %.1f +-%.1f" % (mean, conf))
    print("Total img/sec on %d device(s): %.1f +-%.1f" %
          (n, n * mean, n * conf))


if __name__ == "__main__":
    main()
