"""TF2 synthetic benchmark over the TensorFlow binding — the reference's
flagship example config (`examples/tensorflow2_synthetic_benchmark.py`,
BASELINE.json config #2) rebuilt for horovod_tpu: Keras ResNet-50 on
synthetic ImageNet-shaped data, DistributedGradientTape with the
compiled custom-op collectives, warmup + timed batches, `Img/sec per
rank` with the mean +/- 1.96 sigma summary the reference prints.

Note: this exercises the TF-on-host-CPU compatibility surface (the TF
binding's role here); for TPU-resident XLA training use the jax
binding (`examples/jax_synthetic_benchmark.py`).

Run: python -m horovod_tpu.run.run -np 2 -- \
         python examples/tensorflow2_synthetic_benchmark.py
"""

import argparse
import os
import timeit

os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import numpy as np
import tensorflow as tf

import horovod_tpu.tensorflow as hvd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="ResNet50",
                    help="any keras.applications model name")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--fp16-allreduce", action="store_true")
    args = ap.parse_args()

    hvd.init()
    import keras

    keras.utils.set_random_seed(42)
    model = getattr(keras.applications, args.model)(
        weights=None, input_shape=(args.image_size, args.image_size, 3),
        classes=args.num_classes)
    opt = keras.optimizers.SGD(0.01)
    loss_fn = keras.losses.SparseCategoricalCrossentropy(from_logits=False)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)

    rng = np.random.RandomState(hvd.rank())
    data = tf.constant(rng.randn(args.batch_size, args.image_size,
                                 args.image_size, 3).astype(np.float32))
    target = tf.constant(rng.randint(0, args.num_classes,
                                     args.batch_size).astype(np.int64))

    @tf.function
    def benchmark_step():
        with hvd.DistributedGradientTape(
                compression=compression) as tape:
            probs = model(data, training=True)
            loss = loss_fn(target, probs)
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))

    # Consistent start across ranks (the reference broadcasts after the
    # first step so optimizer slots exist).
    benchmark_step()
    hvd.broadcast_variables(model.variables, root_rank=0)
    hvd.broadcast_variables(opt.variables, root_rank=0)

    if hvd.rank() == 0:
        print("Model: %s, batch size %d, %d ranks"
              % (args.model, args.batch_size, hvd.size()), flush=True)

    timeit.timeit(benchmark_step, number=args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t = timeit.timeit(benchmark_step,
                          number=args.num_batches_per_iter)
        img_sec = args.batch_size * args.num_batches_per_iter / t
        if hvd.rank() == 0:
            print("Iter #%d: %.1f img/sec per rank" % (i, img_sec),
                  flush=True)
        img_secs.append(img_sec)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print("Img/sec per rank: %.1f +- %.1f"
              % (img_sec_mean, img_sec_conf), flush=True)
        print("Total img/sec on %d rank(s): %.1f +- %.1f"
              % (hvd.size(), hvd.size() * img_sec_mean,
                 hvd.size() * img_sec_conf), flush=True)
        print("done", flush=True)


if __name__ == "__main__":
    main()
