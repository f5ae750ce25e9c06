"""Trace a few train steps of a small LM with `hvd.profile`, in this process,
and print where the device time went by the program's own names
(docs/TRACING.md, "The in-`jit` step").

Usage: python examples/profile_step.py [--steps 5] [--logdir DIR]

The step's phases carry the program's scopes (`hvd_fwd_bwd`,
`hvd_grad_sync`, `hvd_optimizer`; `hvd_embed`, `hvd_block`, `hvd_loss`, ...
inside the first) and its Pallas kernels their names (`hvd_flash_fwd`,
...). Open `--logdir` with TensorBoard's profile plugin or xprof; the table
printed here is `benchmark/scope_reduce.py`'s reduction of the `.xplane.pb`
(a trace of a TPU run: the CPU backend's trace has no device plane to
reduce, and only the trace's path is printed).
"""

import argparse
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import models, parallel  # noqa: E402
from horovod_tpu.ops.losses import chunked_softmax_cross_entropy  # noqa: E402


def print_table(path):
    """Milliseconds a step by phase, model part, direction and kernel."""
    from benchmark import scope_reduce, trace_reduce

    trace = trace_reduce.load(path)
    if not trace.devices:
        print("no TPU plane in the trace: nothing to reduce on this backend")
        return
    out = scope_reduce.reduce_file(path, trace,
                                   trace.modules[min(trace.modules)])
    for title, rows in (("phase", out["phases"]), ("kernel", out["kernels"]),
                        ("scope", out["parts"])):
        print("%-60s ms a step" % title)
        for name, ms in sorted(rows.items(), key=lambda kv: -kv[1]):
            print("  %-58s %9.3f" % (name, ms))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--logdir", default=None)
    args = ap.parse_args()
    logdir = args.logdir or tempfile.mkdtemp(prefix="hvd_profile_")

    hvd.init()
    mesh = parallel.data_parallel_mesh()
    cfg = models.TransformerConfig(
        vocab_size=8192, num_layers=2, num_heads=4, embed_dim=512,
        mlp_dim=2048, max_seq_len=args.seq_len, attention="flash",
        dtype=jnp.bfloat16)
    model = models.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (2 * mesh.size, args.seq_len), 0, 8192)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    opt = optax.adam(1e-4)

    def loss_fn(params, batch):
        hid = model.apply({"params": params}, batch["x"],
                          return_hidden=True)
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"],
            jnp.roll(batch["x"], -1, axis=1), chunk=128)

    step = parallel.make_train_step(loss_fn, opt, mesh)
    params, opt_state, batch = step.place(params, opt.init(params),
                                          {"x": tokens})
    for _ in range(2):  # compile and warm up outside the trace
        params, opt_state, loss = step(params, opt_state, batch)
    loss.block_until_ready()

    hvd.profile.start(logdir)
    for _ in range(args.steps):
        with hvd.profile.span("dispatch"):
            params, opt_state, loss = step(params, opt_state, batch)
        with hvd.profile.span("wait_loss"):
            loss.block_until_ready()
    path = hvd.profile.stop()
    print("loss %.4f after %d traced steps on %d x %s"
          % (float(loss), args.steps, mesh.size, jax.devices()[0].platform))
    print("trace: %s" % path)
    print_table(path)
    hvd.shutdown()


if __name__ == "__main__":
    main()
