"""Expert-parallel Switch-MoE language model on a (dp x ep) device mesh.

The ep member of the parallelism family end to end, as a user would
write it: expert weights sharded over the `ep` mesh axis
(`ep_param_specs`), tokens sharded over BOTH axes (each device routes
its own shard; the MoE all_to_all exchanges token slots for local
experts), gradients synchronized with `ep_grad_sync` (LOCAL loss +
explicit sync — see parallel/expert.py), and the Switch load-balancing
aux loss wired into the objective.

Runs on whatever devices exist: a TPU slice uses the real chips; for a
CPU demo set XLA_FLAGS=--xla_force_host_platform_device_count=8.

Run: python examples/jax_moe_lm.py --steps 10
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16,
                    help="global batch (sequences)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel degree (0 = half the devices)")
    ap.add_argument("--aux-weight", type=float, default=0.01)
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import (ep_grad_sync, ep_param_specs,
                                      hybrid_mesh, router_aux_losses)

    devices = jax.devices()
    n = len(devices)
    ep = args.ep or max(1, n // 2)
    dp = n // ep
    if dp * ep != n:
        raise SystemExit("need dp*ep == device count (%d)" % n)
    if args.experts % ep:
        raise SystemExit("--experts must be divisible by ep=%d" % ep)
    mesh = hybrid_mesh((dp, ep), ("dp", "ep"), devices=devices)
    print("mesh: dp=%d x ep=%d over %d devices" % (dp, ep, n))

    base = TransformerConfig(vocab_size=512, num_layers=4, num_heads=4,
                             embed_dim=128, mlp_dim=256,
                             moe_experts=args.experts, moe_every=2,
                             moe_capacity_factor=1.25,
                             dtype=jnp.float32)
    model = Transformer(dataclasses.replace(base, ep_axis="ep",
                                            ep_size=ep))

    rng = np.random.RandomState(0)
    tokens_all = rng.randint(
        0, 512, size=(args.steps, args.batch, args.seq_len))

    variables = Transformer(base).init(
        jax.random.PRNGKey(0), jnp.asarray(tokens_all[0][:1]))
    params = variables["params"]
    specs = ep_param_specs(params, "ep")
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    # Adam moments mirror the param tree: shard them identically.
    opt_specs = (optax.ScaleByAdamState(count=P(), mu=specs, nu=specs),
                 optax.EmptyState())

    def step(params, opt_state, tokens):
        def loss_fn(params):
            logits, state = model.apply({"params": params}, tokens,
                                        mutable=["intermediates"])
            tgt = jnp.roll(tokens, -1, axis=1)
            logp = jax.nn.log_softmax(logits)
            xent = -jnp.mean(
                jnp.take_along_axis(logp, tgt[..., None], axis=-1))
            aux, _ = router_aux_losses(state["intermediates"])
            return xent + args.aux_weight * aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = ep_grad_sync(grads, "ep", dp_axis="dp", average=True)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(
            jax.lax.pmean(loss, "ep"), "dp")

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, opt_specs, P(("dp", "ep"))),
        out_specs=(specs, opt_specs, P()),
        check_vma=False))

    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    opt_state = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        opt_state, opt_specs)

    first = last = None
    for i in range(args.steps):
        params, opt_state, loss = mapped(params, opt_state,
                                         jnp.asarray(tokens_all[i]))
        last = float(loss)
        first = first if first is not None else last
        print("step %d loss %.4f" % (i, last))
    assert np.isfinite(last)
    assert last < first, (first, last)
    print("done")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
