"""Zigzag sequence-parallel language model — the causal load-balanced
ring, as a user writes it.

Contiguous causal ring attention leaves the last rank doing all the
lower-triangle work while early ranks idle; `schedule="zigzag"` splits
the sequence into 2n chunks and gives rank r chunks (r, 2n-1-r), so
every rank does equal work at every ring step. The recipe is three moves:

1. zigzag_shard the per-sequence arrays (tokens, positions, shifted
   labels) BEFORE feeding shard_map — the model's rotary embedding
   reads explicit global positions, so the permuted layout stays exact;
2. `TransformerConfig(attention="ring", sp_axis=..,
   sp_schedule="zigzag")`;
3. zigzag_unshard anything you read back in sequence order (here the
   loss is a mean over tokens — order-free — so nothing needs it).

Runs on whatever devices exist; for a CPU demo set
XLA_FLAGS=--xla_force_host_platform_device_count=8
HVD_TPU_PALLAS_INTERPRET=1 (the zigzag path runs the Pallas ring
kernels; interpret mode covers them off-TPU).

Run: python examples/jax_zigzag_lm.py --steps 4
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="global sequence length; per-rank shards must "
                         "be 256-multiples (two 128-aligned chunks)")
    ap.add_argument("--sp", type=int, default=4, help="ring size")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import zigzag_shard

    n = args.sp
    L = args.seq_len
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise SystemExit(f"need {n} devices, have {len(devices)}")
    mesh = Mesh(np.array(devices), ("sp",))

    cfg = TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=4, embed_dim=128,
        mlp_dim=256, max_seq_len=L, dtype=jnp.float32,
        attention="ring", sp_axis="sp", sp_schedule="zigzag")
    model = Transformer(cfg)

    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (args.batch, L), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 tokens.shape)
    # Shift in NATURAL order first, then re-layout: next-token labels
    # are neighbors in sequence order, not zigzag order.
    labels = jnp.roll(tokens, -1, axis=1)
    tz, pz, lz = (zigzag_shard(x, n) for x in (tokens, positions, labels))

    # Init via a dense-attention twin (identical param structure): a
    # ring model can't trace outside shard_map (unbound axis name).
    import dataclasses
    dense_twin = Transformer(dataclasses.replace(
        cfg, attention="dense", sp_axis=None, sp_schedule="contiguous"))
    params = dense_twin.init(jax.random.PRNGKey(1),
                             tokens[:, :16])["params"]
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def local_loss(params, t, p, y):
        logits = model.apply({"params": params}, t, p)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ll = jnp.take_along_axis(logp, y[..., None], axis=-1)
        # This rank's CONTRIBUTION to the global token mean: local sum
        # over the GLOBAL token count (y is the local shard, B x L/n,
        # so the global count is B * L). No psum inside the
        # differentiated function: under check_vma=False a psum
        # transposes to another psum and scales every cotangent by n.
        # The explicit grads psum in `step` sums contributions instead.
        return -jnp.sum(ll) / (y.shape[0] * L)

    def step(params, opt_state, t, p, y):
        loss, grads = jax.value_and_grad(local_loss)(params, t, p, y)
        # The gradient allreduce (and the loss report), safely OUTSIDE
        # the differentiated closure: summed contributions = the exact
        # global-mean gradient, identical on every rank.
        grads = jax.lax.psum(grads, "sp")
        loss = jax.lax.psum(loss, "sp")
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=(P(), P(), P()), check_vma=False))

    for i in range(args.steps):
        params, opt_state, loss = f(params, opt_state, tz, pz, lz)
        print(f"step {i}: loss {float(loss):.4f}")
    print("done: zigzag ring LM trained",
          f"(sp={n}, L={L}, {L // (2 * n)}-token chunks)")


if __name__ == "__main__":
    main()
