"""Tensor-parallel transformer LM on the 2-D (batch x model) mesh
(docs/GROUPS.md) — the acceptance model for process groups.

Megatron-style sharding over the MODEL group of
``hvd.init(model_parallel=k)``: attention heads and the MLP hidden dim
split across the k model ranks (column-parallel QKV / mlp_in,
row-parallel out-proj / mlp_out), with the host-plane f/g operators
(``parallel.tensor_parallel.copy_to_model_parallel`` /
``reduce_from_model_parallel``) completing activations forward and
gradients backward over the model group's ring. Gradients average over
the BATCH group only — the ranks holding the same shard.

The point of the exercise: at the configured width this model CANNOT
run pure data-parallel — the full parameter set exceeds the per-rank
budget (HVD_TPU_TP_BUDGET_BYTES models the chip's HBM headroom), and
the example refuses to start unless model_parallel shards it under
budget. ``--reference`` lifts the budget to produce the single-process
reference loss trajectory the distributed run must match (the "big
host" stand-in for a run that would not fit the real chip).

Run::

    horovodrun_tpu -np 4 python examples/jax_tp_lm.py --model-parallel 2
    python examples/jax_tp_lm.py --reference          # 1-process reference
"""

import argparse
import json
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import horovod_tpu.jax as hvd  # noqa: E402
from horovod_tpu.parallel.tensor_parallel import (  # noqa: E402
    copy_to_model_parallel,
    reduce_from_model_parallel,
)


def build_params(rng, vocab, d_model, n_heads, d_head, d_mlp, n_layers):
    """FULL (unsharded) parameter tree, deterministic from `rng`.

    Every rank builds the same full tree and slices its own model shard
    — initial cross-rank agreement by construction, re-asserted by the
    initial broadcast below.
    """
    def normal(key, shape, scale):
        return (scale * jax.random.normal(key, shape)).astype(jnp.float32)

    keys = jax.random.split(rng, 2 + 6 * n_layers)
    params = {
        "embed": normal(keys[0], (vocab, d_model), 0.02),
        "lm_head": normal(keys[1], (d_model, vocab), 0.02),
        "layers": [],
    }
    for i in range(n_layers):
        k = keys[2 + 6 * i:8 + 6 * i]
        params["layers"].append({
            "qkv": normal(k[0], (d_model, 3, n_heads, d_head), 0.02),
            "out": normal(k[1], (n_heads, d_head, d_model), 0.02),
            "mlp_in": normal(k[2], (d_model, d_mlp), 0.02),
            "mlp_out": normal(k[3], (d_mlp, d_model), 0.02),
            "ln1": jnp.ones(d_model),
            "ln2": jnp.ones(d_model),
        })
    return params


def shard_params(params, tp_rank, tp_size):
    """This model rank's shard: heads dim of qkv/out and the MLP hidden
    dim split into tp_size contiguous blocks (block tp_rank kept);
    everything else replicated."""
    def blk(x, dim):
        n = x.shape[dim] // tp_size
        return jax.lax.slice_in_dim(x, tp_rank * n, (tp_rank + 1) * n,
                                    axis=dim)

    out = {"embed": params["embed"], "lm_head": params["lm_head"],
           "layers": []}
    for lyr in params["layers"]:
        out["layers"].append({
            "qkv": blk(lyr["qkv"], 2),      # heads
            "out": blk(lyr["out"], 0),      # heads
            "mlp_in": blk(lyr["mlp_in"], 1),
            "mlp_out": blk(lyr["mlp_out"], 0),
            "ln1": lyr["ln1"],
            "ln2": lyr["ln2"],
        })
    return out


def _ln(x, g):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return g * (x - mu) / jnp.sqrt(var + 1e-5)


def forward(params, tokens, model_group, layer_tag):
    """Loss of the sharded model. `model_group` None = unsharded
    reference (the f/g ops degrade to identity/sum-of-one)."""
    x = params["embed"][tokens]  # [B, T, D]
    T = tokens.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i, lyr in enumerate(params["layers"]):
        h = _ln(x, lyr["ln1"])
        if model_group is not None:
            # f: identity fwd, model-group allreduce bwd — completes the
            # gradient of the replicated input of the column-parallel
            # projections.
            h = copy_to_model_parallel(h, model_group,
                                       name="%s.f.attn.%d" % (layer_tag, i))
        q, k, v = jnp.einsum("btd,dchy->cbthy", h, lyr["qkv"])
        scores = jnp.einsum("bthy,bshy->bhts", q, k) / np.sqrt(q.shape[-1])
        scores = jnp.where(causal[None, None], scores, -1e9)
        att = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhts,bshy->bthy", att, v)
        partial = jnp.einsum("bthy,hyd->btd", ctx, lyr["out"])
        if model_group is not None:
            # g: model-group allreduce fwd (sums the head shards'
            # partial projections), identity bwd.
            partial = reduce_from_model_parallel(
                partial, model_group, name="%s.g.attn.%d" % (layer_tag, i))
        x = x + partial
        h = _ln(x, lyr["ln2"])
        if model_group is not None:
            h = copy_to_model_parallel(h, model_group,
                                       name="%s.f.mlp.%d" % (layer_tag, i))
        inner = jax.nn.gelu(h @ lyr["mlp_in"])
        partial = inner @ lyr["mlp_out"]
        if model_group is not None:
            partial = reduce_from_model_parallel(
                partial, model_group, name="%s.g.mlp.%d" % (layer_tag, i))
        x = x + partial
    logits = x @ params["lm_head"]
    logp = jax.nn.log_softmax(logits[:, :-1])
    tgt = tokens[:, 1:]
    return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()


def param_bytes(params):
    return sum(np.asarray(p).nbytes
               for p in jax.tree_util.tree_leaves(params))


def assert_fits(params, budget, model_parallel):
    """The acceptance gate: this width does not fit a rank unsharded."""
    have = param_bytes(params)
    if have > budget:
        raise SystemExit(
            "model shard (%d B) exceeds the per-rank parameter budget "
            "(%d B, HVD_TPU_TP_BUDGET_BYTES): model_parallel=%d is too "
            "narrow for this width — raise it (pure data-parallel CANNOT "
            "run this model)" % (have, budget, model_parallel))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="mesh model width k (0: HVD_TPU_MODEL_PARALLEL)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--d-head", type=int, default=8)
    ap.add_argument("--d-mlp", type=int, default=128)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--batch-per-row", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--reference", action="store_true",
                    help="single-process unsharded reference run (lifts "
                         "the parameter budget; the 'big host' stand-in)")
    ap.add_argument("--loss-out", default="",
                    help="write the per-step loss trajectory as JSON")
    args = ap.parse_args()

    if args.reference:
        rank, batch_rows = 0, 1
        model_group, tp_rank, tp_size = None, 0, 1
    else:
        hvd.init(model_parallel=args.model_parallel or None)
        import horovod_tpu as hvd_core
        rank = hvd.rank()
        k = hvd_core.model_parallel_size()
        if k < 2:
            raise SystemExit(
                "this model is the process-group acceptance case and "
                "cannot run pure-DP: start with hvd.init(model_parallel"
                ">=2) (e.g. --model-parallel 2 at 4 ranks)")
        model_group = hvd_core.model_group()
        batch_group = hvd_core.batch_group()
        tp_rank, tp_size = model_group.rank(), k
        batch_rows = hvd.size() // k

    full = build_params(jax.random.PRNGKey(7), args.vocab, args.d_model,
                        args.n_heads, args.d_head, args.d_mlp, args.layers)
    if args.reference:
        params = full
    else:
        # The budget models the chip: the FULL tree must not fit, the
        # 1/k shard must. Default: just under the full parameter bytes.
        budget = int(os.environ.get("HVD_TPU_TP_BUDGET_BYTES",
                                    str(int(param_bytes(full) * 0.75))))
        params = shard_params(full, tp_rank, tp_size)
        assert_fits(params, budget, tp_size)
        # Initial agreement: replicated leaves broadcast from rank 0
        # world-wide; sharded leaves are deterministic slices of the
        # same seeded full tree, re-broadcast within each batch group
        # (same shard) from its first member.
        params = {
            "embed": hvd.broadcast_parameters(params["embed"],
                                              name_prefix="tp.embed"),
            "lm_head": hvd.broadcast_parameters(params["lm_head"],
                                                name_prefix="tp.lm_head"),
            "layers": [
                {k2: hvd.broadcast(v, root_rank=batch_group.ranks[0],
                                   group=batch_group,
                                   name="tp.l%d.%s" % (i, k2))
                 for k2, v in lyr.items()}
                for i, lyr in enumerate(params["layers"])],
        }

    # Synthetic LM stream, deterministic per batch row: model peers in
    # one row MUST consume identical tokens.
    row = 0 if args.reference else rank // tp_size
    loss_grad = jax.value_and_grad(
        lambda p, t: forward(p, t, model_group, "tp"))

    losses = []
    for step in range(args.steps):
        if args.reference:
            toks = np.concatenate([
                np.random.RandomState(1000 + 17 * step + r).randint(
                    0, args.vocab,
                    (args.batch_per_row, args.seq_len))
                for r in range(int(os.environ.get(
                    "HVD_TPU_TP_REF_ROWS", "2")))])
        else:
            toks = np.random.RandomState(1000 + 17 * step + row).randint(
                0, args.vocab, (args.batch_per_row, args.seq_len))
        loss, grads = loss_grad(params, jnp.asarray(toks))
        if not args.reference:
            # Batch-axis sync only: replicated leaves are identical
            # across the model group already (f/g complete them), and
            # sharded leaves are exact per shard.
            grads = hvd.allreduce_gradients(grads, average=True,
                                            name_prefix="tp.grad",
                                            group=batch_group)
            # The loss is row-local; its batch-group mean matches the
            # reference's full-batch loss.
            loss = hvd.allreduce(jnp.asarray(loss), average=True,
                                 group=batch_group, name="tp.loss")
        params = jax.tree_util.tree_map(
            lambda p, g: p - args.lr * g, params, grads)
        losses.append(float(loss))
        if rank == 0:
            print("step %d loss %.6f" % (step, losses[-1]), flush=True)

    if args.loss_out and rank == 0:
        with open(args.loss_out, "w") as f:
            json.dump({"losses": losses,
                       "mode": "reference" if args.reference else
                       "mesh(k=%d)" % tp_size}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
