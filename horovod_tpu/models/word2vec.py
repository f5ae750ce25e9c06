"""Skip-gram word2vec with negative sampling.

Capability parity: reference `examples/tensorflow_word2vec.py` (the
BASELINE.json config that "exercises allgather + broadcast") — its
embedding gradients are IndexedSlices, which the reference allreduces via
the sparse allgather path (`horovod/tensorflow/__init__.py:65-76`).

TPU-first: embedding lookups are one-hot-free `jnp.take` gathers (static
shapes), NCE loss against `num_sampled` shared negative samples per batch.
Sparse gradients surface as rows of the dense embedding table; the jax
binding's `allreduce_sparse` gathers (indices, values) across ranks instead
of densifying — see `horovod_tpu/jax/sparse.py`.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


class SkipGram(nn.Module):
    """Skip-gram embedding + NCE output layer."""
    vocab_size: int = 50000
    embedding_dim: int = 200
    dtype: Any = jnp.float32

    def setup(self):
        self.embedding = nn.Embed(self.vocab_size, self.embedding_dim,
                                  param_dtype=jnp.float32,
                                  embedding_init=nn.initializers.uniform(2.0))
        self.nce_weight = self.param(
            "nce_weight",
            nn.initializers.truncated_normal(1.0 / self.embedding_dim ** 0.5),
            (self.vocab_size, self.embedding_dim), jnp.float32)
        self.nce_bias = self.param("nce_bias", nn.initializers.zeros,
                                   (self.vocab_size,), jnp.float32)

    def __call__(self, center_ids):
        """Embeds a batch of center-word ids -> [batch, embedding_dim]."""
        return self.embedding(center_ids)

    def nce_loss(self, center_ids, context_ids, negative_ids):
        """Sampled-softmax/NCE loss.

        center_ids [B], context_ids [B] (positives), negative_ids [K]
        (shared negatives) — all int32, static shapes.
        """
        emb = self.embedding(center_ids)                        # [B, D]
        pos_w = jnp.take(self.nce_weight, context_ids, axis=0)  # [B, D]
        pos_b = jnp.take(self.nce_bias, context_ids, axis=0)    # [B]
        neg_w = jnp.take(self.nce_weight, negative_ids, axis=0)  # [K, D]
        neg_b = jnp.take(self.nce_bias, negative_ids, axis=0)    # [K]

        pos_logit = jnp.sum(emb * pos_w, axis=-1) + pos_b        # [B]
        neg_logit = emb @ neg_w.T + neg_b[None, :]               # [B, K]

        pos_loss = -jax.nn.log_sigmoid(pos_logit)
        neg_loss = -jnp.sum(jax.nn.log_sigmoid(-neg_logit), axis=-1)
        return jnp.mean(pos_loss + neg_loss)

    def nearest(self, word_ids, k=8):
        """Cosine-nearest neighbours for eval (reference word2vec eval loop)."""
        norm = self.embedding.embedding / (jnp.linalg.norm(
            self.embedding.embedding, axis=1, keepdims=True) + 1e-8)
        q = jnp.take(norm, word_ids, axis=0)
        sim = q @ norm.T
        return jax.lax.top_k(sim, k + 1)[1][:, 1:]


def w2v_make_step(mesh, n, sparse, lr=0.5, num_iters=100, donate=True):
    """Skip-gram NCE multi-step train fn over a dp mesh, sparse or
    dense gradient plane. The IndexedSlices rationale (reference
    horovod/tensorflow/__init__.py:65-76) as a measurable A/B:

    * sparse: grads w.r.t. the GATHERED rows only (O(B*D)), shipped
      through the PRODUCT sparse plane — `horovod_tpu.jax.sparse.
      allreduce_sparse` (allgather (indices, values) over the axis,
      average) + `apply_sparse` (scatter-add; duplicates accumulate,
      exactly IndexedSlices application).
    * dense: differentiate through the gathers (XLA materializes the
      full [V, D] scatter-add gradient), psum it, dense SGD update —
      O(V*D) per step, the `sparse_as_dense` escape hatch.

    `tests/test_jax_api.py` pins the two paths against each other on a
    CPU mesh."""
    from horovod_tpu.jax.sparse import allreduce_sparse, apply_sparse

    def nce(er, pw, pb, nw, nb):
        pos = jnp.sum(er * pw, axis=-1) + pb
        negl = er @ nw.T + nb[None, :]
        return jnp.mean(-jax.nn.log_sigmoid(pos) -
                        jnp.sum(jax.nn.log_sigmoid(-negl), axis=-1))

    def run(emb, nce_w, nce_b, center, context, neg):
        def one(tables, _):
            emb, nce_w, nce_b = tables
            if sparse:
                er = jnp.take(emb, center, axis=0)
                pw = jnp.take(nce_w, context, axis=0)
                pb = jnp.take(nce_b, context, axis=0)
                nw = jnp.take(nce_w, neg, axis=0)
                nb = jnp.take(nce_b, neg, axis=0)
                loss, g = jax.value_and_grad(
                    nce, argnums=(0, 1, 2, 3, 4))(er, pw, pb, nw, nb)

                def sparse_apply(table, ix, vals):
                    ai, av = allreduce_sparse(ix, vals, average=True,
                                              axis_name="dp")
                    return apply_sparse(table, ai, av, scale=-lr)

                emb = sparse_apply(emb, center, g[0])
                nce_w = sparse_apply(nce_w, context, g[1])
                nce_b = sparse_apply(nce_b, context, g[2])
                nce_w = sparse_apply(nce_w, neg, g[3])
                nce_b = sparse_apply(nce_b, neg, g[4])
            else:
                def full_loss(emb, nce_w, nce_b):
                    return nce(jnp.take(emb, center, axis=0),
                               jnp.take(nce_w, context, axis=0),
                               jnp.take(nce_b, context, axis=0),
                               jnp.take(nce_w, neg, axis=0),
                               jnp.take(nce_b, neg, axis=0))
                loss, g = jax.value_and_grad(
                    full_loss, argnums=(0, 1, 2))(emb, nce_w, nce_b)
                emb = emb - lr * (lax.psum(g[0], "dp") / n)
                nce_w = nce_w - lr * (lax.psum(g[1], "dp") / n)
                nce_b = nce_b - lr * (lax.psum(g[2], "dp") / n)
            return (emb, nce_w, nce_b), lax.pmean(loss, "dp")

        tables, losses = lax.scan(one, (emb, nce_w, nce_b), None,
                                  length=num_iters)
        return tables + (losses[-1],)

    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(), P("dp"), P("dp"), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False)
    # donate=False exists for the CPU-mesh equivalence test, which feeds
    # the same tables to both variants; donation keeps the table update
    # in place.
    return jax.jit(sharded,
                   donate_argnums=(0, 1, 2) if donate else ())
