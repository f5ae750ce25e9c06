"""Block-diffusion training of a decoder (BD3-LM, Arriola et al.,
arXiv:2503.09573; the recipe SDAR's models are trained by): what a user's
`loss_fn` calls around `models.Transformer`.

A sequence x0 of L tokens lies in blocks of `block`. Each block draws a
noise level t ~ U(t_min, 1) and each of its tokens becomes `mask_id` with
probability t: x_t. The stack runs ONCE on [x_t ; x0], 2L positions with
the positions 0..L-1 given twice, under `ops.BlockDiffusionMask(L, block)`
(`TransformerConfig.attention_mask`): a noisy block sees itself and the
clean blocks before it, the clean copy is block-causal. Head and loss run
over the noisy half only; the output at a masked position predicts THAT
position's own token (no shift) and a masked row of a block weighs 1 / t:

    loss = 1/(B L) sum_blocks 1/t_block sum_{masked l in block}
           -log p(x0_l | position l of the noisy half)

which is `ops.losses.chunked_softmax_cross_entropy(weights=)` on this
module's `weights`:

    batch = models.block_diffusion_batch(key, tokens, block, mask_id)
    hidden = model.apply(params, batch["ids"], batch["positions"],
                         return_hidden=True)
    loss = chunked_softmax_cross_entropy(
        models.block_diffusion_noisy_half(hidden), head, batch["targets"],
        weights=batch["weights"])

The noise is a pure function of `key`, so the key is part of a step's batch
like its tokens; nothing here is a state of the train step. The work lies
under the profiler's scope `hvd_bd` (`profile.BD`).
"""

import jax
import jax.numpy as jnp

from horovod_tpu import profile


def block_diffusion_batch(key, tokens, block, mask_id, t_min=1e-3):
    """The doubled batch of block-diffusion training from `tokens` [B, L]
    (int) and `key` (one PRNG key, or [B, 2]: a key a sequence, which is
    how a key shards with the batch it belongs to). Returns a dict:

    - ``ids`` [B, 2L]: [x_t ; x0], the noisy copy first;
    - ``positions`` [B, 2L]: 0..L-1 twice (rotary positions);
    - ``targets`` [B, L]: x0;
    - ``weights`` [B, L] f32: 1 / (t B L) on a masked row, 0 elsewhere;
    - ``t`` [B, L // block] f32: every block's noise level.

    A sequence's key is split in two: the blocks' levels, uniform in
    [t_min, 1), then the tokens' own uniforms; a token is masked where its
    uniform lies under its block's level."""
    B, L = tokens.shape
    if L % block:
        raise ValueError("block=%d must divide the sequence length %d"
                         % (block, L))
    keys = jnp.asarray(key)
    typed = jnp.issubdtype(keys.dtype, jax.dtypes.prng_key)
    if keys.ndim == (0 if typed else 1):
        keys = jax.random.split(keys, B)

    def noise(k):
        k_t, k_u = jax.random.split(k)
        t = jax.random.uniform(k_t, (L // block,), jnp.float32, t_min, 1.0)
        return t, jax.random.uniform(k_u, (L,), jnp.float32)

    with jax.named_scope(profile.BD):
        t, u = jax.vmap(noise)(keys)
        t_row = jnp.repeat(t, block, axis=1)
        masked = u < t_row
        noisy = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
        positions = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None], (B, L))
        return {"ids": jnp.concatenate([noisy, tokens], axis=1),
                "positions": jnp.concatenate([positions, positions], axis=1),
                "targets": tokens,
                "weights": jnp.where(masked, 1.0 / (t_row * (B * L)), 0.0),
                "t": t}


def block_diffusion_noisy_half(hidden):
    """The noisy half [B, L, D] of the stack's output [B, 2L, D]: the rows
    the head and the loss run over."""
    with jax.named_scope(profile.BD):
        return hidden[:, :hidden.shape[1] // 2]


def block_diffusion_stats(batch, t_min=1e-3):
    """A step's noise, from what `block_diffusion_batch` returned: ``masked``
    and ``kept`` [B] int32 (they add up to L), ``masked_share`` (of all
    positions), ``t_mean``, ``t_lowest`` / ``t_highest`` (inside [t_min, 1)),
    ``empty_blocks`` (blocks with nothing masked: they add nothing to the
    loss) and ``t_outside`` (blocks whose level left [t_min, 1]: 0)."""
    masked = batch["weights"] > 0
    B, L = masked.shape
    t = batch["t"]
    n = jnp.sum(masked, axis=1, dtype=jnp.int32)
    per_block = jnp.sum(masked.reshape(B, t.shape[1], -1), axis=2)
    return {"masked": n, "kept": L - n,
            "masked_share": jnp.mean(masked.astype(jnp.float32)),
            "t_mean": jnp.mean(t), "t_lowest": jnp.min(t),
            "t_highest": jnp.max(t),
            "empty_blocks": jnp.sum(per_block == 0, dtype=jnp.int32),
            "t_outside": jnp.sum((t < t_min) | (t > 1.0), dtype=jnp.int32)}
