"""The selective state-space recurrence of a Mamba-2 layer in its chunked
form (SSD: Dao & Gu, arXiv:2405.21060 §6), in jnp.

Per head h of group g(h), with a state S [P, N]:

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

A chunk of Q tokens is four matrix products and the chunks are tied by a
scan over the L / Q chunk states, never a loop over the tokens and never an
[L, L] array a head:

    cum_t   = sum_{r <= t, r in the chunk} dt_r a             (f32)
    inside  : y_t += sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
    state   : S_c  = sum_s exp(cum_last - cum_s) dt_s x_s (x) B_s
    carry   : S^start_{c+1} = exp(cum_last of c) S^start_c + S_c
    between : y_t += exp(cum_t) C_t . S^start_c

Decays, cumulative sums and the carried states are f32; the operands of the
four products are rounded to the dtype of `x` (bf16 in training) and
accumulate in f32. B and C are a group's, shared by its H / G heads, so
C . B is formed once a group. All of it lies under the scope `hvd_ssd`.
"""

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile


def ssd_scan(x, dt, a, b, c, chunk):
    """x [B, L, H, P]; dt [B, L, H] f32, positive (after the softplus);
    a [H] f32, negative; b, c [B, L, G, N] with G dividing H; L a multiple
    of `chunk`. Returns (y [B, L, H, P] f32, without the skip D x; the
    largest |S| any chunk starts from or the sequence ends in, an f32
    scalar: a counter, no part of a program that does not read it)."""
    B, L, H, P = x.shape
    G, N = b.shape[2:]
    if L % chunk or H % G:
        raise ValueError("ssd_scan: length %d is no multiple of the chunk "
                         "%d, or %d heads are not shared by %d groups"
                         % (L, chunk, H, G))
    K, Q, nc = H // G, chunk, L // chunk
    f32 = jnp.float32
    with jax.named_scope(profile.SSD):
        dt = dt.astype(f32)
        # [B, nc, G, K, Q]: the tokens of a chunk last, as the products
        # take them.
        cum = jnp.cumsum(
            (dt * a.astype(f32)).reshape(B, nc, Q, G, K), axis=2
        ).transpose(0, 1, 3, 4, 2)
        last = cum[..., -1:]
        xf = (x.astype(f32) * dt[..., None]).reshape(B, nc, Q, G, K, P)
        bq = b.reshape(B, nc, Q, G, N)
        cq = c.reshape(B, nc, Q, G, N)
        # Inside a chunk: (C B^T (.) decay) (dt x), the decay masked before
        # the exponential (cum_t - cum_s > 0 above the diagonal).
        cb = jnp.einsum("bctgn,bcsgn->bcgts", cq, bq,
                        preferred_element_type=f32)
        seg = cum[..., :, None] - cum[..., None, :]
        causal = lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
            lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        y = jnp.einsum("bcgkts,bcsgkp->bctgkp",
                       (cb[:, :, :, None] * decay).astype(x.dtype),
                       xf.astype(x.dtype), preferred_element_type=f32)
        # A chunk's own state, [B, nc, G, K, P, N].
        to_end = jnp.exp(last - cum).transpose(0, 1, 4, 2, 3)[..., None]
        states = jnp.einsum("bcsgn,bcsgkp->bcgkpn", bq,
                            (xf * to_end).astype(x.dtype),
                            preferred_element_type=f32)
        # The carry over the chunks, f32.
        total = jnp.exp(last)[..., None]  # [B, nc, G, K, 1, 1]

        def carry(s, step):
            s_c, t_c = step
            return t_c * s + s_c, s

        final, starts = lax.scan(
            carry, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)
        # Between chunks: exp(cum_t) C_t . S^start.
        y = y + jnp.einsum(
            "bctgn,bcgkpn->bctgkp", cq, starts.astype(x.dtype),
            preferred_element_type=f32) * jnp.exp(cum).transpose(
                0, 1, 4, 2, 3)[..., None]
        state_max = jnp.maximum(jnp.max(jnp.abs(starts)),
                                jnp.max(jnp.abs(final)))
        return y.reshape(B, L, H, P), state_max
