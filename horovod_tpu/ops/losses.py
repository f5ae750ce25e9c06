"""Memory-lean LM losses.

`chunked_softmax_cross_entropy` computes causal-LM cross entropy
without ever materializing the full [B, L, vocab] logits tensor in
f32: it scans over sequence chunks, projecting each chunk to the
vocabulary, reducing it to logsumexp + target-logit immediately, and
rematerializing the chunk projection in the backward
(``jax.checkpoint``) — peak live memory is O(B * chunk * vocab)
instead of O(B * L * vocab). At GPT-2-small shapes (V=32k) the dense
f32 logits + softmax of a [8, 2048] batch is ~4 GB of HBM traffic per
pass; at L=8192 the dense form does not fit a single v5e at all, the
chunked form does.

No reference analogue (the reference never sees model internals); this
is part of the long-context extension the flash kernels anchor.
"""

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile


def chunked_softmax_cross_entropy(hidden, kernel, targets, chunk=512):
    """Mean token cross entropy over chunked vocab projections.

    Args:
      hidden: [B, L, D] final hidden states (any float dtype; the
        projection runs in the kernel's compute dtype and reduces in
        f32).
      kernel: [D, V] lm-head kernel (no bias, the standard LM head).
      targets: [B, L] int target token ids.
      chunk: sequence chunk length; L must be divisible by it (pass
        chunk=L for one-shot).

    Returns the scalar mean loss = mean(logsumexp(logits) -
    logits[target]) — identical math to log_softmax + gather.
    """
    B, L, D = hidden.shape
    if L % chunk != 0:
        raise ValueError("L=%d not divisible by chunk=%d" % (L, chunk))
    with jax.named_scope(profile.LOSS):
        n = L // chunk
        h = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
        t = targets.reshape(B, n, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def chunk_loss(h_c, t_c):
            logits = (h_c @ kernel.astype(h_c.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, t_c[..., None],
                                      axis=-1)[..., 0]
            return jnp.sum(lse - tgt)

        def body(acc, xs):
            h_c, t_c = xs
            return acc + chunk_loss(h_c, t_c), None

        total, _ = lax.scan(body, jnp.float32(0.0), (h, t))
        return total / (B * L)
