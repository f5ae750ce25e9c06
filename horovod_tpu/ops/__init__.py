"""TPU kernels (Pallas) for hot ops the XLA fuser doesn't already own.

The reference's analogue layer is its CUDA machinery
(`horovod/common/ops/cuda_operations.cc`) — hand-written device code where
the framework needs more than the stock library gives. Here that role is
played by Pallas TPU kernels:

* :mod:`.flash_attention` — blockwise attention with online softmax in
  VMEM (O(L) memory), causal block skipping, custom VJP.
* :mod:`.grouped_matmul` — the experts of a dropless mixture-of-experts
  layer: rows in contiguous ragged groups, each group its own matrix,
  f32 matrices rounded in VMEM, custom VJP (rows' and matrices'
  gradients).
* :mod:`.hc_stat` — a hyper-connection's per-token sum of squares and
  its projection onto the maps' columns from one read of the residual
  streams, custom VJP (the projection's gradient from one more; the
  streams' own gradient is jnp).
* :mod:`.moe_rows` — the dispatch and the combine of a routed layer that
  holds a part of the experts: the rows of its k*T-row buffer moved by
  the count of those that are live, each op's transpose the other
  kernel.
* :mod:`.moe_act` — the experts' activation between the grouped matmuls
  of such a layer (``act(g) * h``, or ``act(h)``): the live tiles of its
  buffers alone, zeros to the end of the last one, custom VJP.
* :mod:`.kda_conv` — the short convolutions of a Kimi Delta Attention
  mixer with their SiLU and the head norms of q and k: the q | k | v
  columns of the in-projection read once, q, k, v written once as the
  recurrence's kernels read them, custom VJP (the pre-activation formed
  again, the taps' gradient resident).

Beside them, in jnp (XLA's fusions own it until a trace says otherwise):

* :mod:`.ssd` — the selective state-space recurrence of a Mamba-2 layer
  in its chunked form: four matrix products a chunk, the chunk states
  carried by a scan, f32 decays and carry under bf16 operands.
* :mod:`.kda` — Kimi Delta Attention's gated delta rule (a decay a channel)
  in its chunked form: the decayed scores by sub-blocks, one
  unit-lower-triangular solve a chunk (the WY form), a scan over the chunks
  whose body multiplies WITH the carried state.
* :mod:`.sconv` — the pass between the two projections of a double-gated
  short convolution mixer (LFM2): u = B * z, a few causal taps a channel as
  shifted sums, G * c, f32 to one rounding.
"""

from .flash_attention import (  # noqa: F401
    BandMask, BlockDiffusionMask, flash_attention)
