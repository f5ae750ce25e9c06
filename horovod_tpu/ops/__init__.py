"""TPU kernels (Pallas) for hot ops the XLA fuser doesn't already own.

The reference's analogue layer is its CUDA machinery
(`horovod/common/ops/cuda_operations.cc`) — hand-written device code where
the framework needs more than the stock library gives. Here that role is
played by Pallas TPU kernels:

* :mod:`.flash_attention` — blockwise attention with online softmax in
  VMEM (O(L) memory), causal block skipping, custom VJP.
"""

from .flash_attention import flash_attention  # noqa: F401
