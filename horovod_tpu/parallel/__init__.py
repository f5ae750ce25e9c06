"""Parallelism strategies over JAX device meshes.

The reference framework is data-parallel only (SURVEY.md §2.5); data
parallelism here reproduces it natively (``make_train_step`` = the
DistributedOptimizer loop lowered onto an ICI mesh). Long-context sequence
parallelism (ring attention, Ulysses all-to-all) is a first-class TPU
extension layered on the same mesh machinery.

* :mod:`.mesh`  — topology discovery and Mesh construction (ICI within a
  slice, DCN across slices — the TPU analogue of the reference's
  local/cross communicator split, `common/mpi/mpi_context.cc:133-165`).
* :mod:`.train` — jitted, shard_map'd data-parallel train-step builder
  (the in-XLA equivalent of `_DistributedOptimizer.apply_gradients`,
  reference `horovod/tensorflow/__init__.py:231-258`), with
  ``accum_steps`` gradient accumulation (the flagship
  backward_passes_per_step), ``zero1`` optimizer-state sharding, and
  :func:`make_fsdp_train_step` — FSDP/ZeRO-3 through pure GSPMD
  shardings.
* :mod:`.ring`  — ring attention (blockwise flash attention with k/v
  blocks rotated over the ICI ring via ``ppermute``) and Ulysses-style
  all-to-all sequence parallelism (sp).
* :mod:`.tensor_parallel` — Megatron-style tp: full-size init,
  `tp_param_specs` placement, per-shard `cfg.local()` modules,
  `tp_grad_sync`.
* :mod:`.pipeline` — GPipe pp over stage-stacked blocks, with the
  pinned in-shard_map gradient contract and a ``remat`` option
  (1F1B-class activation memory).
* :mod:`.expert` — routed MoE feed-forward: top-k routing, dropless on
  one device (sort, grouped matmul, unsort), static capacity and
  expert-dim all_to_all under ep, `ep_param_specs` / `ep_grad_sync`.

Pairwise compositions are test-pinned: tp x sp, sp x ep (ring AND
Ulysses), dp x pp, fsdp x tp, plus the dryrun's dp x {sp,tp,ep}
train steps.
"""

from .mesh import (  # noqa: F401
    data_parallel_mesh,
    hybrid_mesh,
    mesh_axis_size,
    topology_summary,
)
from .expert import (  # noqa: F401
    MoeMlp, ep_grad_sync, ep_param_specs, moe_ffn, router_aux_losses,
    routing_stats)
# The exits of a looped stack: the counter beside `routing_stats`; the loss
# itself is `ops.losses.expected_exit_loss`.
from horovod_tpu.ops.losses import exit_stats  # noqa: F401
from .pipeline import pipeline_apply, stack_block_params  # noqa: F401
from .ring import (ring_attention, ulysses_attention,  # noqa: F401
                   zigzag_shard, zigzag_unshard)
from .tensor_parallel import (  # noqa: F401
    tp_grad_sync, tp_param_specs)
from .train import (grad_overlap_options, make_fsdp_train_step,  # noqa: F401
                    make_train_step)
