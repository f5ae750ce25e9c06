"""JAX binding — the TPU-native flagship API.

Two data planes, selected automatically:

* **In-jit (TPU path)**: inside ``jit``/``shard_map``/``pmap`` with a mapped
  axis, collectives lower to XLA ``AllReduce``/``AllGather``/
  ``CollectiveBroadcast`` over ICI — the TPU analogue of the reference's
  NCCL plane (/root/reference horovod/common/ops/nccl_operations.cc). XLA
  fuses and schedules them; no host round trip.
* **Host path**: on concrete arrays outside jit, tensors ride the C++ core
  (negotiation, fusion, response cache) exactly like the reference's CPU
  path (ops/mpi_operations.cc / gloo_operations.cc) — used for parameter
  broadcast, eager-style code, and cross-host DCN traffic.

API parity with the reference framework bindings
(``horovod/tensorflow/__init__.py``, ``horovod/torch/__init__.py``):
``init/rank/size/allreduce/allgather/broadcast``, ``DistributedOptimizer``
(optax), ``broadcast_parameters``, ``Compression``.
"""

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as _hvd
from horovod_tpu import compression as _wire
from horovod_tpu import profile as _profile
from horovod_tpu import (  # noqa: F401
    init, shutdown, is_initialized, rank, local_rank, cross_rank, size,
    local_size, cross_size, is_homogeneous,
    mpi_threads_supported, mpi_enabled, mpi_built, gloo_enabled,
    gloo_built, nccl_built, ddl_built, mlsl_built,
)
# Elastic API: hvd.elastic.run / hvd.elastic.ElasticState (reference
# analogue: horovod.tensorflow.elastic).
from horovod_tpu import elastic  # noqa: F401
from horovod_tpu.common import ops as _ops

# Default mapped-axis name for the in-jit data plane.
AXIS_NAME = "hvd"

_name_counter = [0]


def _auto_name(prefix):
    _name_counter[0] += 1
    return "%s.%d" % (prefix, _name_counter[0])


def _reset_auto_names():
    """Generation reset: auto-generated collective names must restart
    from the same counter on every member after (re-)init. Without this,
    a survivor of an elastic shrink/regrow keeps its old count while a
    freshly spawned worker starts at zero — the two negotiate different
    names for the same call site and the job hangs (the divergence
    cross-check reports it; this removes the cause)."""
    _name_counter[0] = 0
    _assert_counter[0] = 0


_hvd.register_init_callback(_reset_auto_names)


def _is_traced(x):
    return isinstance(x, jax.core.Tracer)


def _axis_in_scope(axis_name):
    try:
        jax.lax.axis_index(axis_name)
        return True
    except NameError:
        return False
    except Exception:
        return False


def _multi_process():
    return _hvd.is_initialized() and _hvd.size() > 1


def _require_init_traced():
    """A collective traced in plain jit (no mapped axis) before ``init()``
    must fail loudly — silently degrading to identity would let a
    multi-process program train unsynchronized. (The in-jit mapped-axis
    plane needs no init: it is pure XLA.)"""
    if not _hvd.is_initialized():
        raise RuntimeError(
            "horovod_tpu collective used inside jit before hvd.init(); "
            "call init() first (single-process size-1 init is fine)")


def _host_callback(fn, tensor):
    """Routes a traced tensor through the host core from inside jit.

    ``ordered=True`` is required for deadlock freedom: every rank traces
    the same program, so ordered callbacks enqueue collectives in the same
    sequence on all ranks while each callback blocks on its completion.
    """
    from jax.experimental import io_callback
    out_shape = jax.ShapeDtypeStruct(tensor.shape, tensor.dtype)
    return io_callback(fn, out_shape, tensor, ordered=True)


class Compression:
    """Gradient compression codecs (reference: tensorflow/compression.py).

    Two families share this namespace:

    * legacy tensor codecs (``fp16``/``bf16`` classes below) cast the
      TENSOR before the collective and back after — reduction then
      accumulates in the narrow dtype;
    * wire modes (``wire_bf16``/``wire_int8``, =
      ``horovod_tpu.compression.Compression``) re-encode only the bytes
      each transport hop moves, keeping the f32 accumulator — the
      preferred, negotiated, cache-keyed path (docs/COMPRESSION.md).
      Strings ('bf16', 'int8') and ``HVD_TPU_COMPRESSION`` select these.
    """

    # Wire modes (docs/COMPRESSION.md): negotiated per tensor, f32
    # accumulation, selectable by string everywhere compression= is
    # accepted.
    wire_bf16 = _wire.Compression.bf16
    wire_int8 = _wire.Compression.int8

    class none:
        @staticmethod
        def compress(tensor):
            return tensor, None

        @staticmethod
        def decompress(tensor, ctx):
            return tensor

    class fp16:
        @staticmethod
        def compress(tensor):
            if tensor.dtype in (jnp.float32, jnp.float64):
                return tensor.astype(jnp.float16), tensor.dtype
            return tensor, None

        @staticmethod
        def decompress(tensor, ctx):
            return tensor.astype(ctx) if ctx is not None else tensor

    class bf16:
        """bfloat16 — the native TPU 16-bit format; preferred on TPU."""

        @staticmethod
        def compress(tensor):
            if tensor.dtype in (jnp.float32, jnp.float64):
                return tensor.astype(jnp.bfloat16), tensor.dtype
            return tensor, None

        @staticmethod
        def decompress(tensor, ctx):
            return tensor.astype(ctx) if ctx is not None else tensor


def allreduce(tensor, average=True, name=None, axis_name=AXIS_NAME,
              compression=None, prescale_factor=1.0,
              postscale_factor=1.0, group=None):
    """Allreduce across ranks (and, in-jit, across the mapped axis).

    ``group``: a ``hvd.ProcessGroup`` scoping the HOST-plane collective
    to a subgroup (docs/GROUPS.md) — e.g. ``hvd.batch_group()`` under
    ``init(model_parallel=k)``. The in-jit mapped-axis plane expresses
    subgroups through MESH AXES instead (psum over the batch or model
    axis of a 2-D mesh); ``group`` is ignored there.

    ``compression``: a wire mode ('none'/'bf16'/'int8', a
    ``horovod_tpu.compression`` mode, or None = HVD_TPU_COMPRESSION) —
    or a legacy tensor codec (``Compression.fp16``/``.bf16``), which
    keeps its historical cast-the-tensor semantics. Wire modes keep f32
    accumulation on both data planes: in-jit, bf16 and int8 run the
    EQuARX-style ``ring_allreduce`` with encode/decode fused into each
    hop (narrow bytes on the link, f32 dequant-add); on the host plane
    the mode rides the negotiation into the native ring
    (docs/COMPRESSION.md).
    """
    legacy = compression is not None and hasattr(compression, "compress")
    mode = _wire.Compression.none if legacy else _wire.resolve(compression)
    if _is_traced(tensor):
        if _axis_in_scope(axis_name):
            # XLA/ICI plane. none/legacy: psum over the mapped axis; XLA
            # emits an AllReduce that rides the TPU interconnect.
            compressed, ctx = (compression.compress(tensor) if legacy
                               else (tensor, None))
            if prescale_factor != 1.0:
                compressed = compressed * prescale_factor
            if mode.mode != _wire.NONE and \
                    compressed.dtype == jnp.float32:
                # Compressed modes ride the explicit ppermute ring: each
                # hop ships the narrow payload but dequantizes and ADDS
                # IN F32, preserving the f32-accumulation contract. (A
                # bf16-operand psum would NOT: XLA's AllReduce reduction
                # computation for a bf16 operand is add(bf16,bf16), so
                # every pairwise add rounds — error grows with world
                # size.)
                from horovod_tpu.parallel.ring import ring_allreduce
                summed = ring_allreduce(compressed, axis_name,
                                        compression=mode)
            else:
                summed = jax.lax.psum(compressed, axis_name)
            if average:
                summed = summed / jax.lax.psum(1, axis_name)
            if postscale_factor != 1.0:
                summed = summed * postscale_factor
            return compression.decompress(summed, ctx) if legacy \
                else summed.astype(tensor.dtype)
        if _multi_process():
            # Plain jit, no mapped axis: ride the host core via an ordered
            # callback (the reference's "CPU op inside the graph" shape).
            op_name = name or _auto_name("allreduce")

            def _cb(arr):
                return np.asarray(_ops.allreduce(
                    np.asarray(arr), op_name, average=average,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    compression=mode, group=group)).astype(arr.dtype)

            compressed, ctx = (compression.compress(tensor) if legacy
                               else (tensor, None))
            reduced = _host_callback(_cb, compressed)
            return compression.decompress(reduced, ctx) if legacy \
                else reduced
        _require_init_traced()
        # Single process: allreduce is identity up to scaling.
        scale = prescale_factor * postscale_factor
        return tensor * scale if scale != 1.0 else tensor
    compressed, ctx = (compression.compress(tensor) if legacy
                       else (tensor, None))
    arr = np.asarray(compressed)
    out = _ops.allreduce(arr, name or _auto_name("allreduce"),
                         average=average, prescale_factor=prescale_factor,
                         postscale_factor=postscale_factor,
                         compression=mode, group=group)
    result = jnp.asarray(out)
    return compression.decompress(result, ctx) if legacy else result


def reduce_scatter(tensor, average=True, name=None, axis_name=AXIS_NAME,
                   compression=None, prescale_factor=1.0,
                   postscale_factor=1.0, group=None):
    """Reduce-scatter across ranks (docs/ZERO.md): the tensor is
    flattened, summed (or averaged) across ranks, and this rank keeps
    only its 1/N shard of the result — the gradient leg of the sharded
    weight update.

    In-jit over a mapped axis the flat tensor must divide evenly by the
    axis size (pad first; ``parallel.ring.ring_reduce_scatter`` handles
    padding and the compressed per-hop ring). On the host plane the
    shard partition is :func:`horovod_tpu.shard_partition` (uneven sizes
    allowed). Returns a 1-D array.
    """
    mode = _wire.resolve(compression)
    if _is_traced(tensor):
        if _axis_in_scope(axis_name):
            from horovod_tpu.parallel.ring import ring_reduce_scatter
            flat = tensor.reshape(-1)
            if prescale_factor != 1.0:
                flat = flat * prescale_factor
            shard = ring_reduce_scatter(flat, axis_name, compression=mode)
            if average:
                shard = shard / jax.lax.psum(1, axis_name)
            if postscale_factor != 1.0:
                shard = shard * postscale_factor
            return shard.astype(tensor.dtype)
        if _multi_process():
            from jax.experimental import io_callback

            from horovod_tpu import groups as _grp
            op_name = name or _auto_name("reduce_scatter")
            counts, _ = _ops.shard_partition(
                int(np.prod(tensor.shape, dtype=np.int64)),
                _grp.group_size(group))
            my_count = counts[_grp.group_rank(group)]

            def _cb(arr):
                return np.asarray(_ops.reduce_scatter(
                    np.asarray(arr), op_name, average=average,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    compression=mode, group=group)).astype(arr.dtype)

            out_shape = jax.ShapeDtypeStruct((my_count,), tensor.dtype)
            return io_callback(_cb, out_shape, tensor, ordered=True)
        _require_init_traced()
        scale = prescale_factor * postscale_factor
        flat = tensor.reshape(-1)
        return flat * scale if scale != 1.0 else flat
    arr = np.asarray(tensor)
    out = _ops.reduce_scatter(arr, name or _auto_name("reduce_scatter"),
                              average=average,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              compression=mode, group=group)
    return jnp.asarray(out)


def allgather(tensor, name=None, axis_name=AXIS_NAME, group=None):
    """Concatenates tensors from all ranks along dim 0.

    In plain jit without a mapped axis, all ranks must pass equal shapes
    (the host path outside jit supports unequal first dims, like the
    reference's allgatherv)."""
    if _is_traced(tensor):
        if _axis_in_scope(axis_name):
            return jax.lax.all_gather(tensor, axis_name, tiled=True)
        if _multi_process():
            from jax.experimental import io_callback

            from horovod_tpu import groups as _grp
            op_name = name or _auto_name("allgather")
            if tensor.ndim == 0:  # match the host path's 0-d -> (1,)
                tensor = tensor.reshape(1)

            def _cb(arr):
                return np.asarray(
                    _ops.allgather(np.asarray(arr), op_name, group=group))

            shape = (tensor.shape[0] * _grp.group_size(group),) + \
                tuple(tensor.shape[1:])
            out_shape = jax.ShapeDtypeStruct(shape, tensor.dtype)
            return io_callback(_cb, out_shape, tensor, ordered=True)
        _require_init_traced()
        return tensor
    arr = np.asarray(tensor)
    out = _ops.allgather(arr, name or _auto_name("allgather"), group=group)
    return jnp.asarray(out)


def broadcast(tensor, root_rank=0, name=None, axis_name=AXIS_NAME,
              group=None):
    """Broadcasts the root rank's tensor — or pytree of tensors,
    leaf-wise with order-stable names — to every rank (the group's
    members under ``group=``; ``root_rank`` stays a WORLD rank)."""
    leaves, treedef = jax.tree_util.tree_flatten(tensor)
    if len(leaves) != 1 or leaves[0] is not tensor:
        base = name or _auto_name("broadcast")
        out = [_broadcast_one(leaf, root_rank, "%s.%d" % (base, i),
                              axis_name, group)
               for i, leaf in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)
    return _broadcast_one(tensor, root_rank, name, axis_name, group)


def _broadcast_one(tensor, root_rank, name, axis_name, group=None):
    if _is_traced(tensor):
        if _axis_in_scope(axis_name):
            # In-jit: mask every rank but the root to zero and psum — XLA
            # lowers this to a select+AllReduce with O(tensor) memory per
            # rank, vs. the N x tensor an all_gather would materialize.
            idx = jax.lax.axis_index(axis_name)
            masked = jnp.where(idx == root_rank, tensor,
                               jnp.zeros_like(tensor))
            # psum promotes bool to int32; cast back (no-op otherwise).
            return jax.lax.psum(masked, axis_name).astype(tensor.dtype)
        if _multi_process():
            op_name = name or _auto_name("broadcast")

            def _cb(arr):
                return np.asarray(_ops.broadcast(
                    np.asarray(arr), root_rank, op_name,
                    group=group)).astype(arr.dtype)

            return _host_callback(_cb, tensor)
        _require_init_traced()
        return tensor
    arr = np.asarray(tensor)
    out = _ops.broadcast(arr, root_rank, name or _auto_name("broadcast"),
                         group=group)
    return jnp.asarray(out)


def allreduce_gradients(grads, average=True, name_prefix="grad",
                        compression=None, axis_name=AXIS_NAME, group=None):
    """Allreduces a pytree of gradients (order-stable naming so all ranks
    negotiate the same tensors). ``compression`` as in :func:`allreduce`
    (wire modes negotiate per leaf; the core fuses same-mode leaves into
    one ring pass). ``group`` scopes the reduction — under a 2-D mesh
    this is the BATCH group: gradients average over the ranks sharing
    this model shard only (docs/GROUPS.md)."""
    legacy = compression is not None and hasattr(compression, "compress")
    mode = _wire.Compression.none if legacy else _wire.resolve(compression)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if leaves and _is_traced(leaves[0]):
        # Named for the profiler (hvd.profile): the collectives and what
        # is fused around them (compression casts, the divide).
        with jax.named_scope(_profile.GRAD_SYNC):
            reduced = [allreduce(g, average=average, axis_name=axis_name,
                                 compression=compression, group=group)
                       for g in leaves]
        return jax.tree_util.tree_unflatten(treedef, reduced)
    # Host path: enqueue everything first so the core can fuse within a
    # cycle, then synchronize in order.
    from horovod_tpu import groups as _grp
    handles = []
    for i, g in enumerate(leaves):
        comp, ctx = compression.compress(g) if legacy else (g, None)
        arr = np.asarray(comp)
        postscale = 1.0 / _grp.group_size(group) if average else 1.0
        handles.append((_ops.allreduce_async(arr, "%s.%d" % (name_prefix, i),
                                             postscale_factor=postscale,
                                             compression=mode, group=group),
                        ctx))
    reduced = []
    for h, ctx in handles:
        out = jnp.asarray(_ops.synchronize(h))
        reduced.append(compression.decompress(out, ctx) if legacy else out)
    return jax.tree_util.tree_unflatten(treedef, reduced)


def broadcast_parameters(params, root_rank=0, name_prefix="param"):
    """Broadcasts a pytree of parameters from root (consistent init /
    checkpoint restore; reference: torch/__init__.py:255-284)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    handles = []
    for i, p in enumerate(leaves):
        arr = np.asarray(p)
        handles.append(
            _ops.broadcast_async(arr, root_rank, "%s.%d" % (name_prefix, i)))
    out = [jnp.asarray(_ops.synchronize(h)) for h in handles]
    # Preserve original dtypes (e.g. bf16 params round-trip exactly).
    out = [o.astype(l.dtype) if hasattr(l, "dtype") else o
           for o, l in zip(out, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_optimizer_state(opt_state, root_rank=0,
                              name_prefix="opt_state"):
    """Broadcasts an optax optimizer state pytree from root."""
    return broadcast_parameters(opt_state, root_rank=root_rank,
                                name_prefix=name_prefix)


def DistributedOptimizer(optimizer, compression=None,
                         average=True, name_prefix="grad",
                         axis_name=AXIS_NAME, sharded_update=None,
                         group=None, agc=None):
    """Wraps an optax GradientTransformation so every update first averages
    gradients across ranks (reference: _DistributedOptimizer,
    tensorflow/__init__.py:231-258).

    Works both inside a jitted+shard_map'd step (psum plane) and eagerly on
    host arrays (core plane). ``compression='bf16'``/``'int8'`` (or
    ``HVD_TPU_COMPRESSION``) shrinks the gradient bytes every hop moves
    — see :func:`allreduce` and docs/COMPRESSION.md, including when NOT
    to compress (integer/embedding gradients; hvd-lint flags those).

    ``sharded_update=True`` (job-wide: ``HVD_TPU_SHARDED_UPDATE=1``)
    switches the host plane to the ZeRO-style sharded weight update
    (docs/ZERO.md): gradients are flattened into ONE fused buffer and
    reduce-scattered (same wire bytes as the allreduce they replace —
    the ring's reduce-scatter leg runs either way), the optimizer
    applies only to this rank's 1/N shard — so momentum/Adam state
    shrinks N-fold — and updated parameter shards are allgathered back.
    Numerically identical to the replicated path for ELEMENTWISE
    transforms (sgd/momentum/adam/adamw...). Mixed sharded/replicated
    ranks are rejected at negotiation naming both ranks and modes. For
    the in-jit XLA plane use ``parallel.make_train_step(zero1=True)``
    instead. The optimizer state it returns is RANK-LOCAL — read it
    through :func:`sharded_state_full` (hvd-lint rule
    ``sharded-update-rank-local-param-read`` flags direct reads).

    ``group`` scopes the gradient reduction to a process group; under
    ``hvd.init(model_parallel=k)`` it DEFAULTS to this rank's batch
    group, so a mesh job's gradients average over the ranks sharing its
    model shard without any call-site change (docs/GROUPS.md).

    ``agc`` enables adaptive gradient clipping at the given clipping
    factor (e.g. 0.01 — ``ops/agc.py``, arxiv 2102.06171): each
    parameter's reduced gradient is unit-wise clipped against the
    parameter's own norm BEFORE the inner optimizer. This is what makes
    the norm-free zoo variants (``resnet50nf``/``resnet101nf`` — the
    measured-fastest conv route, PERF.md) trainable; it requires
    ``update(grads, state, params)`` and is rejected under
    ``sharded_update`` (1/N flat shards destroy the unit structure).
    """
    import optax

    if sharded_update is None:
        sharded_update = _ops.sharded_update_default()
    if sharded_update:
        if agc is not None:
            raise ValueError(
                "agc= does not compose with sharded_update: the sharded "
                "path updates 1/N flat shards, which destroys the "
                "per-unit (output-row) norm structure AGC clips against "
                "— every rank would clip a different slice of each "
                "filter. Use replicated updates with AGC, or chain "
                "optax.adaptive_grad_clip equivalents before a "
                "replicated optimizer")
        from horovod_tpu.groups import assert_sharded_update_world_scope
        assert_sharded_update_world_scope(group)
        return _sharded_distributed_optimizer(optimizer, compression,
                                              average, name_prefix)

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(updates, state, params=None):
        # group=None resolves to the CURRENT batch group per update:
        # construction-time capture would go stale across elastic
        # re-inits (the mesh re-forms with fresh ids).
        grp = group if group is not None else _hvd.batch_group()
        updates = allreduce_gradients(updates, average=average,
                                      name_prefix=name_prefix,
                                      compression=compression,
                                      axis_name=axis_name, group=grp)
        with jax.named_scope(_profile.OPTIMIZER):
            if agc is not None:
                # Clip AFTER the reduction: the threshold applies to the
                # true global gradient, and every rank clips identically.
                from horovod_tpu.ops.agc import agc_clip
                if params is None:
                    raise ValueError(
                        "agc= needs params: call update(grads, state, "
                        "params) — the clip threshold is relative to each "
                        "parameter's unit-wise norm")
                updates = agc_clip(updates, params, clipping=agc)
            return optimizer.update(updates, state, params)

    return optax.GradientTransformation(init_fn, update_fn)


def _flat_f32_concat(tree):
    """Flattens a pytree of arrays into one f32 vector (the Python-level
    fusion buffer: leaf offsets in flatten order ARE the shard
    boundaries' coordinate system)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return np.zeros(0, np.float32), leaves, treedef
    flat = np.concatenate(
        [np.ravel(np.asarray(l)).astype(np.float32) for l in leaves])
    return flat, leaves, treedef


def _report_opt_state_bytes(inner_state):
    """Reports this rank's optimizer-state bytes into the native
    opt_state_bytes gauge (docs/ZERO.md — the memory claim, observable
    in hvd-top and the bench A/B)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(inner_state):
        arr = np.asarray(leaf)
        total += arr.nbytes
    _hvd.get_basics().opt_state_metrics(total)


def _sharded_distributed_optimizer(optimizer, compression, average,
                                   name_prefix):
    """The sharded_update host-plane transformation (docs/ZERO.md).

    State layout: ``{"inner": <optimizer state over this rank's flat
    shard>, "total": <flat element count>, "world": <world size it was
    sharded for>, "rank": <owning rank>}``. The inner state's array
    leaves are SHARDS — 1/N of each momentum/Adam moment.
    """
    import optax

    mode = _wire.resolve_wire_arg(compression, Compression.none)

    def _my_shard(flat):
        counts, offsets = _ops.shard_partition(flat.size, _hvd.size())
        r = _hvd.rank()
        return flat[offsets[r]:offsets[r] + counts[r]]

    def init_fn(params):
        flat, _, _ = _flat_f32_concat(params)
        inner = optimizer.init(jnp.asarray(_my_shard(flat)))
        _report_opt_state_bytes(inner)
        return {"inner": inner, "total": int(flat.size),
                "world": _hvd.size(), "rank": _hvd.rank()}

    def update_fn(updates, state, params=None):
        # Re-checked per update: a mesh formed AFTER the optimizer was
        # built must fail here, not silently reduce-scatter the fused
        # buffer across model shards.
        from horovod_tpu.groups import assert_sharded_update_world_scope
        assert_sharded_update_world_scope()
        if params is None:
            raise ValueError(
                "sharded_update needs params: call update(grads, state, "
                "params) — the updated shard is params + update")
        if state["world"] != _hvd.size() or state["rank"] != _hvd.rank():
            raise RuntimeError(
                "sharded optimizer state was built for rank %d of %d but "
                "this process is rank %d of %d; after an elastic resize "
                "restore the last COMMITTED full-form state (the old "
                "membership's shards are gone) and re-shard it via "
                "sharded_state_shard() (docs/ZERO.md)"
                % (state["rank"], state["world"], _hvd.rank(), _hvd.size()))
        flat_g, _, _ = _flat_f32_concat(updates)
        if flat_g.size != state["total"]:
            raise ValueError("gradient tree has %d elements; state was "
                             "built for %d" % (flat_g.size, state["total"]))
        # ONE fused reduce-scatter over the flat gradient buffer. The
        # name deliberately matches the replicated path's first per-leaf
        # allreduce ("<prefix>.0") so a sharded rank meeting a replicated
        # peer collides at negotiation and is rejected naming both ranks
        # and modes (docs/ZERO.md) instead of hanging.
        g_shard = np.asarray(_ops.reduce_scatter(
            flat_g, "%s.0" % name_prefix, average=average,
            compression=mode))
        flat_p, p_leaves, treedef = _flat_f32_concat(params)
        p_shard = _my_shard(flat_p)
        u_shard, inner = optimizer.update(
            jnp.asarray(g_shard), state["inner"], jnp.asarray(p_shard))
        new_shard = p_shard + np.asarray(u_shard, np.float32)
        # Allgather of updated parameter shards: rank order == chunk
        # order, so the concatenation IS the full flat parameter vector.
        full_new = np.asarray(_ops.allgather(
            new_shard, "%s.param_ag" % name_prefix))
        _report_opt_state_bytes(inner)
        out_leaves = []
        off = 0
        for leaf in p_leaves:
            arr = np.asarray(leaf)
            seg = full_new[off:off + arr.size].reshape(arr.shape)
            off += arr.size
            out_leaves.append(jnp.asarray(
                (seg - arr.astype(np.float32)).astype(arr.dtype)))
        new_state = {"inner": inner, "total": state["total"],
                     "world": state["world"], "rank": state["rank"]}
        return jax.tree_util.tree_unflatten(treedef, out_leaves), new_state

    return optax.GradientTransformation(init_fn, update_fn)


def sharded_state_full(state, name_prefix="shard_state"):
    """Materializes a sharded optimizer state (from
    ``DistributedOptimizer(sharded_update=True)``) as its FULL,
    world-size-independent form: every shard-shaped array leaf of the
    inner state is allgathered into the full flat array; scalar leaves
    (Adam's step count) pass through. This is a COLLECTIVE — call it on
    every rank at the same point (a checkpoint/commit boundary).

    The result re-shards to ANY world size via
    :func:`sharded_state_shard`, which is how sharded state rides the
    durable checkpoint layer's re-shard-on-restore contract
    (docs/ZERO.md). Idempotent: a state already in full form is
    returned unchanged (no collective)."""
    if state["world"] == -1:
        return state
    if state["world"] != _hvd.size() or state["rank"] != _hvd.rank():
        # The old membership's shards no longer exist anywhere:
        # allgathering over the CURRENT ranks would reassemble a short
        # buffer and silently label it full. Only the membership that
        # built the shards can materialize them.
        raise RuntimeError(
            "sharded optimizer state was built for rank %d of %d but "
            "this process is rank %d of %d; the full form can only be "
            "materialized by the membership that built the shards — "
            "restore the last COMMITTED full-form state instead "
            "(docs/ZERO.md)"
            % (state["rank"], state["world"], _hvd.rank(), _hvd.size()))
    counts, _ = _ops.shard_partition(state["total"], state["world"])
    my_count = counts[state["rank"]]
    leaves, treedef = jax.tree_util.tree_flatten(state["inner"])
    out = []
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        if arr.ndim >= 1 and arr.shape[0] == my_count:
            arr = np.asarray(_ops.allgather(
                arr, "%s.%d" % (name_prefix, i)))
        out.append(arr)
    # world/rank -1 = "full form, not sharded for anyone" (not None:
    # the elastic state sync broadcasts every leaf through numpy).
    return {"inner": jax.tree_util.tree_unflatten(treedef, out),
            "total": state["total"], "world": -1, "rank": -1}


def sharded_state_shard(full_state):
    """Inverse of :func:`sharded_state_full` for the CURRENT rank/world:
    slices every full-length array leaf down to this rank's shard. Pure
    local slicing — no collective — so a restore path can re-shard a
    checkpointed full state at any world size. A state still sharded
    for THIS rank/world passes through unchanged; one sharded for a
    different (rank, world) cannot be re-sliced locally and is
    rejected (materialize the full form on the OLD membership first)."""
    if full_state["world"] != -1:
        if full_state["world"] == _hvd.size() and \
                full_state["rank"] == _hvd.rank():
            return full_state
        raise ValueError(
            "sharded_state_shard needs the full form (world=-1) or a "
            "state already sharded for this rank; got one sharded for "
            "rank %d of %d on rank %d of %d — call sharded_state_full() "
            "before the membership changes"
            % (full_state["rank"], full_state["world"], _hvd.rank(),
               _hvd.size()))
    total = full_state["total"]
    counts, offsets = _ops.shard_partition(total, _hvd.size())
    r = _hvd.rank()
    lo, hi = offsets[r], offsets[r] + counts[r]
    leaves, treedef = jax.tree_util.tree_flatten(full_state["inner"])
    out = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.ndim >= 1 and arr.shape[0] == total:
            arr = arr[lo:hi]
        out.append(jnp.asarray(arr))
    return {"inner": jax.tree_util.tree_unflatten(treedef, out),
            "total": total, "world": _hvd.size(), "rank": r}


def init_distributed(local_device_ids=None):
    """Bootstraps ``jax.distributed`` from horovod_tpu's topology so jit
    programs span every host's chips (XLA collectives over ICI within a
    host/slice and DCN across hosts — the reference's multi-host NCCL
    role, SURVEY §2.6/§5.8).

    Call after ``init()``. Rank 0 reserves the coordinator port and
    broadcasts it through the host core, so no extra configuration is
    needed beyond the launcher's own rendezvous. No-op at size 1 or when
    jax.distributed is already initialized (idempotent: users following
    the standard JAX convention may have called
    ``jax.distributed.initialize`` themselves).
    """
    import os

    if not _hvd.is_initialized():
        raise RuntimeError("call hvd.init() before init_distributed()")
    if jax.distributed.is_initialized():
        return
    size = _hvd.size()
    if size <= 1:
        return
    from horovod_tpu.run.rendezvous import reserve_port

    port = reserve_port() if _hvd.rank() == 0 else 0
    port = int(np.asarray(_ops.broadcast(
        np.array([port], np.int64), 0, "jax_dist.coordinator_port"))[0])
    addrs = (os.environ.get("HVD_TPU_ADDRS") or "").split(",")
    if not addrs[0]:
        # Unreachable after a size>1 init (the core requires the addr
        # table); fail fast rather than pointing peers at loopback.
        raise RuntimeError(
            "HVD_TPU_ADDRS is not set; cannot derive the jax.distributed "
            "coordinator host")
    host = addrs[0].rsplit(":", 1)[0]
    jax.distributed.initialize(
        coordinator_address="%s:%d" % (host, port),
        num_processes=size, process_id=_hvd.rank(),
        local_device_ids=local_device_ids)


def sync_batch_norm_stats(stat_sum, stat_sumsq, count, group=None,
                          name="sync_bn", axis_name=AXIS_NAME):
    """Distributed-BN stats reduction (docs/GROUPS.md composition): sums
    per-replica (sum, sum-of-squares) partial statistics across ranks —
    ``group``-scoped on the host plane (e.g. ``hvd.batch_group()`` under
    a 2-D mesh so statistics stay within the batch group), psum when a
    mapped axis is in scope — and returns ``(mean, var, global_count)``.

    The standalone jax-wrapper surface for CUSTOM norm layers bringing
    their own one-pass statistics. The shipped module
    (``ops.batch_norm.LeanBatchNorm(sync_group=...)`` or
    ``(axis_name=...)``) does this same reduction inside its custom VJP
    (``_lean_sync`` — the backward needs its own group-scoped pass, which
    a forward-only helper cannot provide).
    ``count`` is the PER-REPLICA element count behind the partial sums
    (a static int)."""
    from horovod_tpu import groups as _grp

    stacked = jnp.stack([jnp.asarray(stat_sum, jnp.float32),
                         jnp.asarray(stat_sumsq, jnp.float32)])
    if _is_traced(stacked) and _axis_in_scope(axis_name):
        total = jax.lax.psum(stacked, axis_name)
        n = jax.lax.psum(1, axis_name)
    else:
        total = allreduce(stacked, average=False, name=name, group=group)
        n = _grp.group_size(group)
    global_count = count * n
    mean = total[0] / global_count
    var = jnp.maximum(total[1] / global_count - mean * mean, 0.0)
    return mean, var, global_count


def metric_average(value, name=None):
    """Averages a scalar metric across ranks (reference:
    _keras/callbacks.py MetricAverageCallback semantics)."""
    arr = np.asarray(value, dtype=np.float64)
    return float(_ops.allreduce(arr, name or _auto_name("metric"),
                                average=True))


def collective_digest():
    """This rank's collective call fingerprint: ``(seq, digest)``.

    ``seq`` counts host-plane collectives enqueued since init; ``digest``
    is a rolling FNV-1a over each call's (op, dtype, shape-rank, name).
    Two ranks that executed identical call sequences report identical
    values. (In-jit psum/all_gather collectives ride XLA, not the host
    core, and are not counted — XLA already guarantees their cross-rank
    consistency by construction.)"""
    return _hvd.get_basics().call_digest()


class DivergenceError(RuntimeError):
    """Raised by :func:`assert_synchronized` when ranks' collective call
    sequences have diverged."""


_assert_counter = [0]


def assert_synchronized(name=None):
    """Runtime divergence assertion: verifies every rank has executed the
    same collective call sequence up to this point.

    Snapshots this rank's :func:`collective_digest`, allgathers the
    per-rank (rank, seq, digest) triples, and raises
    :class:`DivergenceError` naming the disagreeing ranks when they
    differ. Call it at natural barriers — after the initial
    ``broadcast_parameters``, at epoch ends, before checkpointing —
    wherever all ranks are structurally in the same place. Cost: one
    24-byte allgather.

    Every rank must call it the same number of times at the same points
    (it is itself a collective); a rank-conditional ``assert_synchronized``
    is exactly the bug it exists to catch — hvd-lint flags it like any
    other collective.
    """
    seq, digest = collective_digest()
    _assert_counter[0] += 1
    op_name = name or "hvd_assert_sync.%d" % _assert_counter[0]
    # int64 transport (the core's dtype table has no uint64); the digest
    # round-trips bit-exactly through the signed view.
    mine = np.array([[_hvd.rank(), seq, digest]],
                    dtype=np.uint64).view(np.int64)
    all_rows = np.asarray(_ops.allgather(mine, op_name)).view(np.uint64)
    rows = sorted((int(r[0]), int(r[1]), int(r[2])) for r in all_rows)
    if len({(s, d) for _, s, d in rows}) <= 1:
        return
    detail = "; ".join("rank %d: seq=%d digest=%016x" % row for row in rows)
    raise DivergenceError(
        "collective call sequences diverged across ranks (%s). Some rank "
        "executed extra, missing, or reordered collectives since init — "
        "typically a rank-conditional collective or unordered name "
        "iteration; run hvd-lint on the training script (docs/LINT.md)."
        % detail)
