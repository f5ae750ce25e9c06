"""horovod_tpu — a TPU-native distributed training framework with the
capabilities of Horovod (allreduce-based data parallelism, coordinator
negotiation with tensor fusion / response cache / autotune, timeline, stall
inspection, a ``horovodrun``-style launcher) built on JAX/XLA for the TPU
data plane and a C++ host runtime for the control plane and host tensors.

Top level exposes the framework-agnostic (numpy) API; framework bindings
live in ``horovod_tpu.jax``, ``horovod_tpu.torch``, ``horovod_tpu.keras``,
``horovod_tpu.tensorflow``, ``horovod_tpu.mxnet``.
"""

import atexit as _atexit

from .common import (  # noqa: F401
    HorovodInternalError,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    broadcast,
    broadcast_async,
    get_basics,
    poll,
    reduce_scatter,
    reduce_scatter_async,
    shard_partition,
    synchronize,
)
from .groups import (  # noqa: F401
    WORLD,
    ProcessGroup,
    group_rank,
    group_size,
    new_group,
)
from . import profile  # noqa: F401

__version__ = "0.4.0"

_initialized_here = False
_world_env = None  # launcher-injected env saved before a rank-subset remap

# Callbacks invoked after every successful init() — including elastic
# re-inits. Framework bindings use this for per-generation state that must
# restart identically on every member (e.g. the jax binding's auto-name
# counter: a survivor of an elastic shrink/regrow and a freshly spawned
# worker must generate the same collective names).
_init_callbacks = []


def register_init_callback(fn):
    """Registers `fn()` to run after every successful init()."""
    _init_callbacks.append(fn)

_TOPOLOGY_KEYS = ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_LOCAL_RANK",
                  "HVD_TPU_LOCAL_SIZE", "HVD_TPU_CROSS_RANK",
                  "HVD_TPU_CROSS_SIZE", "HVD_TPU_ADDRS")


def _remap_subset_env(ranks):
    """Rewrites the HVD_TPU_* env so the native core rendezvouses over the
    `ranks` sub-communicator (members) or a size-1 self communicator
    (non-members). Reference analogue: ``hvd.init(comm=[...])``
    (`horovod/common/basics.py:29-60`, `common/mpi/mpi_context.cc:128-140`,
    where MPI_Group_incl builds the subset communicator); here the subset is
    realized by re-deriving rank/size/topology from the subset's addresses.
    Non-members become independent size-1 communicators (the reference
    falls back to MPI_COMM_WORLD with a warning, which leaves the two
    groups' collectives incompatible anyway)."""
    import os

    from .run.util import topology_env

    global _world_env
    if _world_env is None:
        _world_env = {k: os.environ.get(k) for k in _TOPOLOGY_KEYS}
    else:  # re-init with a different subset: start from the world view
        for k, v in _world_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    world_rank = int(os.environ.get("HVD_TPU_RANK", "0"))
    world_size = int(os.environ.get("HVD_TPU_SIZE", "1"))
    if len(set(ranks)) != len(ranks):
        raise ValueError("duplicate entries in ranks: %r" % (ranks,))
    for r in ranks:
        if not 0 <= r < world_size:
            raise ValueError("rank %d out of range for world size %d" %
                             (r, world_size))
    if world_rank not in ranks:
        for k in _TOPOLOGY_KEYS:
            os.environ.pop(k, None)
        os.environ["HVD_TPU_RANK"] = "0"
        os.environ["HVD_TPU_SIZE"] = "1"
        return
    addrs = (os.environ.get("HVD_TPU_ADDRS") or "").split(",")
    if len(addrs) != world_size:
        raise RuntimeError(
            "HVD_TPU_ADDRS does not cover the world; cannot form a "
            "rank-subset communicator")
    sub_addrs = [addrs[r] for r in ranks]
    os.environ.update(topology_env(list(ranks).index(world_rank), sub_addrs))


def _maybe_rendezvous():
    """Dynamic rendezvous: when the launcher supplied only
    ``HVD_TPU_RENDEZVOUS_ADDR`` (no pre-assigned ``HVD_TPU_ADDRS``), bind
    a port on this host, publish it, fetch the peer table and derive the
    topology env. Reference analogue: the Gloo HTTP rendezvous
    (`horovod/run/rendezvous/http_server.py:33-205`)."""
    import os

    if os.environ.get("HVD_TPU_ADDRS"):
        return
    rdv_addr = os.environ.get("HVD_TPU_RENDEZVOUS_ADDR")
    if not rdv_addr:
        return
    if os.environ.get("HVD_TPU_ELASTIC") == "1" and \
            "HVD_TPU_RANK" not in os.environ:
        # Elastic worker: rank/size/generation come from the driver-
        # published membership, not the spawn env (they change every
        # generation; see elastic/run.py).
        from .elastic.run import bootstrap_topology
        bootstrap_topology()
    size = int(os.environ.get("HVD_TPU_SIZE", "1"))
    if size <= 1:
        return
    if "HVD_TPU_RANK" not in os.environ:
        raise RuntimeError(
            "HVD_TPU_RENDEZVOUS_ADDR and HVD_TPU_SIZE are set but "
            "HVD_TPU_RANK is missing; the launcher must inject all three "
            "(check ssh env forwarding)")
    rank = int(os.environ["HVD_TPU_RANK"])
    timeout = float(os.environ.get("HVD_TPU_START_TIMEOUT", "60"))
    generation = int(os.environ.get("HVD_TPU_GENERATION", "0") or 0)
    from .run import rendezvous as _rdv
    os.environ.update(_rdv.resolve_topology(rank, size, rdv_addr, timeout,
                                            generation=generation))


# 2-D mesh state (docs/GROUPS.md): set by init(model_parallel=k) — this
# rank's (batch, model) groups plus the mesh shape. Re-formed on every
# (re-)init: the native group table clears per generation.
_mesh = None


@profile.phase(profile.SPAN_INIT)
def init(ranks=None, model_parallel=None):
    """Initializes the core runtime (rendezvous + background thread).

    Args:
      ranks: optional list of world ranks forming the communicator (the
        reference's ``hvd.init(comm=[0, 1])`` rank-subset form,
        ``horovod/common/basics.py:29-60``). Processes whose world rank is
        not listed initialize as independent size-1 communicators and sit
        out the subset's collectives.
      model_parallel: optional model-parallel width k (docs/GROUPS.md).
        The N ranks form a (N/k, k) (batch, model) mesh: rank r sits at
        batch row r//k and model column r%k; ``batch_group()`` is the
        rank's model-COLUMN (gradient reduction runs over it — N/k
        members) and ``model_group()`` its contiguous k-rank model row
        (tensor-parallel collectives ride it). Persists through elastic
        re-inits via ``HVD_TPU_MODEL_PARALLEL`` (the env form sets it
        job-wide without a code change).

    Reference analogue: ``hvd.init()`` -> ``horovod/common/basics.py:29-60``.
    """
    import os as _os

    global _initialized_here, _world_env, _mesh
    if not is_initialized():
        _maybe_rendezvous()
    if ranks is not None and len(ranks) > 0:
        _remap_subset_env(ranks)
    elif _world_env is not None:
        # A previous init(ranks=...) remapped the env; a plain init() must
        # see the original world topology again, not the stale subset.
        import os
        for k, v in _world_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _world_env = None
    get_basics().init()
    # The native listener has bound; drop any rendezvous port
    # reservation held across init (see rendezvous.reserve_port).
    from .run.rendezvous import release_held_ports
    release_held_ports()
    for cb in _init_callbacks:
        cb()
    # Mesh formation AFTER the callbacks (groups are per-generation; the
    # native table was cleared by the (re-)init). The env is only
    # persisted AFTER validation against the live world size, so an
    # invalid model_parallel= raises without poisoning later init()
    # retries.
    _mesh = None
    mp = int(model_parallel) if model_parallel is not None else \
        int(_os.environ.get("HVD_TPU_MODEL_PARALLEL", "1") or "1")
    if mp > 1:
        _mesh = _form_mesh(mp, explicit=model_parallel is not None)
    if model_parallel is not None:
        # Persist so elastic re-inits (plain init() calls) re-form the
        # mesh for the new membership.
        _os.environ["HVD_TPU_MODEL_PARALLEL"] = str(mp)
    # Metrics endpoint (docs/METRICS.md): serve Prometheus at
    # HVD_TPU_METRICS_PORT + rank. After the callbacks (rank may have
    # changed across an elastic re-init; the server follows its slot).
    from . import _metrics
    _metrics.on_init()
    if not _initialized_here:
        _atexit.register(shutdown)
        _initialized_here = True


def _form_mesh(k, explicit=True):
    """Registers the (batch, model) mesh groups on THIS rank (every rank
    runs the identical sequence, so ids agree; docs/GROUPS.md).

    Megatron-style layout: model groups are k CONSECUTIVE ranks (the
    fastest-moving axis — on a TPU slice, launcher-ordered neighbors
    share ICI links), batch groups are the strided columns {j, j+k, ...}.
    Registration order: all k batch groups (column 0..k-1), then all N/k
    model groups (row 0..N/k-1).
    """
    n = size()
    if n % k != 0:
        if explicit:
            raise ValueError(
                "model_parallel=%d does not divide world size %d"
                % (k, n))
        # Env-driven re-form (an elastic re-init): the model is SHARDED
        # k ways, so a membership whose size k does not divide cannot
        # host it — name the resume constraint instead of a bare
        # divisibility error mid-recovery.
        raise RuntimeError(
            "elastic membership of size %d cannot resume the "
            "model_parallel=%d mesh (size must be a multiple of k — "
            "the model is sharded k ways); resize to a multiple of %d, "
            "or unset HVD_TPU_MODEL_PARALLEL for a fresh pure-DP job "
            "(docs/GROUPS.md)" % (n, k, k))
    batch_groups = [new_group(range(j, n, k)) for j in range(k)]
    model_groups = [new_group(range(i * k, (i + 1) * k))
                    for i in range(n // k)]
    r = rank()
    return {
        "k": k,
        "batch": batch_groups[r % k],
        "model": model_groups[r // k],
        "batch_groups": batch_groups,
        "model_groups": model_groups,
    }


def model_parallel_size():
    """The mesh's model-parallel width k (1 = pure data-parallel)."""
    return _mesh["k"] if _mesh is not None else 1


def batch_group():
    """This rank's batch-axis (data-parallel) group: the N/k ranks
    holding the same model shard. Gradient allreduces run over it —
    ``DistributedOptimizer`` defaults to it when the mesh is active.
    None without ``init(model_parallel=k)``."""
    return _mesh["batch"] if _mesh is not None else None


def model_group():
    """This rank's model-axis (tensor-parallel) group: the k ranks
    forming one model replica. ``parallel.tensor_parallel``'s host-plane
    f/g collectives ride it. None without ``init(model_parallel=k)``."""
    return _mesh["model"] if _mesh is not None else None


def mesh_groups():
    """(batch_group, model_group) for this rank, or (None, None)."""
    return (batch_group(), model_group())


def shutdown():
    """Coordinated shutdown of the core runtime."""
    get_basics().shutdown()
    from . import _metrics
    _metrics.stop_server()


def metrics():
    """This worker's live metrics registry (native/metrics.h) as a
    dict: monotonic counters (cycles, tensors/bytes executed, fusion,
    cache hit/miss, stall warnings, divergence errors), gauges (queue
    depth, generation), and fixed-bucket histograms (cycle duration,
    negotiation latency, tensors/bytes per cycle, fusion fill). See
    docs/METRICS.md for the catalog."""
    from . import _metrics
    return _metrics.metrics()


def job_metrics():
    """Rank 0 only: the job-wide view — every rank's piggybacked
    summary plus the per-rank announce-lag table (the straggler
    signal). Empty dict on other ranks."""
    from . import _metrics
    return _metrics.job_metrics()


def autotune():
    """Live closed-loop tuner state (docs/AUTOTUNE.md) as a dict:
    ``active``, ``rearm_epoch``/``rearms_total``, sample count, best
    score, the synchronized knob values under ``params`` (fusion_mb,
    cycle_time_ms, pipeline_chunk_kb, cache_enabled, the three
    hierarchical toggles), which knobs env pinned under ``fixed``, the
    observed workload ``profile``, and the converged drift ``baseline``.
    Callable any time from any thread."""
    import json as _json
    return _json.loads(get_basics().autotune_json())


def is_initialized():
    return get_basics().initialized()


def rank():
    return get_basics().rank()


def local_rank():
    return get_basics().local_rank()


def cross_rank():
    return get_basics().cross_rank()


def size():
    return get_basics().size()


def local_size():
    return get_basics().local_size()


def cross_size():
    return get_basics().cross_size()


def is_homogeneous():
    return get_basics().is_homogeneous()


def tcp_built():
    return get_basics().tcp_built()


def cpu_ops_built():
    return get_basics().cpu_ops_built()


# Reference-named capability probes (horovod/common/basics.py:117-191),
# for drop-in migration: the TCP controller fills the gloo role here;
# MPI/NCCL/DDL/MLSL backends do not exist in the TPU redesign (ICI
# collectives live inside XLA programs instead — see docs/DESIGN.md).

def mpi_threads_supported():
    return False


def mpi_enabled():
    return False


def mpi_built():
    return False


def gloo_enabled():
    """True: the TCP rendezvous/controller provides the gloo-role
    host data plane."""
    return tcp_built()


def gloo_built():
    return tcp_built()


def nccl_built():
    return False


def ddl_built():
    return False


def mlsl_built():
    return False
