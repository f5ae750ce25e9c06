"""ctypes binding to the native core runtime (libhorovod_tpu.so).

Capability parity with the reference ``horovod/common/basics.py:22-197``
(HorovodBasics): process-wide init/shutdown/rank/size queries and build
probes, plus the handle-based enqueue/wait surface the collective wrappers
use (reference analogue: the torch binding's handle manager,
``horovod/torch/mpi_ops.py:58-90``).
"""

import ctypes
import fcntl
import os
import subprocess
import threading

import numpy as np

from .. import profile

_MOD_DIR = os.path.dirname(os.path.abspath(__file__))
# HVD_TPU_NATIVE_DIR points at an alternate build of the core (e.g. a
# `make SANITIZE=thread` TSAN build, or a system-installed location).
_NATIVE_DIR = os.environ.get(
    "HVD_TPU_NATIVE_DIR", os.path.join(_MOD_DIR, "..", "native"))
_LIB_PATH = os.path.join(_NATIVE_DIR, "libhorovod_tpu.so")
_build_lock = threading.Lock()


def _ensure_built():
    """Brings the native core up to date with its sources before every
    first load. The .so is git-ignored but copied with the tree, so one
    that merely exists may be older than the sources beside it: always
    go through ``make`` (a no-op when fresh — the Makefile tracks header
    dependencies). A directory without a Makefile is a prebuilt
    alternate core (``HVD_TPU_NATIVE_DIR``, e.g. the sanitizer builds)
    and is loaded as it is.

    Launcher-spawned worker processes hit this concurrently on a fresh
    checkout, so an inter-process flock serializes the build (the
    threading.Lock only covers threads within one process)."""
    if not os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
        return
    with profile.phase(profile.SPAN_NATIVE_BUILD), _build_lock:
        lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
        with open(lock_path, "w") as lock_file:
            fcntl.flock(lock_file, fcntl.LOCK_EX)
            try:
                subprocess.run(["make", "-j", str(os.cpu_count() or 4)],
                               cwd=_NATIVE_DIR, check=True,
                               stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    "failed to build libhorovod_tpu.so:\n" +
                    e.stdout.decode("utf-8", "replace")) from e
            finally:
                fcntl.flock(lock_file, fcntl.LOCK_UN)

# DataType enum values must match native/message.h.
_NUMPY_TO_DTYPE = {
    np.dtype(np.uint8): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.uint16): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.float16): 6,
    np.dtype(np.float32): 7,
    np.dtype(np.float64): 8,
    np.dtype(np.bool_): 9,
}

_DTYPE_TO_NUMPY = {v: k for k, v in _NUMPY_TO_DTYPE.items()}

HVD_BFLOAT16 = 10

try:  # ml_dtypes ships with jax; bfloat16 is the native TPU 16-bit format.
    import ml_dtypes

    _NUMPY_TO_DTYPE[np.dtype(ml_dtypes.bfloat16)] = HVD_BFLOAT16
    _DTYPE_TO_NUMPY[HVD_BFLOAT16] = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    pass


def numpy_to_hvd_dtype(dtype):
    dt = np.dtype(dtype)
    if dt not in _NUMPY_TO_DTYPE:
        raise ValueError("Unsupported dtype for horovod_tpu collective: %s"
                         % dt)
    return _NUMPY_TO_DTYPE[dt]


class HorovodBasics:
    """Wraps the extern "C" API exported by the native core."""

    def __init__(self, lib_path=_LIB_PATH):
        _ensure_built()
        self.lib = ctypes.CDLL(os.path.abspath(lib_path),
                               mode=ctypes.RTLD_GLOBAL)
        lib = self.lib
        lib.horovod_tpu_init.restype = ctypes.c_int
        for fn in ("horovod_tpu_rank", "horovod_tpu_local_rank",
                   "horovod_tpu_cross_rank", "horovod_tpu_size",
                   "horovod_tpu_local_size", "horovod_tpu_cross_size",
                   "horovod_tpu_initialized", "horovod_tpu_is_homogeneous",
                   "horovod_tpu_connection_lost",
                   "horovod_tpu_tcp_built", "horovod_tpu_cpu_ops_built"):
            getattr(lib, fn).restype = ctypes.c_int
        lib.horovod_tpu_enqueue_allreduce.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_allreduce.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_int,
        ]
        lib.horovod_tpu_enqueue_reduce_scatter.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_reduce_scatter.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_int,
        ]
        # Process groups (docs/GROUPS.md): registry + group-scoped
        # enqueue variants (the plain entry points stay group-0 so older
        # bindings keep their signatures).
        lib.horovod_tpu_new_group.restype = ctypes.c_int
        lib.horovod_tpu_new_group.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
        lib.horovod_tpu_group_size.restype = ctypes.c_int
        lib.horovod_tpu_group_size.argtypes = [ctypes.c_int]
        lib.horovod_tpu_group_rank.restype = ctypes.c_int
        lib.horovod_tpu_group_rank.argtypes = [ctypes.c_int]
        lib.horovod_tpu_group_count.restype = ctypes.c_int
        lib.horovod_tpu_group_count.argtypes = []
        lib.horovod_tpu_enqueue_allreduce_grp.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_allreduce_grp.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_int, ctypes.c_int,
        ]
        lib.horovod_tpu_enqueue_reduce_scatter_grp.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_reduce_scatter_grp.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_int, ctypes.c_int,
        ]
        lib.horovod_tpu_enqueue_allgather_grp.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_allgather_grp.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
        ]
        lib.horovod_tpu_enqueue_broadcast_grp.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_broadcast_grp.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.horovod_tpu_sharded_update_default.restype = ctypes.c_int
        lib.horovod_tpu_sharded_update_default.argtypes = []
        lib.horovod_tpu_opt_state_metrics.restype = None
        lib.horovod_tpu_opt_state_metrics.argtypes = [ctypes.c_int64]
        lib.horovod_tpu_parse_compression.restype = ctypes.c_int
        lib.horovod_tpu_parse_compression.argtypes = [ctypes.c_char_p]
        lib.horovod_tpu_effective_compression.restype = ctypes.c_int
        lib.horovod_tpu_effective_compression.argtypes = [ctypes.c_int,
                                                          ctypes.c_int]
        lib.horovod_tpu_compressed_size.restype = ctypes.c_int64
        lib.horovod_tpu_compressed_size.argtypes = [ctypes.c_int64,
                                                    ctypes.c_int]
        lib.horovod_tpu_enqueue_allgather.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_allgather.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        lib.horovod_tpu_enqueue_broadcast.restype = ctypes.c_int
        lib.horovod_tpu_enqueue_broadcast.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
        ]
        lib.horovod_tpu_poll.restype = ctypes.c_int
        lib.horovod_tpu_poll.argtypes = [ctypes.c_int]
        lib.horovod_tpu_wait.restype = ctypes.c_int
        lib.horovod_tpu_wait.argtypes = [ctypes.c_int]
        lib.horovod_tpu_error_string.restype = ctypes.c_char_p
        lib.horovod_tpu_error_string.argtypes = [ctypes.c_int]
        lib.horovod_tpu_allgather_bytes.restype = ctypes.c_int64
        lib.horovod_tpu_allgather_bytes.argtypes = [ctypes.c_int]
        lib.horovod_tpu_allgather_rank_dim.restype = ctypes.c_int64
        lib.horovod_tpu_allgather_rank_dim.argtypes = [ctypes.c_int,
                                                       ctypes.c_int]
        lib.horovod_tpu_allgather_copy.restype = ctypes.c_int
        lib.horovod_tpu_allgather_copy.argtypes = [ctypes.c_int,
                                                   ctypes.c_void_p]
        lib.horovod_tpu_allgather_data.restype = ctypes.c_void_p
        lib.horovod_tpu_allgather_data.argtypes = [ctypes.c_int]
        lib.horovod_tpu_release.argtypes = [ctypes.c_int]
        lib.horovod_tpu_perf_counters.restype = None
        lib.horovod_tpu_perf_counters.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        lib.horovod_tpu_effective_fusion_threshold.restype = ctypes.c_int64
        lib.horovod_tpu_protocol_counters.restype = None
        lib.horovod_tpu_protocol_counters.argtypes = [
            ctypes.POINTER(ctypes.c_uint64)]
        lib.horovod_tpu_protocol_counters_reset.restype = None
        lib.horovod_tpu_protocol_counters_reset.argtypes = []
        lib.horovod_tpu_call_digest.restype = None
        lib.horovod_tpu_call_digest.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
        lib.horovod_tpu_metrics_json.restype = ctypes.c_char_p
        lib.horovod_tpu_metrics_json.argtypes = []
        lib.horovod_tpu_crc32c.restype = ctypes.c_uint32
        lib.horovod_tpu_crc32c.argtypes = [ctypes.c_char_p,
                                           ctypes.c_uint64]
        lib.horovod_tpu_crc32c_extend.restype = ctypes.c_uint32
        lib.horovod_tpu_crc32c_extend.argtypes = [
            ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint64]
        lib.horovod_tpu_ckpt_metrics.restype = None
        lib.horovod_tpu_ckpt_metrics.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_double]
        lib.horovod_tpu_drain_metrics.restype = None
        lib.horovod_tpu_drain_metrics.argtypes = [
            ctypes.c_int64, ctypes.c_int64]
        lib.horovod_tpu_job_metrics_json.restype = ctypes.c_char_p
        lib.horovod_tpu_job_metrics_json.argtypes = []
        lib.horovod_tpu_autotune_params.restype = None
        lib.horovod_tpu_autotune_params.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        # Optional in older cores (a stale HVD_TPU_NATIVE_DIR build):
        # the binding degrades to autotune_params-only introspection
        # instead of failing every import.
        try:
            lib.horovod_tpu_autotune_json.restype = ctypes.c_char_p
            lib.horovod_tpu_autotune_json.argtypes = []
            self._has_autotune_json = True
        except AttributeError:
            self._has_autotune_json = False
        # Distributed tracing (native/trace.h, docs/TRACING.md) — also
        # optional, same stale-build tolerance.
        try:
            lib.horovod_tpu_trace_now_ns.restype = ctypes.c_int64
            lib.horovod_tpu_trace_now_ns.argtypes = []
            lib.horovod_tpu_trace_record.restype = None
            lib.horovod_tpu_trace_record.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
            lib.horovod_tpu_trace_dump_bundle.restype = ctypes.c_char_p
            lib.horovod_tpu_trace_dump_bundle.argtypes = [ctypes.c_char_p]
            lib.horovod_tpu_trace_counters.restype = None
            lib.horovod_tpu_trace_counters.argtypes = [
                ctypes.POINTER(ctypes.c_uint64)]
            self._has_trace = True
        except AttributeError:
            self._has_trace = False

    # -- lifecycle ---------------------------------------------------------
    def init(self):
        with profile.phase(profile.SPAN_NATIVE_INIT):
            ok = self.lib.horovod_tpu_init()
        if not ok:
            raise RuntimeError(
                "horovod_tpu initialization failed (rendezvous error?). "
                "Check HVD_TPU_ADDRS / HVD_TPU_RANK / HVD_TPU_SIZE.")

    def shutdown(self):
        self.lib.horovod_tpu_shutdown()

    def initialized(self):
        return bool(self.lib.horovod_tpu_initialized())

    def connection_lost(self):
        """True when the background loop died because a peer connection
        was lost (elastic-recoverable), not a requested shutdown."""
        return bool(self.lib.horovod_tpu_connection_lost())

    def perf_counters(self):
        """(responses_performed, tensors_performed) — fusion
        diagnostics: equal counts mean no tensor shared a response."""
        responses = ctypes.c_int64()
        tensors = ctypes.c_int64()
        self.lib.horovod_tpu_perf_counters(ctypes.byref(responses),
                                           ctypes.byref(tensors))
        return responses.value, tensors.value

    def effective_fusion_threshold(self):
        """The controller's working fusion threshold in bytes, after
        hierarchical divisibility rounding; -1 before init."""
        return self.lib.horovod_tpu_effective_fusion_threshold()

    def protocol_counters(self):
        """Control-plane negotiation accounting for THIS rank: dict of
        ctrl_bytes_sent / ctrl_bytes_recv (12-byte frame headers
        included, data-plane ring traffic excluded), ctrl_msgs, and
        cycles_fast / cycles_full — both counting WORK cycles only
        (idle heartbeat cycles are excluded from cycle counts, but
        their control bytes DO accrue with wall time; keep cycle
        pacing at its default when byte-per-op numbers matter)."""
        out = (ctypes.c_uint64 * 5)()
        self.lib.horovod_tpu_protocol_counters(out)
        return {
            "ctrl_bytes_sent": out[0],
            "ctrl_bytes_recv": out[1],
            "ctrl_msgs": out[2],
            "cycles_fast": out[3],
            "cycles_full": out[4],
        }

    def protocol_counters_reset(self):
        self.lib.horovod_tpu_protocol_counters_reset()

    def call_digest(self):
        """(seq, digest) of this rank's collective call sequence since
        init: seq counts enqueued collectives, digest is a rolling
        FNV-1a over each call's (op, dtype, shape-rank, name). Ranks
        that executed identical call sequences report identical values
        (the runtime divergence assertion compares them)."""
        seq = ctypes.c_uint64()
        digest = ctypes.c_uint64()
        self.lib.horovod_tpu_call_digest(ctypes.byref(seq),
                                         ctypes.byref(digest))
        return seq.value, digest.value

    def metrics_json(self):
        """This worker's live metrics registry snapshot (counters /
        gauges / histograms / rank-lag tables) as a JSON string —
        native/metrics.h, rendered by horovod_tpu._metrics. Callable
        any time from any thread (the registry is process-global
        atomics)."""
        return self.lib.horovod_tpu_metrics_json().decode("utf-8")

    def job_metrics_json(self):
        """Rank 0's job-wide view as JSON: every rank's piggybacked
        summary, summary staleness, and the per-rank announce-lag
        table (straggler signal). "{}" on non-coordinator ranks."""
        return self.lib.horovod_tpu_job_metrics_json().decode("utf-8")

    def crc32c(self, data, crc=0):
        """CRC32C (Castagnoli) over `data` via the native slicing-by-8
        implementation (the transport frame checksum, native/checksum) —
        chained from `crc` for incremental use. The durable checkpoint
        writer checksums every shard and manifest through this."""
        buf = bytes(data)
        return int(self.lib.horovod_tpu_crc32c_extend(
            ctypes.c_uint32(crc), buf, len(buf))) if crc else \
            int(self.lib.horovod_tpu_crc32c(buf, len(buf)))

    def ckpt_metrics(self, writes=0, failures=0, nbytes=0, restores=0,
                     restore_failures=0, last_step=-1,
                     write_seconds=-1.0):
        """Reports durable-checkpoint accounting into the native
        registry (deltas; last_step absolute with <0 = skip;
        write_seconds one histogram observation with <0 = skip)."""
        self.lib.horovod_tpu_ckpt_metrics(
            int(writes), int(failures), int(nbytes), int(restores),
            int(restore_failures), int(last_step), float(write_seconds))

    def sharded_update_default(self):
        """The HVD_TPU_SHARDED_UPDATE job default (docs/ZERO.md)."""
        return bool(self.lib.horovod_tpu_sharded_update_default())

    def opt_state_metrics(self, nbytes):
        """Reports this rank's optimizer-state byte count into the
        native opt_state_bytes gauge (docs/ZERO.md; < 0 = skip)."""
        self.lib.horovod_tpu_opt_state_metrics(int(nbytes))

    def drain_metrics(self, requested=0, draining=-2):
        """Reports graceful-drain accounting into the native registry
        (docs/FLEET.md): `requested` is a counter delta; `draining` the
        absolute posture gauge (1 = victim of the current drain epoch,
        0 = survivor, -1 = reset; < -1 = leave unchanged)."""
        self.lib.horovod_tpu_drain_metrics(int(requested), int(draining))

    def compressed_size(self, count, mode):
        """Wire bytes `count` f32 elements occupy under compression
        mode `mode` (native/compression.cc layout)."""
        return int(self.lib.horovod_tpu_compressed_size(
            int(count), int(mode)))

    def effective_compression(self, mode, dtype):
        """The mode a payload of native dtype id `dtype` actually rides
        the wire with (non-f32 degrades to 0 = none)."""
        return int(self.lib.horovod_tpu_effective_compression(
            int(mode), int(dtype)))

    def autotune_json(self):
        """The full live closed-loop tuner state (docs/AUTOTUNE.md) as a
        JSON string: knobs (incl. pipeline_chunk_kb and
        hierarchical_reduce_scatter), fixed flags, workload profile,
        re-arm epoch/counters, and the convergence baseline the drift
        watch compares against. Callable any time from any thread."""
        if not self._has_autotune_json:
            # Keep the documented hvd.autotune() schema stable: knobs
            # under "params", closed-loop state zeroed (the old core
            # has no re-arm machinery to report).
            import json
            p = self.autotune_params()
            return json.dumps({
                "active": p.pop("active"),
                "rearm_epoch": 0, "rearms_total": 0, "samples": 0,
                "best_score_bytes_per_us": 0.0, "last_rearm_reason": "",
                "params": p, "fixed": {}, "profile": {}, "baseline": {},
            })
        return self.lib.horovod_tpu_autotune_json().decode("utf-8")

    def autotune_params(self):
        """Current synchronized knob values (autotune introspection):
        dict with fusion_mb, cycle_time_ms, cache_enabled,
        hierarchical_allreduce, hierarchical_allgather, active."""
        fusion = ctypes.c_double()
        cycle = ctypes.c_double()
        cache = ctypes.c_int()
        har = ctypes.c_int()
        hag = ctypes.c_int()
        active = ctypes.c_int()
        self.lib.horovod_tpu_autotune_params(
            ctypes.byref(fusion), ctypes.byref(cycle), ctypes.byref(cache),
            ctypes.byref(har), ctypes.byref(hag), ctypes.byref(active))
        return {"fusion_mb": fusion.value, "cycle_time_ms": cycle.value,
                "cache_enabled": bool(cache.value),
                "hierarchical_allreduce": bool(har.value),
                "hierarchical_allgather": bool(hag.value),
                "active": bool(active.value)}

    # -- distributed tracing (docs/TRACING.md) -----------------------------
    def trace_now_ns(self):
        """Monotonic trace-clock ns on the native recorder's per-process
        epoch; 0 on a pre-trace core build."""
        if not self._has_trace:
            return 0
        return int(self.lib.horovod_tpu_trace_now_ns())

    def trace_record(self, name, phase, start_ns, end_ns, nbytes=0,
                     group=0):
        """Records one span into the native trace ring (no-op before
        init, with HVD_TPU_TRACE=0, or on a pre-trace core). `phase`
        takes the wire values from native/trace.h (8 = request)."""
        if not self._has_trace:
            return
        self.lib.horovod_tpu_trace_record(
            name.encode("utf-8"), int(phase), int(start_ns), int(end_ns),
            int(nbytes), int(group))

    def trace_dump_bundle(self, reason="manual"):
        """Forces a flight-recorder bundle dump; returns the bundle path
        or "" when HVD_TPU_BUNDLE_DIR is unset, the per-process cap is
        hit, or the core predates tracing."""
        if not self._has_trace:
            return ""
        out = self.lib.horovod_tpu_trace_dump_bundle(
            reason.encode("utf-8"))
        return out.decode("utf-8") if out else ""

    def trace_counters(self):
        """Dict of trace_spans_total / trace_spans_dropped_total /
        bundles_written_total (all zero on a pre-trace core)."""
        if not self._has_trace:
            return {"trace_spans_total": 0, "trace_spans_dropped_total": 0,
                    "bundles_written_total": 0}
        out = (ctypes.c_uint64 * 3)()
        self.lib.horovod_tpu_trace_counters(out)
        return {"trace_spans_total": int(out[0]),
                "trace_spans_dropped_total": int(out[1]),
                "bundles_written_total": int(out[2])}

    # -- topology ----------------------------------------------------------
    def rank(self):
        return self._query("horovod_tpu_rank")

    def local_rank(self):
        return self._query("horovod_tpu_local_rank")

    def cross_rank(self):
        return self._query("horovod_tpu_cross_rank")

    def size(self):
        return self._query("horovod_tpu_size")

    def local_size(self):
        return self._query("horovod_tpu_local_size")

    def cross_size(self):
        return self._query("horovod_tpu_cross_size")

    def is_homogeneous(self):
        return bool(self.lib.horovod_tpu_is_homogeneous())

    def _query(self, fn):
        value = getattr(self.lib, fn)()
        if value == -1:
            raise ValueError(
                "Horovod-TPU has not been initialized; call hvd.init() first.")
        return value

    # -- build probes ------------------------------------------------------
    def tcp_built(self):
        return bool(self.lib.horovod_tpu_tcp_built())

    def cpu_ops_built(self):
        return bool(self.lib.horovod_tpu_cpu_ops_built())


_basics = None


def get_basics():
    global _basics
    if _basics is None:
        _basics = HorovodBasics()
    return _basics
