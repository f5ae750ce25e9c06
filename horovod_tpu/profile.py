"""The profiler control of the in-`jit` train step, and the names the
program gives its own work (`hvd.profile`; docs/TRACING.md, "The in-`jit`
step").

The train step is one XLA program: its `psum` never enters the native core,
so `hvd-trace`, the timeline and the metrics registry read zero on it. What
shows where a step's device time goes is the runtime's profiler
(`jax.profiler`), and it can tell the step's phases and kernels apart only
by the names the program gives them. Both are here:

- the names: `jax.named_scope`s around the phases of the step
  (`PHASE_SCOPES`) and the parts of the models (`MODEL_SCOPES`), and the
  `name=` of every Pallas kernel (`KERNELS`). They are compile-time
  metadata: the optimized program is the same with or without them, and no
  Python runs for them on the per-step path.
- the control: `start(logdir)` / `stop()` switch the profiler on and off in
  the running process that holds the chip, any number of times;
  `span(name)` is what host code puts around its own per-call work, on
  the profiler's clock (the clock of the device planes), and is one shared
  no-op context while no trace is active.
- getting going: `phase(name)` is what host code puts around work a process
  does a bounded number of times (`hvd.init()`, building and placing a
  step), and the runtime's own trace, lowering and compile-or-load of every
  jitted function arrive through jax's monitoring hooks (`SETUP_SPANS`).
  Both are kept in memory, always, on the clock of the profiler's host
  plane: `phases()` is the record, `compiles()` what it says per function
  (which one recompiled, and when), `dropped()` what the cap turned away.

Nothing here reads the environment, and importing this module imports
nothing, starts nothing and touches no device: jax is imported by the calls
that need it, so `import horovod_tpu` stays free of it.
"""

import contextlib
import glob
import os
import re
import threading
import time

# Phases of `parallel.make_train_step`'s program. Every device operation of
# a step lies under exactly one of them (the outermost on its scope path).
FWD_BWD = "hvd_fwd_bwd"            # loss_fn forward and backward
GRAD_SYNC = "hvd_grad_sync"        # collectives on gradients, casts, divide
OPTIMIZER = "hvd_optimizer"        # optimizer maths and the parameter write
PARAM_GATHER = "hvd_param_gather"  # zero1: all_gather of the new shards
PHASE_SCOPES = (FWD_BWD, GRAD_SYNC, OPTIMIZER, PARAM_GATHER)

# Parts of the models, inside FWD_BWD. A backward operation carries the
# scope of its forward inside `transpose(jvp(...))`.
EMBED = "hvd_embed"
BLOCK = "hvd_block"    # a Transformer block; flax's `attn` and `mlp` inside
STEM = "hvd_stem"
STAGES = ("hvd_stage1", "hvd_stage2", "hvd_stage3", "hvd_stage4")
HEAD = "hvd_head"
LOSS = "hvd_loss"
MODEL_SCOPES = (EMBED, BLOCK, STEM) + STAGES + (HEAD, LOSS)

# The looped stack (`models/transformer.py`, `num_passes` > 1), inside
# FWD_BWD: `LOOP` around all the passes with `LOOP/pass_<t>` (t from 1)
# around each, the blocks' own scopes beneath; `EXIT` around the exit
# gate, the exit distribution, its entropy and the weights it gives the
# loss (`ops/losses.py::expected_exit_loss`). Neither is in MODEL_SCOPES:
# a block under a pass is still told apart as `hvd_block/attn`.
LOOP = "hvd_loop"
LOOP_PASS = "pass_%d"
EXIT = "hvd_exit"

# The routed feed-forward (`parallel/expert.py::MoeMlp`), inside a block's
# `mlp` half: `MOE` around all of it, the four others inside `MOE`.
MOE = "hvd_moe"
MOE_ROUTE = "hvd_moe_route"        # router matmul, softmax, top-k, losses
MOE_DISPATCH = "hvd_moe_dispatch"  # sort, group sizes, gather of the rows
MOE_EXPERTS = "hvd_moe_experts"    # the grouped matmuls and the gate
MOE_COMBINE = "hvd_moe_combine"    # gather back, weights, sum over choices
MOE_SHARED = "hvd_moe_shared"      # the always-on expert beside the routed
# The two shared projections of a layer whose experts run in a latent
# (`MoeMlp` with ``latent_dim``): state -> latent before the dispatch,
# latent -> state after the combine.
MOE_LATENT = "hvd_moe_latent"
MOE_SCOPES = (MOE, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
              MOE_SHARED, MOE_LATENT)

# A Mamba-2 mixer (`models/transformer.py::Mamba2`, a layer of kind "ssm"
# in `layer_types`), inside `BLOCK`: `SSM` around all of it, `SSM_CONV`
# around the causal depthwise convolution and `SSD` around the chunked scan
# (`ops/ssd.py`: the products inside a chunk, the chunk states, the carry)
# inside. Not in MODEL_SCOPES: the layer reads as `hvd_block` in the
# by-scope table.
SSM = "hvd_ssm"
SSM_CONV = "hvd_ssm_conv"
SSD = "hvd_ssd"
# What the mixer does outside its convolution and its scan, inside `SSM` and
# opened in `Mamba2.__call__` itself, beside the two above and never around
# them: `SSM_PROJ` around `in_proj` and `out_proj`; `SSM_GATE` around the
# elementwise part in f32 (dt's bias and softplus, `a`, the skip, `y *
# silu(z)`, the grouped mean square and `rsqrt`, the `norm` scale, the cast
# before `out_proj`). What is left under `SSM` alone is the slices of the
# in-projection's output and the reshapes.
SSM_PROJ = "hvd_ssm_proj"
SSM_GATE = "hvd_ssm_gate"
SSM_SCOPES = (SSM, SSM_CONV, SSD, SSM_PROJ, SSM_GATE)

# A Kimi Delta Attention mixer (`models/transformer.py::KimiDeltaAttention`,
# a layer of kind "kda" in `attention_types`), inside `BLOCK` and around the
# attention half of the two-branch block as `ATTN_FULL` is around a "full"
# layer's: `KDA` around all of it (the norm before it and the residual add
# too), and opened in the mixer itself `KDA_PROJ` around its projections (the
# in-projection of q, k, v, the two low ranks' down sides and beta; the two
# up sides; the output projection), `KDA_CONV` around the causal depthwise
# convolutions with their SiLU and, since PR 64, the l2 norms of q and k
# (`ops/kda_conv.py`: the kernels `KDA_QKV` / `KDA_QKV_BWD` below, or jnp),
# `KDA_GATE` around the rest of the elementwise part in f32 (the decay's
# softplus, beta's sigmoid, the gated head norm), and in
# `ops/kda.py` `KDA_CHUNK` around what is made for all chunks at once (the
# cumulative decays, the decayed scores, the solve, W and U: the kernels
# `KDA_WY` / `KDA_WY_BWD` below, or jnp around `KDA_SCORES` /
# `KDA_SCORES_BWD`) and `KDA_CARRY` around the scan over the chunks (the
# kernels `KDA_SCAN` / `KDA_SCAN_BWD` below, or a `lax.scan`). Not in
# MODEL_SCOPES.
KDA = "hvd_kda"
KDA_PROJ = "hvd_kda_proj"
KDA_CONV = "hvd_kda_conv"
KDA_GATE = "hvd_kda_gate"
KDA_CHUNK = "hvd_kda_chunk"
KDA_CARRY = "hvd_kda_carry"
KDA_SCOPES = (KDA, KDA_PROJ, KDA_CONV, KDA_GATE, KDA_CHUNK, KDA_CARRY)

# A double-gated short convolution mixer (`models/transformer.py::
# GatedShortConv`, a layer of kind "conv" in `attention_types`; LFM2), inside
# `BLOCK` and around the attention half of the two-branch block as `KDA` is
# around a "kda" layer's: `SCONV` around all of it (the norm before it and
# the residual add too), and opened in the mixer itself `SCONV_PROJ` around
# its two projections (`in_proj` to the three column blocks B | G | z,
# `out_proj`) and `SCONV_GATE` around the pass between them
# (`ops/sconv.py::gated_conv`: u = B * z, the causal taps, G * c, in f32 to
# one rounding; both directions). What is left under `SCONV` alone is the
# norm and the add. A tuple of its own: in NEITHER `KDA_SCOPES`,
# `SSM_SCOPES`, `ATTN_PARTS` nor `ATTN_KINDS`, which readers of older cells
# walk. Not in MODEL_SCOPES: the half still reads `hvd_block/attn`.
SCONV = "hvd_sconv"
SCONV_PROJ = "hvd_sconv_proj"
SCONV_GATE = "hvd_sconv_gate"
SCONV_SCOPES = (SCONV, SCONV_PROJ, SCONV_GATE)
# Under `SCONV`, in the backward alone: the in-projection's data gradient
# held as a value of the program between the product and the norm's backward
# (`models/transformer.py::_hold_cotangent`, an `optimization_barrier`: no
# instruction of its own in a trace, so no time). Not in `SCONV_SCOPES`, which
# the benchmark's readers walk: the norm's backward, a pass of its own behind
# it, counts under `SCONV` alone as the norm's forward does.
SCONV_HOLD = "hvd_sconv_hold"

# The hyper-connection around each of a block's two branches
# (`models/transformer.py`, `hc_mult` > 1), inside `BLOCK` and beside the
# branches' own `attn` / `mlp`: `HC` around all of it, `HC_MAP` (the norm
# over the streams, the projection, the Sinkhorn iterations) and `HC_MIX`
# (reading the branch's input from the streams; writing the mixed streams
# and the branch's output back) inside. `MTP` is around the multi-token
# prediction module: its embedding, two norms, projection, block and final
# norm. Like `LOOP`, neither is in MODEL_SCOPES: a block under `MTP` is
# still told apart as `hvd_block/attn`.
HC = "hvd_hc"
HC_MAP = "hvd_hc_map"
HC_MIX = "hvd_hc_mix"
HC_SCOPES = (HC, HC_MAP, HC_MIX)
MTP = "hvd_mtp"

# The attention half of a two-branch block whose layer has a kind
# (`models/transformer.py`, `attention_types`), inside `BLOCK` and around
# flax's `attn`: the norm before it, the projections, the per-head norms,
# the kind's rotation, its flash kernels (under the band, or causal), the
# output projection and the residual add. The two kinds run kernels of one
# name and one shape; the scope tells them apart. Not in MODEL_SCOPES: the
# half still reads as `hvd_block/attn`.
ATTN_WINDOW = "hvd_attn_window"
ATTN_FULL = "hvd_attn_full"
ATTN_KINDS = {"window": ATTN_WINDOW, "full": ATTN_FULL}

# What an attention module does outside its kernels, inside flax's `attn`
# and opened in `Attention.__call__` / `LatentAttention.__call__` themselves
# (so a block under a pass, under `MTP`, inside a hyper-connection or under a
# kind has them): `ATTN_PROJ` around every projection into heads and out of
# them (`query`, `key`, `value`, `out`; latent attention's `q_a`, `q_b`,
# `kv_a`, `kv_b`, `out`), `ATTN_NORM` around the norms (`q_norm`, `k_norm`
# per head or whole; latent attention's `q_norm`, `kv_norm`), `ATTN_ROPE`
# around the rotations of q and k (latent attention: the slices of q and kv
# into their no-position and rotary parts and the rotation of the two rotary
# parts). What is left under `attn` alone is the reshapes, transposes and
# copies that feed the kernels (and the dense, ring and ulysses paths). Not
# in MODEL_SCOPES: the half still reads as `hvd_block/attn`, and under a
# kind as `ATTN_WINDOW` / `ATTN_FULL`. A fusion has ONE `op_name`, so a
# part's time is by fusion: `fused_scopes` says which fusions mix them.
ATTN_PROJ = "hvd_attn_proj"
ATTN_NORM = "hvd_attn_norm"
ATTN_ROPE = "hvd_attn_rope"
ATTN_PARTS = (ATTN_PROJ, ATTN_NORM, ATTN_ROPE)
# The per-head output gate of `Attention` (`attention_gate="head"`), inside
# flax's `attn`: the gate's projection of the branch's normed input, its
# sigmoid in f32 and the product with the heads' outputs before `out`. Kept
# OUT of `ATTN_PARTS` and `ATTN_KINDS`, which readers of older cells walk: a
# stack without a gate carries no such name.
ATTN_GATE = "hvd_attn_gate"

# Block-diffusion training (`models/block_diffusion.py`), inside FWD_BWD and
# outside the model: the noise draw, the doubled ids, positions and row
# weights (`block_diffusion_batch`), and the slice of the noisy half before
# the head (`block_diffusion_noisy_half`). Not in MODEL_SCOPES.
BD = "hvd_bd"

# The `name=` of every `pl.pallas_call`: on the chip's trace the kernel's
# instruction is `<name>.<n>` and its scope path ends in
# `<name>/pallas_call`.
FLASH_FWD = "hvd_flash_fwd"
FLASH_DQ = "hvd_flash_dq"
FLASH_DKV = "hvd_flash_dkv"
FLASH_BWD = "hvd_flash_bwd"  # dQ, dK and dV in one kernel (resident)
RING_ATTN = "hvd_ring_attn"          # one forward step of ring attention
RING_ATTN_DQ = "hvd_ring_attn_dq"    # one backward step: the dQ part
RING_ATTN_DKV = "hvd_ring_attn_dkv"  # one backward step: the dK/dV part
MOE_GMM = "hvd_moe_gmm"            # grouped matmul of the experts, forward
MOE_GMM_DLHS = "hvd_moe_gmm_dlhs"  # backward: the gradient of the rows
MOE_GMM_DRHS = "hvd_moe_gmm_drhs"  # backward: the gradient of the matrices
MOE_GMM_KERNELS = (MOE_GMM, MOE_GMM_DLHS, MOE_GMM_DRHS)
# A hyper-connection's per-token sum of squares and projection from one read
# of the streams (`ops/hc_stat.py`, under `HC_MAP`); also the name under which
# the recomputations of `models/transformer.py` keep its two results.
HC_STAT = "hvd_hc_stat"
HC_STAT_DPHI = "hvd_hc_stat_dphi"  # backward: the gradient of phi
# The rows of a routed layer that is told its live-row count, moved by the
# count (`ops/moe_rows.py`, under `MOE_DISPATCH` / `MOE_COMBINE`). They are
# no grouped matmuls: `MOE_GMM_KERNELS` does not hold them.
MOE_ROWS = "hvd_moe_rows"  # out[s] = scale[s] * src[idx[s]], s live
MOE_SUM = "hvd_moe_sum"    # y[t] = the sum of the live rows s of token t
MOE_ROWS_KERNELS = (MOE_ROWS, MOE_SUM)
# The activation between the grouped matmuls of such a layer, over the live
# tiles alone (`ops/moe_act.py`, under `MOE_EXPERTS`).
MOE_ACT = "hvd_moe_act"          # a = act(g) * h, or act(h), s live
MOE_ACT_BWD = "hvd_moe_act_bwd"  # (dg, dh), or dh, from (g, h, da)
MOE_ACT_KERNELS = (MOE_ACT, MOE_ACT_BWD)
# The chunked KDA recurrence's kernels (`ops/kda.py`), all under `KDA_CHUNK`.
# A sub-block's own decayed scores term by term (q and k against k inside
# each sub-block of 16 tokens, exp(G_t - G_s) a channel): `KDA_SCORES` /
# `KDA_SCORES_BWD`, the public op `own_block_scores` and, in the model's
# layout, a part of every call `chunk_plan` takes. The rest of the chunk
# stage, one call a direction (PR 59): `KDA_WY` reads q, k, v, the cumulative
# decays and beta as the mixer lays them and the own blocks' squares, and
# makes, a chunk at a time in VMEM, the decayed scores, the inverse of the
# unit triangle, W, U and the scan's other operands, chunks leading;
# `KDA_WY_BWD` turns their cotangents into dq, dk, dv, dG, dbeta and the
# squares' cotangents from the five inputs, the saved inverse and the saved
# k-k scores. `kda_kernel_ms` (the sum over `KDA_KERNELS`) is thereby the
# whole chunk stage but XLA's two products for the cumulative decays.
KDA_SCORES = "hvd_kda_scores"          # (pq, pk) [sub, sub] a sub-block
KDA_SCORES_BWD = "hvd_kda_scores_bwd"  # (dq, dk, dG) from their cotangents
KDA_WY = "hvd_kda_wy"          # (W | Q e^G, U, qk, K e^(G_last - G), e^G_last)
KDA_WY_BWD = "hvd_kda_wy_bwd"  # (dq, dk, dv, dG, dbeta, the squares')
KDA_KERNELS = (KDA_SCORES, KDA_SCORES_BWD, KDA_WY, KDA_WY_BWD)
# The scan over the chunks as kernels, under `KDA_CARRY` and so NOT in
# `KDA_KERNELS` (`kda_kernel_ms` keeps meaning the chunk stage): wherever
# `chunk_plan` takes the call, one call a direction. `KDA_SCAN` walks a
# head's chunks with the state S [D, Dv] f32 resident in VMEM, several heads
# a grid step side by side (V' = U - W S, the output as the mixer lays it,
# the state's update; as the rule's forward it also saves S at each chunk's
# start); `KDA_SCAN_BWD` walks them in reverse with the state's cotangent
# resident and writes the five operands' cotangents.
KDA_SCAN = "hvd_kda_scan"          # (o, the final state, the largest |S|)
KDA_SCAN_BWD = "hvd_kda_scan_bwd"  # (dW|Qe^G, dU, dqk, dK_out, dkeep)
KDA_SCAN_KERNELS = (KDA_SCAN, KDA_SCAN_BWD)
# What a KDA mixer does to the q | k | v columns of its in-projection before
# the recurrence (`ops/kda_conv.py`, PR 64), under `KDA_CONV` and so in
# NEITHER `KDA_KERNELS` nor `KDA_SCAN_KERNELS`: the causal depthwise
# convolutions, SiLU, the l2 norm a head of q and k and q's scale, one call
# a direction wherever `kda_conv_plan` takes the call. `KDA_QKV` reads the
# columns once and writes q, k, v as the recurrence's kernels read them;
# `KDA_QKV_BWD` forms the pre-activation again and walks the blocks of rows
# in reverse.
KDA_QKV = "hvd_kda_qkv"          # (q, k, v) [B, L, H D]
KDA_QKV_BWD = "hvd_kda_qkv_bwd"  # (the three parts' cotangents, the taps')
KDA_CONV_KERNELS = (KDA_QKV, KDA_QKV_BWD)
KERNELS = (FLASH_FWD, FLASH_DQ, FLASH_DKV, FLASH_BWD, RING_ATTN,
           RING_ATTN_DQ, RING_ATTN_DKV) + MOE_GMM_KERNELS \
    + (HC_STAT, HC_STAT_DPHI) \
    + MOE_ROWS_KERNELS + MOE_ACT_KERNELS + KDA_KERNELS + KDA_SCAN_KERNELS \
    + KDA_CONV_KERNELS

# Host spans a traced window shows: the program's only per-call Python
# (`span`), and `step.place`, which is a `phase` (below) and so shows there
# too.
SPAN_PLACE = "hvd_place"                  # `step.place`
SPAN_STEP_DISPATCH = "hvd_step_dispatch"  # zero1's per-call wrapper
HOST_SPANS = (SPAN_PLACE, SPAN_STEP_DISPATCH)

# Getting going: what a process does a bounded number of times before its
# first step, each a `phase` where the work happens (docs/TRACING.md has
# the table), and the three stages the runtime reports for every jitted
# function (`_RUNTIME_SPANS`).
SPAN_INIT = "hvd_init"                  # `hvd.init()`
SPAN_NATIVE_BUILD = "hvd_native_build"  # the lock and `make` before a load
SPAN_NATIVE_INIT = "hvd_native_init"    # rendezvous, background thread
SPAN_MAKE_STEP = "hvd_make_step"        # `make_train_step`'s host side
SPAN_JAX_TRACE = "jax_trace"            # Python -> jaxpr
SPAN_JAX_LOWER = "jax_lower"            # jaxpr -> MLIR module
SPAN_JAX_COMPILE = "jax_compile"        # XLA's compile, or the cache's load
SETUP_SPANS = (SPAN_INIT, SPAN_NATIVE_BUILD, SPAN_NATIVE_INIT,
               SPAN_MAKE_STEP, SPAN_PLACE, SPAN_JAX_TRACE, SPAN_JAX_LOWER,
               SPAN_JAX_COMPILE)
# The name the runtime knows `make_train_step`'s program by: that of the
# function it jits (`parallel/train.py`; a test holds the two together).
# The name is in the module's name, so in the compiled text and the key of
# the compile cache: it is read here, never changed for the record's sake.
STEP_FUN_NAME = "shard_step"
# The record holds this many spans; what comes after is counted and dropped.
PHASES_CAP = 4096

# The runtime's profiler is one per process, so its state is too: the
# directory of the trace `start` began, or None.
_logdir = None
_NO_SPAN = contextlib.nullcontext()


def active():
    """Whether a trace started by `start` is running in this process."""
    return _logdir is not None


def start(logdir):
    """Starts the profiler in this process; the trace goes under `logdir`
    at `stop()`. From Python only the spans of `span` are taken
    (`python_tracer_level=0`: not every call), and the runtime's own host
    events in full (`host_tracer_level=2`)."""
    global _logdir
    if _logdir is not None:
        raise RuntimeError("hvd.profile.start: a trace into %r is already "
                           "active; stop() it first" % _logdir)
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    _logdir = str(logdir)


def stop():
    """Stops the profiler and returns the path of the `.xplane.pb` it wrote
    (`jax.profiler.ProfileData.from_file` reads it; TensorBoard's profile
    plugin and xprof open `logdir`). The runtime names a trace by the
    second it stopped in, so two traces stopped into one `logdir` within a
    second are one file: give each its own directory."""
    global _logdir
    if _logdir is None:
        raise RuntimeError("hvd.profile.stop: no trace is active")
    import jax

    logdir, _logdir = _logdir, None
    jax.profiler.stop_trace()
    written = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    if not written:
        raise RuntimeError("hvd.profile.stop: the profiler wrote no "
                           ".xplane.pb under %r" % logdir)
    return max(written, key=os.path.getmtime)


def span(name):
    """A host span named `name` on the profiler's clock while a trace is
    active; the one shared no-op context while none is."""
    if _logdir is None:
        return _NO_SPAN
    import jax

    return jax.profiler.TraceAnnotation(name)


# --- getting going: the spans a process makes a bounded number of times ----
#
# The record is on all the time, because nothing in it is per step: a dozen
# phases a process, and from the runtime one span for each trace, lowering
# and compile-or-load that no other runtime span encloses. Tracing a large
# step calls thousands of small jitted functions (`jnp.mean`, `einsum`, a
# flax module's own), each with a span of its own inside the step's: those
# are counted on the enclosing span (`attrs["nested"]`), whose duration
# holds them, and not kept. Spans are stamped with `time.time_ns()`, which
# is the clock of the profiler's host plane (an event of `/host:CPU` starts
# `start_ns` after the `profile_start_time` of the trace's
# `Task Environment` plane, in Unix nanoseconds) and the clock of the
# runtime's own spans (`time.time()`).
_RUNTIME_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": SPAN_JAX_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": SPAN_JAX_LOWER,
    "/jax/core/compile/backend_compile_duration": SPAN_JAX_COMPILE}
# What the persistent cache says inside a `jax_compile`: a request that
# consults it is a miss until the cache reports the hit.
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
                 "/jax/compilation_cache/cache_hits": "hit"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")  # `jit(f)`, `pmap(f)` -> `f`

_phases = []  # the record, in the order the spans began
_dropped = 0
_record_lock = threading.Lock()
_listening = False


class _Open(threading.local):
    """What is open in this thread."""

    def __init__(self):
        # Indices into the record, innermost last; None for a span the cap
        # dropped.
        self.stack = []
        # Runtime spans open, and the record's index of the outermost (the
        # one that is kept), or None.
        self.runtime = 0
        self.outermost = None


_open = _Open()


def _begin(name, start_ns, attrs):
    global _dropped
    parent = next((i for i in reversed(_open.stack) if i is not None), None)
    with _record_lock:
        index = len(_phases) if len(_phases) < PHASES_CAP else None
        if index is None:
            _dropped += 1
        else:
            _phases.append({"name": name, "start_ns": start_ns,
                            "end_ns": None, "parent": parent,
                            "attrs": attrs})
    _open.stack.append(index)
    return index


def _end(end_ns):
    index = _open.stack.pop()
    if index is not None:
        _phases[index]["end_ns"] = end_ns


@contextlib.contextmanager
def phase(name):
    """A span named `name` around work the process does a bounded number of
    times: always recorded in memory (`phases()`), and while a trace is
    active a `span` of the same name as well, so that the same work shows
    on `/host:CPU` beside the device planes. Per-call work takes `span`,
    which records nothing. Also a decorator."""
    with span(name):
        _begin(name, time.time_ns(), {})
        try:
            yield
        finally:
            _end(time.time_ns())


def phases():
    """The record, a copy: one dict per span with `name`, `start_ns` and
    `end_ns` (Unix nanoseconds; `end_ns` None while it is open), `parent`
    (the index of the span that was open in the same thread when it began;
    None at the top) and `attrs` (a runtime span's `fun_name` and `nested`;
    on `jax_compile` also `cache`: `hit`, `miss`, or `off` where no
    persistent cache was consulted, and `retrieval_s` on a hit). It is the
    process's, not the core's: `hvd.shutdown()` leaves it as it is."""
    with _record_lock:
        return [dict(p, attrs=dict(p["attrs"])) for p in _phases]


def dropped():
    """How many spans came after the record held `PHASES_CAP`."""
    return _dropped


def compiles(record=None):
    """What the record (`phases()`, or the one given) says per function
    name: `requests` (its `jax_compile` spans), the `hits` and `misses` of
    the persistent cache among them, `seconds` by stage, and `recompiles`
    (`requests` - 1) with the start of each in `recompiled_at_ns`: which
    function was compiled again, and when. Functions that share a name
    (`<lambda>`, two steps of one process) count together."""
    out = {}
    for p in phases() if record is None else record:
        if p["name"] not in _RUNTIME_SPANS.values() or p["end_ns"] is None:
            continue
        fun = out.setdefault(p["attrs"]["fun_name"], {
            "requests": 0, "hits": 0, "misses": 0, "recompiles": 0,
            "recompiled_at_ns": [],
            "seconds": dict.fromkeys(_RUNTIME_SPANS.values(), 0.0)})
        fun["seconds"][p["name"]] += (p["end_ns"] - p["start_ns"]) / 1e9
        if p["name"] == SPAN_JAX_COMPILE:
            fun["requests"] += 1
            fun["hits"] += p["attrs"]["cache"] == "hit"
            fun["misses"] += p["attrs"]["cache"] == "miss"
            if fun["requests"] > 1:
                fun["recompiles"] += 1
                fun["recompiled_at_ns"].append(p["start_ns"])
    return out


def _on_runtime_begin(event, value, fun_name=None, **_):
    """jax's scalar listener: a trace, lowering or compile begins, at
    `value` on `time.time()`."""
    name = _RUNTIME_SPANS.get(event)
    if name is None:
        return
    _open.runtime += 1
    if _open.runtime > 1:
        if _open.outermost is not None:
            _phases[_open.outermost]["attrs"]["nested"] += 1
        return
    attrs = {"fun_name": _WRAPPED_NAME.sub(r"\1", str(fun_name)),
             "nested": 0}
    if name == SPAN_JAX_COMPILE:
        attrs.update(cache="off", retrieval_s=None)
    _open.outermost = _begin(name, int(value * 1e9), attrs)


def _on_runtime_end(event, start, end, **_):
    """jax's time-span listener: the span that began last has ended."""
    if event not in _RUNTIME_SPANS or _open.runtime == 0:
        return  # not ours, or it began before `listen`
    _open.runtime -= 1
    if _open.runtime == 0:
        _end(int(end * 1e9))
        _open.outermost = None


def _on_cache_event(event, duration_secs=None, **_):
    """jax's event and duration listener: what the persistent cache did
    for the `jax_compile` that is open in this thread."""
    if _open.outermost is None:
        return
    attrs = _phases[_open.outermost]["attrs"]
    if "cache" not in attrs:
        return  # a trace or a lowering is open, not a compile
    if event == _CACHE_RETRIEVAL:
        attrs["retrieval_s"] = duration_secs
    elif event in _CACHE_EVENTS:
        attrs["cache"] = _CACHE_EVENTS[event]


def listen():
    """Has the runtime report its traces, lowerings and compiles to the
    record, from now on and once a process: `make_train_step` calls it. A
    job whose first jitted function is not a train step calls it itself,
    as early as it likes."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_runtime_begin)
    monitoring.register_event_time_span_listener(_on_runtime_end)
    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_cache_event)


# --- how far the step's gradient collectives are asynchronous -------------
#
# Read from the text of a compiled step (`compiled.as_text()`), so it needs
# no chip: a compile for a described topology tells it. libtpu issues a
# collective asynchronously in one of two forms: the `<op>-start` / `-done`
# instruction pair, or a pair of fusions named `async-collective-start` /
# `-done` whose computation holds the plain `<op>` instruction (further
# steps of it ride inside compute fusions named `async_collective_fusion`).
# Anything else (the plain `<op>` in the entry computation or a loop body)
# holds the core for its whole wire time.
_COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                   "collective-permute", "all-to-all")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_HLO_LINE = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = (.*)$")
_HLO_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_HLO_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.-]+)")
_HLO_OPERAND = re.compile(r"%([\w.-]+)")


def _closing(text, start):
    """Index just past the parenthesis that closes the one at `start`."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def _type_end(rest):
    """Index in `rest` (an instruction's text after ` = `) where its result
    type ends: a tuple type is skipped to its closing parenthesis."""
    return _closing(rest, 0) if rest.startswith("(") else rest.find(" ")


def _array_bytes(type_text):
    total = 0
    for dtype, dims in _HLO_ARRAY.findall(type_text):
        n = _ITEMSIZE.get(dtype, 0)
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def grad_collectives(text):
    """How the compiled step (`compiled.as_text()`) issues the gradient
    collectives, those with `hvd_grad_sync` in their `op_name`:

        {"sync": {"count": n, "bytes": b}, "async": {"count": n, "bytes": b}}

    `bytes` are those of the collective's result on one device (for an
    all-reduce, the gradients it sums, in the type it sums them in). A
    combined all-reduce of several leaves is one collective. Text with no
    such collective gives zeros."""
    sizes = {}        # (computation, instruction) -> bytes of its result
    fused_by = {}     # computation -> name of the fusion that calls it
    found = []        # (computation, instruction, opcode, operands' text)
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HLO_LINE.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        end = _type_end(rest)
        sizes[comp, name] = _array_bytes(rest[:end])
        op = _HLO_OPCODE.match(rest, end)
        if not op:
            continue
        opcode = op.group(1)
        if opcode == "fusion":
            called = _HLO_CALLS.search(rest, end)
            if called:
                fused_by[called.group(1)] = name
        base = opcode[:-len("-start")] if opcode.endswith("-start") \
            else opcode
        if base in _COLLECTIVE_OPS and GRAD_SYNC in rest:
            found.append((comp, name, opcode,
                          rest[op.end():_closing(rest, op.end() - 1) - 1]))
    out = {"sync": {"count": 0, "bytes": 0},
           "async": {"count": 0, "bytes": 0}}
    for comp, name, opcode, operands in found:
        caller = fused_by.get(comp)
        if comp.startswith(("fused_computation", "async_collective_fusion")):
            # Inside a fusion: the start of an asynchronous collective
            # counts; its later steps, its done and a computation that
            # nothing calls (the compiler leaves some behind) do not.
            if caller is None or not caller.startswith(
                    "async-collective-start"):
                continue
            kind = "async"
        else:
            kind = "async" if opcode.endswith("-start") else "sync"
        nbytes = sizes[comp, name]
        if opcode.endswith("-start") and opcode != "all-reduce-start":
            # Such a start returns its operands beside its results.
            nbytes -= sum(sizes.get((comp, o), 0)
                          for o in _HLO_OPERAND.findall(operands))
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


# --- how far a scope's device time can be trusted ---------------------------
#
# A fusion is one device instruction with ONE `op_name` (the root's, as a
# rule), and a trace counts its whole time for that name. The instructions
# INSIDE its fused computation keep their own `op_name`, so the text of a
# compiled step says which fusions hold work of more than one scope: a
# norm's scaling inside a rotation, a gate inside a projection's matmul.
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_SCOPE_SPLIT = re.compile(r"[/()]")


def _instructions(text):
    """(computation, name, opcode, result type, `op_name` or "", the
    computation a fusion calls or None) of every instruction of a compiled
    program's text."""
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HLO_LINE.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        end = _type_end(rest)
        op = _HLO_OPCODE.match(rest, end)
        if not op:
            continue
        found = _HLO_OP_NAME.search(rest, end)
        called = _HLO_CALLS.search(rest, end) \
            if op.group(1) == "fusion" else None
        yield (comp, name, op.group(1), rest[:end],
               found.group(1) if found else "", called and called.group(1))


def fused_scopes(text, scopes):
    """Which fusions of a compiled step (`compiled.as_text()`) hold work
    under `scopes` (names of this module, e.g. `ATTN_PARTS`), and how pure
    each is. Per fusion instruction of the entry computation (and of a loop's
    or a branch's body) whose own `op_name`, or that of any instruction
    inside its fused computation, holds one of `scopes`:

        {fusion: {"scope": s, "inner": {scope or None: n}, "mixed": bool}}

    `scope` is the one of `scopes` the fusion's own `op_name` holds (the
    innermost on its path, so `scopes` may hold `BLOCK` beside the parts
    inside it; None: none of them), which is where a trace counts its time;
    `inner` counts the fused computation's instructions but its parameters
    and constants by the scope on their own `op_name` (None: none of
    `scopes`, or no `op_name`), a fusion inside it by ITS computation's
    instructions (libtpu fuses the producers of a matmul's operands into the
    matmul's fusion as fusions of their own); `mixed` says that `inner`
    holds more than one of `scopes`. Like `grad_collectives` it reads text
    and needs no chip."""
    scopes = tuple(scopes)

    def scope_of(op_name):
        return next((t for t in reversed(_SCOPE_SPLIT.split(op_name))
                     if t in scopes), None)

    # computation -> [(scope or None, the computation it calls if a fusion)]
    held = {}
    fusions = []  # (fusion, the computation it is in, the one it calls, scope)
    for comp, name, op, _, op_name, called in _instructions(text):
        if op in ("parameter", "constant"):
            continue
        scope = scope_of(op_name)
        held.setdefault(comp, []).append((scope, called))
        if called:
            fusions.append((name, comp, called, scope))

    counted = {}  # computation -> {scope or None: instructions}, all depths

    def count(comp):
        if comp not in counted:
            counts = counted[comp] = {}
            for scope, called in held.get(comp, ()):
                for s, n in (count(called).items() if called
                             else ((scope, 1),)):
                    counts[s] = counts.get(s, 0) + n
        return counted[comp]

    fused = {called for _, _, called, _ in fusions}
    out = {}
    for name, comp, called, scope in fusions:
        if comp in fused:
            continue  # a fusion inside a fusion: counted with its caller
        inner = count(called)
        named = [s for s in inner if s is not None]
        if scope is None and not named:
            continue
        out[name] = {"scope": scope, "inner": dict(inner),
                     "mixed": len(named) > 1}
    return out


def product_fusions(text):
    """The fusions of a compiled program (`compiled.as_text()`) that hold a
    matrix product (`convolution`, as libtpu writes one; `dot` elsewhere),
    and what each computes beside it:

        {fusion: {"op_name": its own, "results": [array type, ...],
                  "reduces": [array type, ...]}}

    `results` are the arrays of the fusion's result type (`"bf16[2,8192,
    2048]"`, no layout), `reduces` the results of the `reduce` instructions
    in its computation at all depths (a producer fusion inside counted by
    its contents, as `fused_scopes` counts). A data gradient whose fusion
    also sums ALONG its output rows and ACROSS them (an RMS norm's backward
    behind it: results dh, f32[rows], f32[columns]) is the form a "conv"
    layer's `_hold_cotangent` (`models/transformer.py`) takes apart; the
    fusions with "reduces" name it wherever else it stands (every norm in
    front of a projection: `examples/norm_grad_sweep.py` times it). Reads
    text and needs no chip."""
    held = {}  # computation -> its `_instructions`
    for inst in _instructions(text):
        held.setdefault(inst[0], []).append(inst)

    def arrays(result_type):
        return ["%s[%s]" % a for a in _HLO_ARRAY.findall(result_type)]

    def inside(comp):
        """(whether a product is in `comp`, its reduces' results)."""
        product, reduces = False, []
        for _, _, op, result_type, _, called in held.get(comp, ()):
            if called:
                p, r = inside(called)
                product, reduces = product or p, reduces + r
            elif op in ("convolution", "dot"):
                product = True
            elif op == "reduce":
                reduces += arrays(result_type)
        return product, reduces

    fused = {i[5] for insts in held.values() for i in insts if i[5]}
    out = {}
    for comp, insts in held.items():
        if comp in fused:
            continue  # a fusion inside a fusion: counted with its caller
        for _, name, _, result_type, op_name, called in insts:
            if called:
                product, reduces = inside(called)
                if product:
                    out[name] = {"op_name": op_name,
                                 "results": arrays(result_type),
                                 "reduces": reduces}
    return out


# --- what lies between a projection and a flash kernel ----------------------
#
# The flash kernels take q, out, dO and dQ as the projections' matmuls write
# them: [B*G, group, L, D], a kv head's query heads side by side, a reshape of
# [B, H, L, D] (`ops/flash_attention.py::_by_kv_head`). Whether a compiled
# program still moves such an activation between a matmul and a kernel is in
# its text: a `copy` or a `transpose`, alone or inside a fusion, whose result
# has the elements, the type and the head width of a flash call's first
# operand.
_FLASH_CALL = re.compile(r"\b(%s)\)*/pallas_call" % "|".join(
    (FLASH_FWD, FLASH_DQ, FLASH_DKV, FLASH_BWD)))
_HLO_SHAPE = re.compile(r"^\(?([a-z]+[0-9]*)\[([0-9,]*)\]")


def attention_layout_copies(text):
    """The passes of a compiled program (`compiled.as_text()`) that only
    RE-LAY an activation of a flash call's q / out / dO / dQ shape:

        {"copies": n, "bytes": b, "calls": flash calls found,
         "instructions": [their names, in the text's order]}

    Counted: an instruction outside any fusion that is a `copy` or a
    `transpose`, or a fusion whose computation (at any depth) holds one,
    where that copy's or transpose's own result has the type and the number
    of elements of the first operand of one of the program's flash calls
    (`hvd_flash_fwd`, `_dq`, `_dkv`, `_bwd`: q, in every one) and a last
    dimension that is a head's width or all the heads' side by side;
    `bytes` are those results'. A fusion that
    computes as it copies is counted too, and a copy XLA makes for a matmul
    of its own (a weight gradient that wants the positions minor) likewise:
    the reading is an upper bound on what the kernels' layout costs, and
    `instructions` says where to look. 0 where every kernel's operands and
    results lie as the matmuls beside it write and read them; the parent of
    PR 57 read 4 to 5 a layer and direction. Beside it on the chip: `INFO
    inner_ms_a_step`'s `rest` (`benchmark/inner_reduce.py`), the attention
    time under no part's scope, which is where these passes' time shows.
    Like `grad_collectives` it reads text and needs no chip."""
    held = {}      # computation -> [(name, opcode, type text, called)]
    operands = {}  # (computation, instruction) -> its type text
    calls = []     # (computation, the first operand's name) of a flash call
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            held[comp] = []
            continue
        m = _HLO_LINE.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        end = _type_end(rest)
        op = _HLO_OPCODE.match(rest, end)
        if not op:
            continue
        operands[comp, name] = rest[:end]
        opcode = op.group(1)
        called = _HLO_CALLS.search(rest, end) if opcode == "fusion" else None
        held[comp].append((name, opcode, rest[:end],
                           called and called.group(1)))
        if opcode == "custom-call" and "tpu_custom_call" in rest:
            found = _HLO_OP_NAME.search(rest, end)
            first = _HLO_OPERAND.match(rest, op.end())
            if found and first and _FLASH_CALL.search(found.group(1)):
                calls.append((comp, first.group(1)))

    def key(type_text):
        m = _HLO_SHAPE.match(type_text)
        if not m or not m.group(2):
            return None
        dims = [int(d) for d in m.group(2).split(",")]
        count = 1
        for d in dims:
            count *= d
        return m.group(1), count, dims[-1]

    q_keys = {key(operands[c]) for c in calls if c in operands} - {None}

    def q_side(type_text):
        """An array of a flash call's q: its type, its elements, and a last
        dimension that is the operand's, a whole number of them or a whole
        part of it (a head's width beside all the heads' side by side)."""
        found = key(type_text)
        return found is not None and any(
            found[:2] == q[:2] and (found[2] % q[2] == 0
                                    or q[2] % found[2] == 0)
            for q in q_keys)

    def moved(comp):
        """Bytes of the q-side copies and transposes a computation holds."""
        total = 0
        for _, opcode, type_text, called in held.get(comp, ()):
            if called:
                total += moved(called)
            elif opcode in ("copy", "transpose") and q_side(type_text):
                total += _array_bytes(type_text)
        return total

    fused = {called for insts in held.values() for *_, called in insts
             if called}
    out = {"copies": 0, "bytes": 0, "calls": len(calls), "instructions": []}
    for comp, insts in held.items():
        # Inside a fusion: counted with its caller (one that nothing calls,
        # the compiler leaves some behind, with nobody).
        if comp in fused or comp.startswith("fused_computation"):
            continue
        for name, opcode, type_text, called in insts:
            nbytes = moved(called) if called else (
                _array_bytes(type_text)
                if opcode in ("copy", "transpose") and q_side(type_text)
                else 0)
            if nbytes:
                out["copies"] += 1
                out["bytes"] += nbytes
                out["instructions"].append(name)
    return out


# --- which path the flash kernels of a call take ---------------------------

def flash_plan(*args, **kwargs):
    """How `ops.flash_attention` runs a call of the given shape, kernel by
    kernel: `ops.flash_attention.flash_plan` (its arguments and result),
    here beside the other program-side counters. Per kernel name
    (`FLASH_FWD`; with ``backward=True`` `FLASH_BWD` alone where the whole
    backward is one resident kernel, else `FLASH_DQ` and `FLASH_DKV`): the
    path (`resident`: the other sequence whole in VMEM, one grid step per
    block; `gridded`: one grid step per tile), the side a grid step holds a
    block of (`held`: "q" or "k"; dK/dV is resident by the k block where a
    kv head's queries fit VMEM, else by the q block with dK and dV summed
    in VMEM, else gridded), the blocks, the grid and the
    grid steps a call issues, and the VMEM bytes it asks for. `block_q`
    counts the ROWS of a score tile: `block_q // group` positions of each of
    a kv head's `group` query heads, whose stripes the q-side block spec
    ([group, block_q // group, D] of an operand [B*H, L, D]) fetches side
    by side and the kernel stacks (row r: head r // bqp, position r % bqp;
    `attention_layout_copies` reads a program for what that order spares).
    With
    ``shared_dim=D2`` the call's scores are of two products (latent
    attention's rotary slice on one key shared by the heads): the same
    kernels hold the second pair of operands and the sums count them
    (D=128, D2=64: the one-kernel backward held by the k block at L=4096,
    22 MiB of the 24; at L=8192 held by the q block, the whole-sequence
    operands in one buffer each and the shared key's gradient summed over
    the heads in VMEM, 24 MiB; past it dQ and dK/dV apart, gridded where
    they must be): every form is a kernel, none is ``{}``. With ``mask=`` a
    rule (`ops.BlockDiffusionMask(length, block)`; L counts all 2 x length
    positions) every plan also says how many score tiles of a call its
    kernel visits, masks and skips (`tiles_visited`, `tiles_masked`,
    `tiles_skipped`: the rule's own runs, which the kernel walks), the
    width of the sub-tile it takes alone where a cut k block has one in
    sight (`cut_k`: under `block_k` in the one-kernel backward held by the q
    block; else `block_k`: the kernel walks k blocks alone) and the
    sub-tiles of that width it visits and masks (`subtiles_visited`,
    `subtiles_masked`): the area under the mask pass is `subtiles_masked` x
    `cut_k` keys a query tile, against `tiles_masked` x `block_k` by k
    blocks alone. The
    forward and dQ take a rule resident only, dK/dV in any of its forms,
    ``{}`` otherwise. With ``gate=True`` the call has a head gate
    (`flash_attention(..., gate=)`: gated attention's sigmoid a head and
    position, > 0) and every plan says in its `gate` field how the gate
    reaches its kernel: "kernel" (the forward, in either form: it reads the
    reciprocals of the rows' gates, one q-side operand more, and multiplies
    the rows' normalisers by them: acc g / l = acc / (l / g)) or "lse" (a
    backward kernel, in every form: the ungated kernel to the letter, handed
    the gated rows' log-normaliser, lse - log gate, which the forward saved,
    and delta / gate, which is the gate's gradient too; no product with dO
    runs anywhere). So `{"fwd": plans_fwd[FLASH_FWD].gate, "bwd":
    plans_bwd[...].gate}` reads `{"fwd": "kernel", "bwd": "lse"}` at both
    calls of `laguna33b_1chip`, and a trace's time under `hvd_attn_gate` is
    the gate's projection, its sigmoid, the operand's forming and delta's
    division only: what the gate costs the kernels (a column's pass a q
    block of the forward) is inside the flash kernels' own time. Without it
    the field is None and the plans are what they were. The kernels run what
    this returns, so like `grad_collectives` it needs no chip."""
    # `ops.flash_attention` imports this module for its kernels' names.
    from horovod_tpu.ops.flash_attention import flash_plan as plan

    return plan(*args, **kwargs)


# --- how the vocabulary loss cuts a call into chunks ------------------------

def loss_plan(*args, **kwargs):
    """How `ops.losses.chunked_softmax_cross_entropy` runs a call of the
    given shapes: `ops.losses.loss_plan(B, L, D, V, chunk, dtype)` (its
    arguments and result), here beside the other program-side counters. The
    rows of a scan iteration and how many iterations that makes, the passes
    of the head a training step makes (3: logits, d-hidden, d-kernel; the
    gradient is formed in the forward pass), the bytes of a chunk's live
    logits and of the residuals the forward keeps. The loss runs what this
    returns, so like `flash_plan` it needs no chip."""
    # `ops.losses` imports this module for its scope's name.
    from horovod_tpu.ops.losses import loss_plan as plan

    return plan(*args, **kwargs)


# --- how a hyper-connection's statistic is formed, and how often ------------

def hc_plan(*args, **kwargs):
    """How `ops.hc_stat.hc_stat` runs a call on the streams [n, T, C]
    against phi's K columns, and how often a training step makes it for one
    connection: `ops.hc_stat.hc_plan(n, T, C, K, dtype, hc_remat=...,
    block_remat=...)` (its arguments and result), here beside the other
    program-side counters. The path (`kernel`: `HC_STAT`, one pass over the
    streams; `jnp`: two), a tile's tokens, the grid steps a call issues, the
    VMEM bytes its blocks take, and the evaluations a step makes under the
    model's recomputations. The op runs what this returns, so like
    `flash_plan` it needs no chip."""
    # `ops.hc_stat` imports this module for its kernel's name.
    from horovod_tpu.ops.hc_stat import hc_plan as plan

    return plan(*args, **kwargs)


# --- how a dropless routed layer moves its rows -----------------------------

def moe_rows_plan(*args, **kwargs):
    """How the dispatch and the combine of `parallel.moe_ffn`'s dropless
    local path move the rows of a call: `ops.moe_rows.rows_plan(T, k, D,
    dtype, experts=, held=)` (its arguments and result), here beside the
    other program-side counters. The path (`kernel`: `MOE_ROWS` and
    `MOE_SUM`, which touch the live rows alone, where the shapes fit and a
    TPU runs it; `jnp`: gathers and sums over all k * T rows of the buffer),
    a tile's rows, the columns of the token side resident at a time, the
    buffer's rows, the VMEM bytes a kernel's blocks take and the kernel
    calls a layer makes in each direction. How many of the buffer's rows are
    live: all where the layer holds every expert, else the router's to
    decide each step (`parallel.routing_stats`' `held_share` counts it).
    And how the sorted order of the rows is formed (`order`, `bins`; PR 55):
    `count` where the layer routes over `experts` and is told it holds
    ``held=(first, count)`` of them, count < experts (the held experts' run
    alone, by counting over its `bins` = count bins and ONE sort that
    carries the weights, the weights' gradient sorted back:
    `parallel/expert.held_order`); `argsort` (0 bins) where it holds them
    all, is not told, or has a capacity (`sort_assignments`: two argsorts
    of all k * T assignments, the weights gathered). The ops run what this
    returns."""
    # `ops.moe_rows` imports this module for its kernels' names.
    from horovod_tpu.ops.moe_rows import rows_plan as plan

    return plan(*args, **kwargs)


def moe_act_plan(*args, **kwargs):
    """How the activation between the grouped matmuls of such a layer runs:
    `ops.moe_act.act_plan(rows, F, dtype, gated=, held=)` (its arguments and
    result). The path (`kernel`: `MOE_ACT` and `MOE_ACT_BWD`, which touch
    the live tiles of the [rows, F] buffers alone, where the layer holds a
    part of the experts, the shapes fit and a TPU runs it; `xla`: the plain
    expression over all rows), a tile's rows, a block's columns, the
    buffer's rows, the grid steps a call issues, the VMEM bytes the
    backward's blocks take and the kernel calls a layer makes in each
    direction. How often it engages is the live tiles' share of the
    buffer's (`parallel.routing_stats`' `held_share`). The op runs what
    this returns."""
    from horovod_tpu.ops.moe_act import act_plan as plan

    return plan(*args, **kwargs)


# --- how a KDA mixer's short convolutions run -------------------------------

def kda_conv_plan(*args, **kwargs):
    """How `ops.kda_conv.kda_qkv` runs a call, the short convolutions of a
    Kimi Delta Attention mixer with their SiLU and the head norms of q and
    k: `ops.kda_conv.conv_plan(B, L, H, D, taps, dtype)` (its arguments and
    result). The path (`kernel`: `KDA_QKV` and `KDA_QKV_BWD`, each one read
    of its operands, where a head is a whole number of lane tiles, a block
    of rows divides the sequence and a TPU runs it; `jnp`: XLA's fusions of
    the plain expression), the rows and the lane tiles of a grid step's
    block, the rows a kernel's loop holds in registers, the grid steps a
    call issues and the bytes a call moves in each direction. The op runs
    what this returns."""
    from horovod_tpu.ops.kda_conv import conv_plan as plan

    return plan(*args, **kwargs)


def sconv_plan(*args, **kwargs):
    """How `ops.sconv.gated_conv` runs a call, the pass between the two
    projections of a double-gated short convolution mixer:
    `ops.sconv.gate_plan(B, L, C, taps, dtype)` (its arguments and result).
    The path (`jnp`: XLA's fusions of the plain expression; no kernel yet)
    and the bytes a ONE-PASS form moves in each direction, which
    `sconv_gate_roofline` is counted on whatever runs the call."""
    from horovod_tpu.ops.sconv import gate_plan as plan

    return plan(*args, **kwargs)
