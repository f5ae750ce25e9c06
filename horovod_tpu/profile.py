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
  `span(name)` is what host code puts around its own work, on the
  profiler's clock (the clock of the device planes), and is one shared
  no-op context while no trace is active.

Nothing here reads the environment, and importing this module imports
nothing, starts nothing and touches no device: jax is imported by the calls
that need it, so `import horovod_tpu` stays free of it.
"""

import contextlib
import glob
import os
import re

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
MOE_SCOPES = (MOE, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
              MOE_SHARED)

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
BN_STATS = "hvd_bn_stats"
BN_GRAD_STATS = "hvd_bn_grad_stats"
MOE_GMM = "hvd_moe_gmm"            # grouped matmul of the experts, forward
MOE_GMM_DLHS = "hvd_moe_gmm_dlhs"  # backward: the gradient of the rows
MOE_GMM_DRHS = "hvd_moe_gmm_drhs"  # backward: the gradient of the matrices
MOE_GMM_KERNELS = (MOE_GMM, MOE_GMM_DLHS, MOE_GMM_DRHS)
# A hyper-connection's per-token sum of squares and projection from one read
# of the streams (`ops/hc_stat.py`, under `HC_MAP`); also the name under which
# the recomputations of `models/transformer.py` keep its two results.
HC_STAT = "hvd_hc_stat"
HC_STAT_DPHI = "hvd_hc_stat_dphi"  # backward: the gradient of phi
KERNELS = (FLASH_FWD, FLASH_DQ, FLASH_DKV, FLASH_BWD, RING_ATTN,
           RING_ATTN_DQ, RING_ATTN_DKV, BN_STATS,
           BN_GRAD_STATS) + MOE_GMM_KERNELS + (HC_STAT, HC_STAT_DPHI)

# Host spans of the program's only per-call Python.
SPAN_PLACE = "hvd_place"                  # `step.place`
SPAN_STEP_DISPATCH = "hvd_step_dispatch"  # zero1's per-call wrapper
HOST_SPANS = (SPAN_PLACE, SPAN_STEP_DISPATCH)

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


# --- which path the flash kernels of a call take ---------------------------

def flash_plan(*args, **kwargs):
    """How `ops.flash_attention` runs a call of the given shape, kernel by
    kernel: `ops.flash_attention.flash_plan` (its arguments and result),
    here beside the other program-side counters. Per kernel name
    (`FLASH_FWD`; with ``backward=True`` `FLASH_BWD` alone where the whole
    backward is one resident kernel, else `FLASH_DQ` and `FLASH_DKV`): the
    path (`resident`: the other sequence whole in VMEM, one grid step per
    block; `gridded`: one grid step per tile), the blocks, the grid and the
    grid steps a call issues, and the VMEM bytes it asks for. With
    ``shared_dim=D2`` the call's scores are of two products (latent
    attention's rotary slice on one key shared by the heads): the same
    kernels hold the second pair of operands and the sums count them
    (D=128, D2=64, L=4096: the one-kernel backward at 22 MiB of the 24);
    ``{}`` says no kernel has that form at the shape (a gridded one would
    be needed) and the call is the blockwise jnp path. The kernels run what
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
