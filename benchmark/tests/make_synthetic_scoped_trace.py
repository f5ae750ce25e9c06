#!/usr/bin/env python3
"""Writes `data/synthetic_scoped.xplane.pb`: a two-chip, two-step trace
whose device events carry scope paths the way the TPU runtime stores them —
the stat `tf_op` of the event's METADATA, which `ProfileData` does not show
— with every interval and scope chosen by hand, so that the answers
`test_scope_reduce.py` holds the reduction to can be worked on paper (they
are worked in that file).

Like `make_synthetic_trace.py` it needs tensorflow's copy of the xplane
protocol buffers and is run by hand when the trace has to change; the tests
read the committed file with `jax.profiler.ProfileData` and
`scope_reduce.op_names` alone.
"""

import os

from make_synthetic_trace import ALLREDUCE, BASE_US, FUSION, KERNEL, WHILE

COPY = ("%{n} = f32[2048,16,128]{{2,1,0:T(8,128)}} copy(f32[2048,16,128]"
        "{{2,0,1:T(8,128)}} %p.1)")
COPY_DONE = ("%{n} = f32[8]{{0}} copy-done((f32[8]{{0}}, f32[8]{{0}}, u32[]"
             "{{:S(2)}}) %copy-start.2)")
STEP = "jit(shard_step)/shard_map/"
FWD = STEP + "hvd_fwd_bwd/jvp(Transformer)/"
BWD = STEP + "hvd_fwd_bwd/transpose(jvp(Transformer))/"
VJP = STEP + "hvd_fwd_bwd/transpose(hvd_fwd_bwd)/jvp(Transformer)/"

# instruction name -> (text template, op_name or None, how the stat is
# stored: "str" as the runtime stores it (with the colon it ends in),
# "ref" a reference to a stat metadata's name, which the format allows).
INSTRUCTIONS = {
    # the compiler's own copy, before anything named: no op_name at all
    "copy.1": (COPY, None, None),
    "fusion.1": (FUSION, FWD + "hvd_embed/embed/jit(_take)/gather:", "str"),
    "hvd_flash_fwd.1": (KERNEL, FWD + "hvd_block/block_0/attn/hvd_flash_fwd/"
                        "pallas_call:", "str"),
    "fusion.2": (FUSION, FWD + "hvd_block/block_0/mlp/mlp_in/dot_general:",
                 "str"),
    "while.1": (WHILE, STEP + "hvd_fwd_bwd/jvp(hvd_loss)/while:", "str"),
    "fusion.3": (FUSION, STEP + "hvd_fwd_bwd/jvp(hvd_loss)/while/body/"
                 "closed_call/dot_general:", "str"),
    "fusion.4": (FUSION, STEP + "hvd_fwd_bwd/transpose(jvp(hvd_loss))/while/"
                 "body/checkpoint/rematted_computation/dot_general:", "str"),
    # the compiler's wait for an asynchronous copy, after the loss
    "copy-done.2": (COPY_DONE, None, None),
    "hvd_flash_dkv.1": (KERNEL, VJP + "hvd_block/block_0/attn/hvd_flash_dkv/"
                        "pallas_call:", "str"),
    "hvd_flash_dq.1": (KERNEL, VJP + "hvd_block/block_0/attn/hvd_flash_dq/"
                       "pallas_call:", "str"),
    "fusion.5": (FUSION, BWD + "hvd_block/block_0/mlp/mlp_out/transpose:",
                 "str"),
    "all-reduce.1": (ALLREDUCE, STEP + "hvd_grad_sync/psum:", "str"),
    "fusion.6": (FUSION, STEP + "hvd_grad_sync/div:", "str"),
    "fusion.7": (FUSION, STEP + "hvd_optimizer/mul:", "ref"),
    # an instruction XLA made and named after the enclosing computation:
    # an op_name with none of the program's phase scopes
    "fusion.8": (FUSION, STEP + "convert.88:", "str"),
}
# Two programs that give one instruction name different op_names: in the
# metadata table only, no event.
AMBIGUOUS = [("fusion.9", FUSION, FWD + "hvd_head/norm_f/mul:"),
             ("fusion.9", FUSION.replace("8,128", "4,128"),
              STEP + "hvd_optimizer/mul:")]

# (instruction name, start, end), microseconds from the start of step 1.
DEVICE0_OPS = [
    ("copy.1", 0, 10),
    ("fusion.1", 10, 60),
    ("hvd_flash_fwd.1", 60, 160),
    ("fusion.2", 160, 260),
    ("while.1", 260, 400),
    ("fusion.3", 260, 320),
    ("fusion.4", 330, 400),
    ("copy-done.2", 400, 420),
    ("hvd_flash_dkv.1", 420, 560),
    ("hvd_flash_dq.1", 560, 640),
    ("fusion.5", 640, 700),
    ("all-reduce.1", 700, 800),
    ("fusion.6", 800, 820),
    ("fusion.7", 820, 900),
    ("fusion.8", 900, 930),
    # 70 us idle, then step 2
    ("fusion.1", 1000, 1050),
    ("hvd_flash_fwd.1", 1050, 1170),
    # 30 us idle
    ("fusion.7", 1200, 1300),
]
DEVICE1_OPS = [
    ("fusion.1", 0, 400),
    ("all-reduce.1", 400, 700),
    ("fusion.7", 700, 800),
    # 200 us idle, then step 2
    ("hvd_flash_fwd.1", 1000, 1200),
]
MODULES = [("jit_shard_step(1)", 0, 1000), ("jit_shard_step(1)", 1000, 1900)]


def main():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()

    def add_plane(name, ops):
        plane = space.planes.add()
        plane.name = name
        plane.id = len(space.planes)
        # a stat that is not the op_name stands before it, as in a real trace
        flops = plane.stat_metadata[1]
        flops.id, flops.name = 1, "flops"
        tf_op = plane.stat_metadata[2]
        tf_op.id, tf_op.name = 2, "tf_op"
        ids = {}

        def metadata(text, scope, how):
            meta = plane.event_metadata[len(plane.event_metadata) + 1]
            meta.id = len(plane.event_metadata)
            meta.name = text
            other = meta.stats.add()
            other.metadata_id = 1
            other.uint64_value = 4096
            if scope is not None:
                stat = meta.stats.add()
                stat.metadata_id = 2
                if how == "str":
                    stat.str_value = scope
                else:
                    ref = plane.stat_metadata[len(plane.stat_metadata) + 1]
                    ref.id, ref.name = len(plane.stat_metadata), scope
                    stat.ref_value = ref.id
            return meta.id

        def add_line(line_name, events, named):
            line = plane.lines.add()
            line.id = len(plane.lines)
            line.name = line_name
            line.timestamp_ns = BASE_US * 1000
            for name_, start, end in events:
                if name_ not in ids:
                    if named:
                        text, scope, how = INSTRUCTIONS[name_]
                        ids[name_] = metadata(text.format(n=name_), scope, how)
                    else:
                        ids[name_] = metadata(name_, None, None)
                ev = line.events.add()
                ev.metadata_id = ids[name_]
                ev.offset_ps = start * 1_000_000
                ev.duration_ps = (end - start) * 1_000_000

        add_line("XLA Modules", MODULES, named=False)
        add_line("XLA Ops", ops, named=True)
        for name_, text, scope in AMBIGUOUS:
            metadata(text.format(n=name_), scope, "str")

    add_plane("/device:TPU:0", DEVICE0_OPS)
    add_plane("/device:TPU:1", DEVICE1_OPS)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "synthetic_scoped.xplane.pb")
    with open(out, "wb") as f:
        f.write(space.SerializeToString())
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main()
