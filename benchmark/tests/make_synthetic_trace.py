#!/usr/bin/env python3
"""Writes `data/synthetic.xplane.pb`: a two-chip, two-step trace whose every
interval is chosen by hand, so that the answers `test_trace_reduce.py` holds
the reduction to can be worked on paper (they are worked in that file).

Needs tensorflow's copy of the xplane protocol buffers, so it is run by hand
when the trace has to change; the tests read the committed file with
`jax.profiler.ProfileData` alone.

Times below are microseconds from the start of step 1; the file adds
`BASE_US`. Device events are named with whole HLO instruction texts, as the
TPU runtime names them.
"""

import os

BASE_US = 10_000

FUSION = ("%{n} = f32[8,128]{{1,0:T(8,128)}} fusion(f32[8,128]{{1,0:T(8,128)}} "
          "%p.0), kind=kLoop, calls=%fused_computation")
KERNEL = ("%{n} = (bf16[4,256,128]{{2,1,0:T(8,128)(2,1)}}, f32[4,256,8]"
          "{{2,1,0:T(8,128)}}) custom-call(bf16[4,256,128]{{2,1,0:T(8,128)(2,1)}}"
          " %bitcast.1), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={{bf16[4,256,128]{{2,1,0}}}}")
BITCAST = ("%{n} = f32[16,128]{{1,0}} custom-call(f32[8,128]{{1,0}} %a, "
           "f32[8,128]{{1,0}} %b), custom_call_target=\"ConcatBitcast\"")
WHILE = ("%{n} = (s32[]{{:T(128)}}, f32[8,128]{{1,0:T(8,128)}}) while((s32[]"
         "{{:T(128)}}, f32[8,128]{{1,0:T(8,128)}}) %tuple.1), condition=%cond, "
         "body=%body")
ALLREDUCE = ("%{n} = f32[2048,8192]{{1,0:T(8,128)}} all-reduce(f32[2048,8192]"
             "{{1,0:T(8,128)}} %fusion.4), channel_id=1, replica_groups="
             "{{{{0,1}}}}, use_global_device_ids=true, to_apply=%add")
AR_START = ("%{n} = f32[16]{{0:T(128)}} all-reduce-start(f32[16]{{0:T(128)}} "
            "%x), channel_id=2, replica_groups={{{{0,1}}}}, to_apply=%add")
AR_DONE = "%{n} = f32[16]{{0:T(128)}} all-reduce-done(f32[16]{{0:T(128)}} %s)"
COPY_START = ("%{n} = (f32[8]{{0}}, f32[8]{{0}}, u32[]{{:S(2)}}) copy-start("
              "f32[8]{{0}} %y)")

# (text template, instruction name, start, end) on each line.
DEVICE0_OPS = [
    (FUSION, "fusion.1", 0, 100),
    (KERNEL, "attn.1", 100, 300),
    (WHILE, "while.1", 300, 700),
    (FUSION, "fusion.2", 300, 450),
    (FUSION, "fusion.3", 460, 700),
    (AR_START, "all-reduce-start.1", 700, 710),
    (FUSION, "fusion.4", 710, 800),
    (AR_DONE, "all-reduce-done.1", 800, 850),
    (ALLREDUCE, "all-reduce.2", 850, 950),
    # 50 us idle, then step 2
    (FUSION, "fusion.1", 1000, 1100),
    (KERNEL, "attn.1", 1100, 1300),
    (BITCAST, "custom-call.9", 1300, 1300),
    (FUSION, "fusion.5", 1300, 1800),
    # 80 us idle
    (ALLREDUCE, "all-reduce.2", 1880, 1900),
]
DEVICE0_ASYNC = [
    (COPY_START, "copy-start.3", 100, 400),
    (AR_START, "all-reduce-start.1", 700, 850),
]
DEVICE1_OPS = [
    (FUSION, "fusion.1", 0, 500),
    (ALLREDUCE, "all-reduce.2", 500, 700),
    (KERNEL, "attn.1", 700, 1000),
    (FUSION, "fusion.1", 1000, 1400),
    # 100 us idle
    (ALLREDUCE, "all-reduce.2", 1500, 1900),
]
MODULES = [("jit_shard_step(1)", 0, 1000), ("jit_shard_step(1)", 1000, 1900)]
HOST = [
    ("bench_dispatch", -20, -5),
    ("bench_wait_loss", 5, 990),
    ("bench_dispatch", 990, 996),
    ("bench_wait_loss", 996, 1795),
    ("bench_dispatch", 1795, 1870),
    ("bench_wait_loss", 1870, 1905),
    ("PjitFunction(jit(shard_step))", 991, 995),
]


def main():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()

    def add_plane(name, lines):
        plane = space.planes.add()
        plane.name = name
        plane.id = len(space.planes)
        ids = {}
        for line_id, (line_name, events) in enumerate(lines, 1):
            line = plane.lines.add()
            line.id = line_id
            line.name = line_name
            line.timestamp_ns = BASE_US * 1000
            for name_, start, end in events:
                if name_ not in ids:
                    ids[name_] = len(ids) + 1
                    meta = plane.event_metadata[ids[name_]]
                    meta.id = ids[name_]
                    meta.name = name_
                ev = line.events.add()
                ev.metadata_id = ids[name_]
                ev.offset_ps = start * 1_000_000
                ev.duration_ps = (end - start) * 1_000_000

    def texts(rows):
        return [(t.format(n=n), s, e) for t, n, s, e in rows]

    add_plane("/device:TPU:0", [("XLA Modules", MODULES),
                                ("XLA Ops", texts(DEVICE0_OPS)),
                                ("Async XLA Ops", texts(DEVICE0_ASYNC))])
    add_plane("/device:TPU:1", [("XLA Modules", MODULES),
                                ("XLA Ops", texts(DEVICE1_OPS))])
    add_plane("/host:CPU", [("python3", HOST)])
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "synthetic.xplane.pb")
    with open(out, "wb") as f:
        f.write(space.SerializeToString())
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main()
