"""Share of their roofline the two kernels of a sub-block's own decayed
scores reach, in percent: the least time the chip could take for the
operations they execute (`flops_kimi.kda_scores_flops`: 5 a [sub, sub, D]
term forward, 10 backward, the whole square) or the bytes they must move
(`flops_kimi.kda_scores_min_bytes`; the builder's `counts`), whichever is
larger, over the device time they took (`kda_kernel_ms`). The operations
are the vector unit's and are held against the MATRIX unit's peak, which
is the only one `peaks.json` has: the bytes bind. Which bound binds is
printed as an INFO line, with both."""

import json

from benchmark import kimi_reduce


def read(trace, context):
    counts, peaks = context["counts"], context["peaks"]
    if "kda_kernel_executed_flops" not in counts:
        return None
    ms = kimi_reduce.ms(trace, context, "kda_kernel")
    if not ms:
        return None
    b = {"operations": counts["kda_kernel_executed_flops"]
         / peaks["bf16_flops_per_s"],
         "bytes": counts["kda_kernel_min_bytes"] / peaks["hbm_bytes_per_s"]}
    print("INFO " + json.dumps(
        {"kda_kernel_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
