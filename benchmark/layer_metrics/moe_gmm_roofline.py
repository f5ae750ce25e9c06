"""Share of their roofline the experts' grouped-matmul kernels reach, in
percent: the least time the chip could take for the operations they execute
(`flops_moe.gated_experts_flops`) or the bytes they must move
(`flops_moe.gated_experts_min_bytes`: every operand once, rows and results
in the compute dtype, the matrices and their gradient in the dtype the
parameters are resident in), whichever is larger, over the device time they
took (`moe_gmm_ms`). Which bound binds is printed as an INFO line, with
both: at about 512 rows an expert over f32 matrices it is the bytes (over
bf16-resident matrices, which the program does not keep, it would be the
operations, narrowly)."""

import json

from benchmark.layer_metrics import moe_gmm_ms


def read(trace, context):
    counts, peaks = context["counts"], context["peaks"]
    if "moe_gmm_executed_flops" not in counts:
        return None
    ms = moe_gmm_ms.read(trace, context)
    if not ms:
        return None
    b = {"operations": counts["moe_gmm_executed_flops"]
         / peaks["bf16_flops_per_s"],
         "bytes": counts["moe_gmm_min_bytes"] / peaks["hbm_bytes_per_s"]}
    print("INFO " + json.dumps(
        {"moe_gmm_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
