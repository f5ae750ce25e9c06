"""Share of their roofline the flash-attention kernels reach, in percent:
the least time the chip could take for the operations they execute (9
matmul-equivalents a layer, `flops.flash_executed_flops`) or the bytes they
must move (`flops.flash_min_bytes`), whichever is larger, over the device
time they took (`flash_ms`). Which bound binds is printed by the harness's
INFO line of this metric's context; at head size 128 and length 2048 it is
the operations."""

import json

from benchmark.layer_metrics import flash_ms


def bounds_s(context):
    counts, peaks = context["counts"], context["peaks"]
    return {"operations": counts["flash_executed_flops"]
            / peaks["bf16_flops_per_s"],
            "bytes": counts["flash_min_bytes"] / peaks["hbm_bytes_per_s"]}


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    ms = flash_ms.read(trace, context)
    if ms is None:
        return None
    b = bounds_s(context)
    print("INFO " + json.dumps(
        {"flash_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
