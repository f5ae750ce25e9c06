"""Share of their roofline the flash-attention kernels reach, in percent:
the least time the chip could take for the operations they execute
(`flops.flash_executed_flops` of the kernels `hvd.profile.flash_plan` names
for the cell's shapes, the builder's `counts`: 7 matmul-equivalents a layer
where the backward is one kernel, 9 where it is two) or the bytes they must
move (`flops.flash_min_bytes`), whichever is larger, over the device time
they took (`flash_ms`). The counts follow the PLAN and the time the TRACE:
where the flash kernels the trace holds (by their own names) are not the
ones the plan names, the share would be wrong by 7/9 or 9/7, so it is not
given and an INFO line says which kernels each side names (`share`, which
the `.olmoe`, `.ouro`, `.sdar` and `.xing` readers use too). Which bound
binds is printed as an INFO line, with both; at head size 128 and length
2048 it is the operations."""

import json

from benchmark import scope_reduce as sr
from benchmark.layer_metrics import flash_ms


def bounds_s(context):
    counts, peaks = context["counts"], context["peaks"]
    return {"operations": counts["flash_executed_flops"]
            / peaks["bf16_flops_per_s"],
            "bytes": counts["flash_min_bytes"] / peaks["hbm_bytes_per_s"]}


def flash_kernels_ms(trace, context):
    """{kernel: ms a step} of the flash kernels the trace holds, by the
    names the program gave them; empty where it has none (or no names)."""
    out = sr.reduce(trace, context) if sr.names is not None else None
    if out is None:
        return {}
    return {k: out["kernels"][k] for k in (
        sr.names.FLASH_FWD, sr.names.FLASH_BWD, sr.names.FLASH_DQ,
        sr.names.FLASH_DKV) if out["kernels"].get(k)}


def share(trace, context, ms, label="flash_roofline"):
    """The bounds of `context["counts"]` over `ms`, in percent, or None:
    where `ms` is nothing, where the builder counted nothing, and where the
    trace's flash kernels are not the plan's (`counts["flash_kernels"]`)."""
    counts = context["counts"]
    if "flash_executed_flops" not in counts or not ms:
        return None
    ran, planned = sorted(flash_kernels_ms(trace, context)), \
        sorted(set(counts["flash_kernels"]))
    if ran != planned:
        print("INFO " + json.dumps(
            {label + "_not_read": "the trace's flash kernels are not the "
             "plan's, which the counts follow", "trace": ran,
             "plan": planned}), flush=True)
        return None
    b = bounds_s(context)
    print("INFO " + json.dumps(
        {label + "_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context, flash_ms.read(trace, context))
