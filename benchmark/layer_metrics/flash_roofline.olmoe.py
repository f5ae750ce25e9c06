"""`flash_roofline` for the OLMoE cell: the bounds of `flash_roofline.py`
over the time of the three flash kernels taken by their own NAMES, not
through `flash_ms` (which counts every `tpu_custom_call` and would count
this cell's grouped-matmul kernels as flash time)."""

import json

from benchmark import scope_reduce as sr
from benchmark.layer_metrics.flash_roofline import bounds_s


def read(trace, context):
    if "flash_executed_flops" not in context["counts"] or sr.names is None:
        return None
    parts = [sr.kernel_ms(trace, context, k) for k in (
        sr.names.FLASH_FWD, sr.names.FLASH_DQ, sr.names.FLASH_DKV)]
    if any(p is None for p in parts):
        return None
    b = bounds_s(context)
    print("INFO " + json.dumps(
        {"flash_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (sum(parts) / 1e3)
