"""Share of their roofline the flash kernels reach under scores of two
products, in percent: the least time the chip could take for the operations
they execute or the bytes they must move (`flops_xing.flash_executed_flops`,
`flops_xing.flash_min_bytes`, by the kernels `flash_plan` names for the
cell's shapes: the builder's `counts`), whichever is larger, over the
device time they took (`flash_ms.xing`: by the kernels' own names). Which
bound binds is printed as an INFO line, with both."""

import json

from benchmark import xing_reduce
from benchmark.layer_metrics.flash_roofline import bounds_s


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    ms = xing_reduce.ms(trace, context, "flash")
    if not ms:
        return None
    b = bounds_s(context)
    print("INFO " + json.dumps(
        {"flash_roofline_xing_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
