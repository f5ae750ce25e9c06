"""Share of their roofline the flash kernels reach under the block-diffusion
mask, in percent: the least time the chip could take for the operations they
EXECUTE (`flops_sdar.flash_executed_flops`: the tiles
`hvd.profile.flash_plan(..., mask=)` says each kernel visits, a cut tile
whole; never L^2 / 2) or the bytes they must move
(`flops_sdar.flash_min_bytes`; the builder's `counts`), whichever is larger,
over the device time they took (`flash_ms.sdar`: by the kernels' own names).
Which bound binds is printed as an INFO line, with both."""

import json

from benchmark import sdar_reduce
from benchmark.layer_metrics.flash_roofline import bounds_s


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    ms = sdar_reduce.ms(trace, context, "flash")
    if not ms:
        return None
    b = bounds_s(context)
    print("INFO " + json.dumps(
        {"flash_roofline_sdar_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
