"""Share of their roofline the flash kernels reach under the block-diffusion
mask, in percent: the least time the chip could take for the operations they
EXECUTE (`flops_sdar.flash_executed_flops`: the tiles
`hvd.profile.flash_plan(..., mask=)` says each kernel visits, a cut tile
whole; never L^2 / 2) or the bytes they must move
(`flops_sdar.flash_min_bytes`; the builder's `counts`), whichever is larger,
over the device time they took (`flash_ms.sdar`: by the kernels' own names).
Nothing to read where the kernels the trace holds are not the plan's
(`flash_roofline.share`). Which bound binds is printed as an INFO line,
with both."""

from benchmark import sdar_reduce
from benchmark.layer_metrics.flash_roofline import share


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context, sdar_reduce.ms(trace, context, "flash"),
                 "flash_roofline_sdar")
