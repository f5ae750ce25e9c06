"""Share of their roofline the flash kernels reach at a head group of 16, in
percent: the least time the chip could take for the operations they execute
or the bytes they must move (`flops.flash_executed_flops`,
`flops.flash_min_bytes`, by the kernels `flash_plan` names for the cell's
shapes: the builder's `counts`), whichever is larger, over the device time
they took (`flash_ms.nemo3`: by the kernels' own names). Nothing to read
where the kernels the trace holds are not the plan's
(`flash_roofline.share`). Which bound binds is printed as an INFO line,
with both."""

from benchmark import nemo3_reduce
from benchmark.layer_metrics.flash_roofline import share


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context, nemo3_reduce.ms(trace, context, "flash"),
                 "flash_roofline_nemo3")
