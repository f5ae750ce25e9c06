"""Share of their roofline the flash kernels reach under scores of two
products at 8192 positions, in percent: the least time the chip could take
for the operations they EXECUTE (`flops_kanana.flash_executed_flops`: the
score tiles the kernels `hvd.profile.flash_plan(..., shared_dim=)` names
compute at its blocks, a tile the diagonal cuts whole, both products, v 128
wide) or the bytes they must move (`flops_kanana.flash_min_bytes`; the
builder's `counts`), whichever is larger, over the device time they took
(`flash_ms.kanana`: by the kernels' own names). Nothing to read where the
kernels the trace holds are not the plan's (`flash_roofline.share`). Which
bound binds is printed as an INFO line, with both."""

from benchmark import kanana_reduce
from benchmark.layer_metrics.flash_roofline import share


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context, kanana_reduce.ms(trace, context, "flash"),
                 "flash_roofline_kanana")
