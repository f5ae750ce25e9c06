"""Share of their roofline the FULL layers' flash kernels reach, in percent:
the least time the chip could take for the operations they EXECUTE
(`flops_mellum.flash_executed_flops`: the tiles
`hvd.profile.flash_plan(..., mask=)` says each kernel visits under the
kind's mask, a cut tile whole; never L^2 / 2) or the bytes they must move
(`flops_mellum.flash_min_bytes`; the builder's `counts["flash_by_kind"]`),
whichever is larger, over the device time they took (`flash_full_ms`).
Nothing to read where the kernels the trace holds are not the plan's
(`flash_roofline.share`). Which bound binds is printed as an INFO line,
with both."""

from benchmark import mellum_reduce


def read(trace, context):
    return mellum_reduce.flash_roofline(trace, context, "full")
