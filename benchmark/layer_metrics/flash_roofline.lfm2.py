"""Share of their roofline the flash kernels reach in the LFM2 cell's two
attention layers, in percent: the least time the chip could take for the
operations they EXECUTE (`flops_lfm2.flash_executed_flops`: the tiles
`hvd.profile.flash_plan` says each kernel visits under the causal triangle
at 32 heads, group 4, head width 64, a cut tile whole) or the bytes they
must move (`flops_lfm2.flash_min_bytes`; the builder's
`counts["flash_by_kind"]`), whichever is larger, over `flash_ms.lfm2`.
Nothing to read, and an INFO `flash_full_roofline_not_read` line, where the
kernels the trace holds are not the plan's (`flash_roofline.share`)."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.flash_roofline(trace, context, "full")
