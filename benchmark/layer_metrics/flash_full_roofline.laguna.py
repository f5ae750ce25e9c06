"""`flash_full_roofline` for the Laguna cell: the least time the chip could
take for the operations the full layers' flash kernels EXECUTE
(`flops_laguna.flash_executed_flops`: the tiles `hvd.profile.flash_plan`
says each kernel visits under the causal triangle at 48 heads, group 6, a
cut tile whole) or the bytes they must move (`flops_laguna.flash_min_bytes`
at 48 heads; the builder's `counts["flash_by_kind"]`), whichever is larger,
over `flash_full_ms.laguna`. Nothing to read, and an INFO
`flash_full_roofline_not_read` line, where the kernels the trace holds are
not the plan's (`flash_roofline.share`)."""

from benchmark import laguna_reduce


def read(trace, context):
    return laguna_reduce.flash_roofline(trace, context, "full")
