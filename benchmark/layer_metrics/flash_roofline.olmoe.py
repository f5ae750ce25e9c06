"""`flash_roofline` for the OLMoE cell: the bounds of `flash_roofline.py`
over the time of the flash kernels taken by their own NAMES, not through
`flash_ms` (which counts every `tpu_custom_call` and would count this
cell's grouped-matmul kernels as flash time): the forward and whichever
backward kernels ran (`hvd_flash_bwd`, or `hvd_flash_dq` +
`hvd_flash_dkv`). Nothing to read where no flash kernel ran, or where the
kernels that ran are not the ones the plan names (`flash_roofline.share`)."""

from benchmark.layer_metrics.flash_roofline import flash_kernels_ms, share


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context,
                 sum(flash_kernels_ms(trace, context).values()),
                 "flash_roofline_olmoe")
