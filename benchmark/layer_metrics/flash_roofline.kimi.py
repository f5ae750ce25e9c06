"""Share of their roofline the flash kernels reach in the Kimi-Linear
cell's two latent layers, in percent: `flops_kanana`'s counts at this
cell's shapes (the kernels `hvd.profile.flash_plan(..., shared_dim=)` names,
the builder's `counts`) over `flash_ms.kimi`, through
`flash_roofline.share` (nothing to read where the kernels the trace holds
are not the plan's)."""

from benchmark import kimi_reduce
from benchmark.layer_metrics.flash_roofline import share


def read(trace, context):
    if "flash_executed_flops" not in context["counts"]:
        return None
    return share(trace, context, kimi_reduce.ms(trace, context, "flash"),
                 "flash_roofline_kimi")
