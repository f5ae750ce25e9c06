"""Share of its roofline the chunked scan reaches, in percent: the least
time the chip could take for the operations the chunked form executes
(`flops_nemo3.ssd_forward_flops`: the four products of every chunk, whole;
a forward, twice that backward, a forward again where the layer is
recomputed) or the bytes a scan must move (`flops_nemo3.ssd_min_bytes`: x,
dt, B, C in and y out, and their gradients), whichever is larger, over the
device time under `hvd_ssd` (`ssd_ms`). Counted from the shapes alone, so
the same whatever implements the scan (jnp today: the decays, cumulative
sums and masks it passes over are time and no count). Which bound binds is
printed as an INFO line, with both."""

import json

from benchmark.layer_metrics import ssd_ms


def read(trace, context):
    counts, peaks = context["counts"], context["peaks"]
    if "ssd_executed_flops" not in counts:
        return None
    ms = ssd_ms.read(trace, context)
    if not ms:
        return None
    b = {"operations": counts["ssd_executed_flops"]
         / peaks["bf16_flops_per_s"],
         "bytes": counts["ssd_min_bytes"] / peaks["hbm_bytes_per_s"]}
    print("INFO " + json.dumps(
        {"ssd_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (ms / 1e3)
