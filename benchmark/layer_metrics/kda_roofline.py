"""Share of its roofline the chunked recurrence reaches, in percent: the
least time the chip could take for the operations the chunked form executes
(`flops_kimi.kda_chunk_forward_flops`, from the SHAPES of the chunked form:
the two score products, the solve's triangle, the three products with the
state and the one with the scores, a chunk and head; a forward, twice that
backward, a forward again where the block is recomputed) or the bytes a
pass must move (`flops_kimi.kda_chunk_min_bytes`: q, k, v, g, beta in and o
out, and their gradients), whichever is larger, over the device time under
`hvd_kda_chunk` + `hvd_kda_carry` (`kda_chunk_ms` + `kda_carry_ms`).
Counted from the shapes alone, so the same whatever implements it (jnp
today): the exponentials, the [sub, sub, D] terms of a sub-block's own
scores, the cumulative sums and the masks are time and no count. Which
bound binds is printed as an INFO line, with both."""

import json

from benchmark import kimi_reduce


def read(trace, context):
    counts, peaks = context["counts"], context["peaks"]
    if "kda_executed_flops" not in counts:
        return None
    parts = [kimi_reduce.ms(trace, context, "kda", scope)
             for scope in ("KDA_CHUNK", "KDA_CARRY")]
    if not all(parts):
        return None
    b = {"operations": counts["kda_executed_flops"]
         / peaks["bf16_flops_per_s"],
         "bytes": counts["kda_min_bytes"] / peaks["hbm_bytes_per_s"]}
    print("INFO " + json.dumps(
        {"kda_roofline_binds": max(b, key=b.get),
         "least_ms": {k: 1e3 * v for k, v in b.items()}}), flush=True)
    return 100.0 * max(b.values()) / (sum(parts) / 1e3)
