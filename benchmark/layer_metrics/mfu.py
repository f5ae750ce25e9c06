"""Model FLOP/s utilisation in percent: the operations the forward and
backward passes require per item (analytic, `flops.py`; recomputation not
counted) times items per second per chip in the untraced burst, over the
chip's published bf16 peak."""


def read(trace, context):
    per_item = context["counts"].get("model_flops_per_item")
    if per_item is None:
        return None
    return (100.0 * per_item * context["throughput"]
            / context["peaks"]["bf16_flops_per_s"])
