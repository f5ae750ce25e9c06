"""`flash_fwd_ms` for the Kanana cell: the forward kernel `hvd_flash_fwd`
under scores of two products, every layer and every recomputed forward, by
its own name. Source: device trace (`kanana_reduce.py`)."""

from benchmark import kanana_reduce


def read(trace, context):
    return kanana_reduce.ms(trace, context, "flash_fwd")
