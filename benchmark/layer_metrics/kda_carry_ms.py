"""The part of `kda_ms` under `hvd_kda_carry`: the scan over the chunks
(`ops/kda.py`: V' = U - W S, the output, the state's update; L / 64
iterations a layer and direction, one after another). Source: device trace
(`kimi_reduce.py`)."""

from benchmark import kimi_reduce


def read(trace, context):
    return kimi_reduce.ms(trace, context, "kda", "KDA_CARRY")
