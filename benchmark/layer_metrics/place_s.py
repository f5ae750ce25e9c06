"""Host seconds inside `step.place`, summed over its calls: the host side
of putting the parameters, the optimizer state and the batch on the device
(the transfers finish under the benchmark's own `block_until_ready`).
Source: the program's own span `hvd_place` of the running process
(`hvd.profile.phases()`, `setup_reduce.py`); a part of `setup_s`."""

from benchmark import setup_reduce


def read(trace, context):
    return setup_reduce.value("place_s")
