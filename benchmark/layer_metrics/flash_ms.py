"""Device milliseconds per step in the Pallas flash-attention kernels (the
forward and the backward, one kernel or two, of every layer), mean over
devices. Source: device trace: the self time of the events whose
instruction is a custom call with target `tpu_custom_call`; in these
configurations the flash kernels are the program's only Pallas kernels (16
a step at depth 8 with the one-kernel backward)."""

from benchmark import trace_reduce as tr


def is_flash_kernel(event):
    return event.target == "tpu_custom_call"


def read(trace, context):
    ns = tr.mean_over_devices(
        trace, lambda ev: tr.matching_ns(ev, is_flash_kernel))
    if ns == 0:
        return None
    return ns / 1e6 / context["steps_traced"]
