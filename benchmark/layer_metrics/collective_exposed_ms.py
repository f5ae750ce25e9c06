"""The part of `collective_ms` during which no other operation ran on that
device: communication the backward pass did not hide. Source: device
trace."""

from benchmark import trace_reduce as tr


def read(trace, context):
    ns = tr.mean_collective_ns(trace)
    return None if ns is None else ns[1] / 1e6 / context["steps_traced"]
