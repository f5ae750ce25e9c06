"""Milliseconds per step with a collective (all-reduce, reduce-scatter,
all-gather, collective-permute, all-to-all) in flight on a device, mean
over devices. Source: device trace."""

from benchmark import trace_reduce as tr


def read(trace, context):
    ns = tr.mean_collective_ns(trace)
    return None if ns is None else ns[0] / 1e6 / context["steps_traced"]
