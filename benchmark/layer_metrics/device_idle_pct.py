"""Percent of the traced window in which no operation ran on a device: 1 -
(union of the device's operation intervals) / (first operation's start to
last operation's end), mean over devices. Source: device trace."""

from benchmark import trace_reduce as tr


def read(trace, context):
    def idle(events):
        start, end = tr.window(events)
        return 100.0 * (1.0 - tr.busy(events) / (end - start))

    return tr.mean_over_devices(trace, idle)
