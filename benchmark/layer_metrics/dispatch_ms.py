"""Host milliseconds for one `step(...)` call to return (the program's
host side: argument flattening, sharding checks, enqueue), median over the
untraced burst. Source: host clock, benchmark's own loop."""

from statistics import median


def read(trace, context):
    samples = context["dispatch_s"]
    return 1e3 * median(samples) if samples else None
