"""GiB the allocator reports as its peak on the fullest chip after the
window: `memory_stats()` `peak_bytes_in_use` (arguments, results, the
loop's arrays) plus `peak_bytes_reserved` (what the runtime set aside for
the executable's temporaries, which it counts apart). Kept beside
`peak_hbm_gib`, the executable's own count; see PERF.md."""


def read(trace, context):
    peak = context["memory_stats_peak_bytes"]
    return peak / float(1 << 30) if peak else None
