"""Median gap between step completions of the measured window, in ms: the
pace of the steps themselves, which a few late waits of the host do not
move (`step_ms_p95` and `throughput` see those). Source: host clock, the
benchmark's own loop (`run.window_account`)."""


def read(trace, context):
    return context["window"]["step_ms_median"]
