"""Milliseconds the measured window lost: its length less its steps at its
own median gap, which is what `throughput` lost to anything but the steps'
usual pace (a short gap after a late one gives the time back, so it can
read a little under 0). `throughput` and `step_ms_p95` are over the whole
window whatever this reads; it says of a low one why it was low. Source:
host clock (`run.window_account`)."""


def read(trace, context):
    return context["window"]["lost_ms"]
