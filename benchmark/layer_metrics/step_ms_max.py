"""Longest gap between step completions of the measured window, in ms. With
`step_ms_median` and `window_lost_ms` it says what a window that read low
was: one long wait (`step_ms_max` near `window_lost_ms`), several, or every
step slower (`step_ms_median` up, little lost). Source: host clock
(`run.window_account`)."""


def read(trace, context):
    return context["window"]["max_ms"]
