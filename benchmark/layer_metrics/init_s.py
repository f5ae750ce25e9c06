"""Host seconds inside `hvd.init()`: the lock and the `make` before the
native core is loaded, its rendezvous and background thread, and the rest
(the line `INFO setup_s_by_program_span`, which this reader prints, splits
the three). Source: the program's own span `hvd_init` of the running
process (`hvd.profile.phases()`, `setup_reduce.py`); a part of `setup_s`."""

from benchmark import setup_reduce


def read(trace, context):
    setup_reduce.report()
    return setup_reduce.value("init_s")
