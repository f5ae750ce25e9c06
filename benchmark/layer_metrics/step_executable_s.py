"""Host seconds from the train step's MLIR module to its executable: XLA's
compile where the persistent cache misses, the cache's retrieval and the
deserialisation where it hits (the line `INFO setup_s_by_program_span` says
which, with `retrieval_s`). Source: the runtime's span `jax_compile` of the
step's function in the running process (`hvd.profile.phases()`,
`setup_reduce.py`); a part of `setup_s`."""

from benchmark import setup_reduce


def read(trace, context):
    return setup_reduce.value("step_executable_s")
