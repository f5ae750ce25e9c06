"""Host seconds from the train step's Python to its MLIR module: the
runtime's trace of the step's function (the traces of the jitted functions
it calls lie inside) plus the lowering of the jaxpr. What Python-unrolled
depth costs, paid with a warm compile cache as with a cold one. Source: the
runtime's spans `jax_trace` and `jax_lower` of the step's function in the
running process (`hvd.profile.phases()`, `setup_reduce.py`); a part of
`setup_s`."""

from benchmark import setup_reduce


def read(trace, context):
    return setup_reduce.value("step_lower_s")
