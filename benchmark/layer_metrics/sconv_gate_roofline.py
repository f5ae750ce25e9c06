"""Share of its roofline the gated pass of the conv mixers reaches, in
percent: the least time the chip could take for the bytes a ONE-PASS form
moves over a step (`flops_lfm2.gate_step_min_bytes`: 8 bytes a token and
channel forward, 14 backward, a forward again where a block is recomputed;
the builder's `counts`) at the peak bandwidth in `peaks.json`, over the
device time under `hvd_sconv_gate` (`sconv_gate_ms`). Counted from the
shapes alone, so the same whatever implements the pass (XLA's fusions of the
jnp form today). The bound is the bytes: the pass has a few multiplies a
channel and no matmul."""

from benchmark import lfm2_reduce


def read(trace, context):
    return lfm2_reduce.gate_roofline(trace, context)
