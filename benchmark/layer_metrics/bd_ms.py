"""Device milliseconds per step under the scope `hvd_bd`, forward and
backward, mean over devices: what block-diffusion training does around the
model (`models.block_diffusion_batch`: the noise draw, the doubled ids,
positions and row weights; `block_diffusion_noisy_half`: the slice before
the head, and in the backward the zeros it puts under the clean half). A
part of `fwd_bwd_ms`. Source: device trace, self time by the program's own
scope (`sdar_reduce.py`); None for a program that names no such scope.

A presence counter more than a cost: it reads 0.01 ms of a 345 ms step (the
slice and its zeros), and XLA fuses the noise draw (2.3 us) into a reshape
of the batch's tokens that carries the PARAMETER's name, outside the scope
(`unscoped_ms` 0.0023). It says the objective's own work is there and stays
negligible; it cannot move `throughput`."""

from benchmark import sdar_reduce


def read(trace, context):
    return sdar_reduce.ms(trace, context, "bd")
