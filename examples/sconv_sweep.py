"""The gated pass of a double-gated short convolution alone
(`horovod_tpu/ops/sconv.py::gated_conv`: u = B * z, three causal taps a
channel, y = G * c) at the LFM2 cell's call ([2, 8192, 6144] bf16 in,
[2, 8192, 2048] out), forward and forward + backward: ms a call and GB/s of
`gate_plan`'s bytes (what a one-pass form moves) against the chip's 819 GB/s.
`examples/kda_sweep.py --conv` is the pattern: several calls a program, each
behind a barrier with taps of its own, every result returned.

Usage (chip only; the examples need PYTHONPATH=/root/repo there):
    python examples/sconv_sweep.py [--B 2 --L 8192 --C 2048 --taps 3]
`--cpu`: tiny shapes, the op held to the three-shifted-copies sum in f32,
nothing timed.
"""

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the timing recipe of the sweep this one follows (its directory is on the
# path of a script run from it): several calls a program, each behind a
# barrier with taps of its own
from kda_sweep import HBM_BYTES_A_S, chained, timed  # noqa: E402

from benchmark.references import lfm2 as reference  # noqa: E402
from horovod_tpu.ops import sconv  # noqa: E402


def shifted_copies(proj, w):
    """The pass as the plain reference writes it, all in f32: an explicit
    sum over shifted copies, a sequence at a time."""
    return jax.vmap(lambda blocks: reference.gated_conv(blocks, w))(
        proj.astype(jnp.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=2)
    ap.add_argument("--L", type=int, default=8192)
    ap.add_argument("--C", type=int, default=2048)
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    B, L, C, taps = (2, 48, 128, 3) if args.cpu \
        else (args.B, args.L, args.C, args.taps)
    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    proj = jax.random.normal(ks[0], (B, L, 3 * C)).astype(bf16)
    w = 0.5 * jax.random.normal(ks[1], (taps, C))
    cot = jax.random.normal(ks[2], (B, L, C)).astype(bf16)

    def with_backward(f):
        def both(proj, w, cot):
            out, vjp = jax.vjp(f, proj, w)
            return out, vjp(cot)
        return both

    got = jax.jit(sconv.gated_conv)(proj, w).astype(jnp.float32)
    want = jax.jit(shifted_copies)(proj, w)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    plan = sconv.gate_plan(B, L, C, taps, bf16)
    row = {"shape": [B, L, 3 * C], "taps": taps, "plan": plan,
           "fwd_rel_err_of_the_f32_sum": err}
    if not args.cpu:
        def ms_and_rate(fn, operands, moved):
            ms = timed(chained(fn, args.chain), operands, args.iters) \
                / args.chain
            rate = moved / ms / 1e6
            return {"ms": ms, "GB/s": rate,
                    "share_of_819": rate / (HBM_BYTES_A_S / 1e9)}

        least = plan["bytes"]
        row["fwd"] = ms_and_rate(sconv.gated_conv, (proj, w),
                                 least["forward"])
        row["fwd_bwd"] = ms_and_rate(
            with_backward(sconv.gated_conv), (proj, w, cot),
            least["forward"] + least["backward"])
        text = jax.jit(with_backward(sconv.gated_conv)).lower(
            proj, w, cot).compile().as_text()
        row["fusions_fwd_bwd"] = text.count(" fusion(")
    print(json.dumps({"sconv": row}), flush=True)


if __name__ == "__main__":
    main()
