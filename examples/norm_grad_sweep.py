"""A projection's DATA gradient behind an RMS norm, `dh = dy W^T` with
dy [T, N] bf16, W [C, N] f32 rounded in the program (as `nn.Dense` rounds
it) and dh [T, C] bf16, followed by the norm's backward (dx, and the
scale's gradient: a sum along each row of dh and one across all of them),
at the shapes the benchmark's cells run, four ways:

    bare    the product alone
    fused   the product and the norm's backward as XLA joins them (on the
            v5e both reductions ride in the product's fusion)
    held    the same with `lax.optimization_barrier` on dh between the two
            (`models/transformer.py::_hold_cotangent`, a "conv" layer's)
    pass    the norm's backward alone, dh an operand

ms a call, TFLOP/s of the product's 2 T N C against the chip's 197, and
from the compiled text how many fusions hold a product and how many of
those hold a reduce (`hvd.profile.product_fusions`): a time is trusted
only where the counts are the chain's. `held - fused` says what the same
hold would give a layer of that cell; `held - bare - pass` should be 0.
`examples/kda_sweep.py --conv` is the pattern (ROADMAP D14): several calls
a program, each behind a barrier with a weight and a scale of its own,
every result the program's, N padded to whole lane tiles (10304 -> 10368,
9280 -> 9344, 576 -> 640) and the compiled text read for whether every
operand then lies row-major.

`--joined` makes dy the sum of three column blocks padded to N, as
`ops/sconv.py::gated_conv`'s backward hands the conv mixer's in-projection
its cotangent; `--rounded` takes W in bf16 (no rounding in the program).

Usage (chip only; the examples need PYTHONPATH=/root/repo there):
    python examples/norm_grad_sweep.py [--shapes lfm2_conv ...]
`--cpu`: tiny shapes, every form's error against the plain arithmetic, the
compiled text's counts, nothing timed.
"""

import argparse
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from kda_sweep import timed  # noqa: E402

from horovod_tpu import profile  # noqa: E402

PEAK_FLOPS = 197e12  # benchmark/peaks.json, "TPU v5 lite", bf16
EPS = 1e-5

# name: (T tokens, N the projection's columns, C the width); the cells'
# traffic and their configurations' widths (benchmark/configs/*.json)
SHAPES = {
    "lfm2_conv": (16384, 6144, 2048),      # B | G | z of a conv mixer
    "lfm2_dense": (16384, 14336, 2048),    # gate | up of its dense layers
    "lm1b4_qkv": (4096, 6144, 2048),
    "lm1b4_mlp": (4096, 8192, 2048),
    "laguna_window": (8192, 10304, 2048),  # q | k | v | gate, 64 heads on 8
    "nemo3_ssm": (4096, 9280, 4096),       # a Mamba-2 in-projection
    "kimi_q": (8192, 6144, 2304),          # latent attention, direct q
    "kimi_kv_a": (8192, 576, 2304),
    "kanana_q": (8192, 6144, 2048),
    "kanana_kv_a": (8192, 576, 2048),
}


def rms_norm(x, scale):
    """`flax.linen.RMSNorm` on bf16 x with an f32 scale, to bf16."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + EPS) * scale).astype(x.dtype)


def product(dy, w):
    """dh [T, C] = dy [T, N] W^T, W [C, N] rounded here to dy's dtype."""
    return lax.dot_general(dy, w.astype(dy.dtype), (((1,), (1,)), ((), ())))


def form(name):
    """(x, scale, w, dy) -> that form's results."""
    def run(x, scale, w, dy):
        if name == "pass":  # dy is dh
            return jax.vjp(rms_norm, x, scale)[1](dy)
        dh = product(dy, w)
        if name == "bare":
            return dh
        if name == "held":
            dh = lax.optimization_barrier(dh)
        return jax.vjp(rms_norm, x, scale)[1](dh)
    return run


def joined(blocks):
    """dy [T, 3 C] as `gated_conv`'s backward leaves it: three column
    blocks, each padded with zeros to the whole width, added."""
    C = blocks[0].shape[-1]
    return sum(jnp.pad(b, ((0, 0), (i * C, (2 - i) * C)))
               for i, b in enumerate(blocks))


def chained(fn, n, join):
    """fn n times in one program, each call behind a barrier on the results
    of the one before, with a weight and a scale of its own ((1 + i) w:
    equal calls are one call once XLA has dropped the barriers); every
    call's results are the program's."""
    def run(x, scale, w, dy):
        held = (x, scale, w, dy)
        outs = []
        for i in range(n):
            x, scale, w, dy = held
            out = fn(x, (1.0 + i) * scale, ((1.0 + i) * w).astype(w.dtype),
                     joined(dy) if join else dy)
            held, out = lax.optimization_barrier((held, out))
            outs.append(out)
        return outs
    return jax.jit(run)


def operands(T, N, C, pass_only, join, w_dtype):
    """x [T, C], the scale [C], W [C, N] and dy [T, N] (dh [T, C] for the
    pass alone; three column blocks under `join`)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf16 = jnp.bfloat16
    wide = C if pass_only else N
    if join:
        dy = tuple(jax.random.normal(k, (T, wide // 3)).astype(bf16)
                   for k in jax.random.split(ks[3], 3))
    else:
        dy = jax.random.normal(ks[3], (T, wide)).astype(bf16)
    return (jax.random.normal(ks[0], (T, C)).astype(bf16),
            1.0 + 0.1 * jax.random.normal(ks[1], (C,)),
            (jax.random.normal(ks[2], (C, N)) * C ** -0.5).astype(w_dtype),
            dy)


_PARAMETER_LAYOUT = re.compile(r"\[[0-9,]*\]\{([0-9,]*)")


def counts(text):
    """From a compiled chain's text: the fusions that hold a product, those
    of them that hold a reduce too, and whether every operand of the
    program lies row-major (`main` pads N for that)."""
    found = profile.product_fusions(text)
    layouts = text[text.index("entry_computation_layout={("):]
    layouts = layouts[:layouts.index(")->")]
    return {"products": len(found),
            "with_reduces": sum(bool(f["reduces"]) for f in found.values()),
            "operands_row_major": all(
                m in ("0", "1,0")
                for m in _PARAMETER_LAYOUT.findall(layouts))}


def check(T, N, C):
    """{form: its largest error against the plain arithmetic on f32 copies
    of the bf16 operands}, at a tiny size. The forms need not agree to the
    bit: XLA may keep dh's f32 inside a fusion and cannot across a held
    value."""
    f32 = jnp.float32
    x, scale, w, dy = operands(T, N, C, False, False, f32)
    dh = (dy.astype(f32) @ w.astype(x.dtype).astype(f32).T).astype(x.dtype)
    want = jax.vjp(rms_norm, x, scale)[1](dh)

    def err(got):
        return max(float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
                         / jnp.max(jnp.abs(b.astype(f32))))
                   for a, b in zip(got, want))

    return {"fused": err(jax.jit(form("fused"))(x, scale, w, dy)),
            "held": err(jax.jit(form("held"))(x, scale, w, dy)),
            "pass": err(jax.jit(form("pass"))(x, scale, None, dh))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    forms = ["bare", "fused", "held", "pass"]
    ap.add_argument("--forms", nargs="*", default=forms, choices=forms)
    ap.add_argument("--joined", action="store_true")
    ap.add_argument("--rounded", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    w_dtype = jnp.bfloat16 if args.rounded else jnp.float32
    for name in args.shapes:
        T, N, C = (64, 384, 128) if args.cpu else SHAPES[name]
        # whole lane tiles: a [T, N] made outside a program whose N is no
        # multiple of 128 lies tokens-minor on the chip, and the product
        # would read an operand no model hands it (ROADMAP D14)
        N = -(-N // 128) * 128
        row = {"shape": name, "T_N_C": [T, N, C], "joined": args.joined,
               "w": jnp.dtype(w_dtype).name}
        if args.cpu:
            row["rel_err_of_the_plain_arithmetic"] = check(T, N, C)
        for f in args.forms:
            join = args.joined and f != "pass"
            fn = chained(form(f), args.chain, join)
            ops = operands(T, N, C, f == "pass", join, w_dtype)
            got = counts(fn.lower(*ops).compile().as_text())
            if not args.cpu:
                ms = timed(fn, ops, args.iters) / args.chain
                got["ms"] = ms
                if f != "pass":
                    got["TFLOP/s"] = 2.0 * T * N * C / ms / 1e9
                    got["share_of_197"] = got["TFLOP/s"] * 1e12 / PEAK_FLOPS
                else:
                    # dh and x read, dx written, twice over the rows
                    got["GB/s_of_3_passes"] = 3 * 2.0 * T * C / ms / 1e6
            row[f] = got
        if not args.cpu and {"fused", "held"} <= set(args.forms):
            row["held_minus_fused_ms"] = row["held"]["ms"] \
                - row["fused"]["ms"]
        print(json.dumps({"norm_grad": row}), flush=True)


if __name__ == "__main__":
    main()
