"""The chunked Kimi Delta Attention recurrence by part, timed on the chip.

`ops/kda.py::kda_chunked` at one benchmark layer's shape ([1, 8192, 32, 128],
bf16 operands, f32 decays; `benchmark/configs/kimilinear48b_a3b_w2304.json`):
the whole call forward and forward + backward; the two Pallas kernels of a
sub-block's own decayed scores (`own_block_scores`) at each `--blocks` x
`--groups` (sub-blocks a grid step, sub-blocks its loop holds at a time)
against the jnp form where that fits; the unit-lower-triangular solve alone;
and the chunk stage's kernels alone (the own blocks' two in the model's
layout; `hvd_kda_wy` as the primal call and as the rule's forward, which also
saves; `hvd_kda_wy_bwd`) at each `--chunks` (chunks a grid step,
`kda.BLOCK_CHUNKS`) and `--side` (chunks a loop iteration, `kda.SIDE`); and
the scan over the chunks alone, forward and with its backward, `lax.scan` in
jnp against the two kernels (`hvd_kda_scan` as the primal call and as the
rule's forward, which also saves the states; `hvd_kda_scan_bwd`) at each
`--heads` (heads a grid step, `kda.SCAN_HEADS`), ms a call beside the least
the bytes allow. Each kernel form is held to the jnp one before it is timed.

With `--conv`, that part alone: the mixer's short convolutions with SiLU and
the head norms (`ops/kda_conv.py::kda_qkv`) at the layer's `proj`
[1, 8192, 12576 padded to 99 lane tiles], the kernel pair (`hvd_kda_qkv`, `hvd_kda_qkv_bwd`) at each
`--rows` x `--lanes` x `--chunk-rows` x `--unroll` (`kda_conv.BLOCK_ROWS`,
`BLOCK_LANES`, `CHUNK_ROWS`, `CHUNK_UNROLL`) against the jnp pair, forward and
forward + backward, ms a call and GB/s of `conv_plan`'s bytes against the
chip's 819; `--chain` calls a program, each behind a barrier on the one
before and with taps of its own (one call a program is under the host's
0.19 ms a dispatch).

Usage: python examples/kda_sweep.py [--blocks 32 64 128] [--groups 1 2 4 8]
       [--chunks 4 8 16] [--side 2] [--heads 8 16 32] [--iters 10] [--cpu]
       (--cpu: tiny shapes, the interpreter, no times; an option with no
       value skips its part)
       python examples/kda_sweep.py --conv [--rows 512 1024] [--lanes 512]
       [--chunk-rows 32 64] [--unroll 1 2] [--chain 4] [--iters 5] [--cpu]
"""

import argparse
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.ops import kda, kda_conv  # noqa: E402


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def worst(got, ref):
    """The largest error of any array of `got`, relative to the largest
    entry of its twin in `ref`."""
    f32 = jnp.float32
    return max(
        float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
              / jnp.maximum(jnp.max(jnp.abs(b.astype(f32))), 1e-30))
        for a, b in zip(got, ref))


def chunk_stage(args, inputs, interpret):
    """The chunk stage's kernels alone by chunks a grid step, each held to
    the jnp form (every result; the five gradients of a scalar of them)."""
    q, k, v, g, beta = inputs
    B, L, H, D = q.shape
    C, sub = 64, 16
    nc = L // C
    *flat, pq, pk = kda._chunk_stage_operands(q, k, v, g, beta, C, sub,
                                              bool(interpret))
    own, own_of = (pq, pk), (flat[0], flat[1], flat[3])
    own_block = kda.own_plan(L // sub, sub, D, interpret)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)

    def in_jnp(*a):  # the jnp form's results laid as the kernels write them
        *four, keep = kda._chunk_stage_jnp(*a, C, sub, interpret)
        return tuple(jnp.moveaxis(t, 2, 0) for t in four) + (keep[..., 0],)

    want = jax.jit(in_jnp)(*inputs)
    cot = tuple(jax.random.normal(kk, t.shape).astype(t.dtype)
                for kk, t in zip(ks, want))

    def scalar(results):
        return sum(jnp.sum(r.astype(jnp.float32) * c.astype(jnp.float32))
                   for r, c in zip(results, cot))

    want_grad = jax.jit(jax.grad(lambda *a: scalar(in_jnp(*a)),
                                 argnums=(0, 1, 2, 3, 4)))(*inputs)
    rows = []
    if not args.cpu and args.chunks:
        rows.append({"own_blocks_ms": timed(
            lambda *a: kda._pallas_own(*a, None, own_block, False, H, sub),
            own_of, args.iters),
            "own_blocks_bwd_ms": timed(
                lambda *a: kda._pallas_own(*a[:3], a[3], own_block, False,
                                           H, sub),
                own_of + (own,), args.iters)})
        print(json.dumps(rows[-1]), flush=True)
    for block in args.chunks:
        if nc % block or block % kda.SIDE or (block % 8 and block != nc):
            continue

        def whole(*a):
            return kda._chunk_stage_kernels(*a, C, sub, block,
                                            bool(interpret))

        def primal(*a):
            return kda._pallas_wy(*a, own, None, None, C, sub, block, False,
                                  bool(interpret))

        def saving(*a):
            return kda._pallas_wy(*a, own, None, None, C, sub, block, True,
                                  bool(interpret))

        def backward(*a):
            return kda._pallas_wy(*a[:5], None, a[5], a[6], C, sub, block,
                                  False, bool(interpret))

        got = whole(*inputs)
        got_grad = jax.grad(lambda *a: scalar(whole(*a)),
                            argnums=(0, 1, 2, 3, 4))(*inputs)
        row = {"chunks": block, "side": kda.SIDE,
               "fwd_rel_err": worst(got, want),
               "bwd_rel_err": worst(got_grad, want_grad)}
        if not args.cpu:
            saved = saving(*flat)[5:]
            row["wy_ms"] = timed(primal, flat, args.iters)
            row["wy_saving_ms"] = timed(saving, flat, args.iters)
            row["wy_bwd_ms"] = timed(backward, flat + [cot, tuple(saved)],
                                     args.iters)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


HBM_BYTES_A_S = 819e9  # benchmark/peaks.json, "TPU v5 lite"


def scan_alone(args, inputs, interpret):
    """The scan over the chunks alone by heads a grid step: `lax.scan` in jnp
    and the kernels, each forward and forward + backward (the five operands'
    cotangents from a fixed cotangent of o), the kernels one at a time too;
    the kernels held to the jnp scan; ms beside the least by bytes (every
    operand read once, every result written once)."""
    B, L, H, D = inputs[0].shape
    steps = kda._chunk_stage_kernels(
        *inputs, 64, 16, kda.chunk_plan(B, L, H, D, D, 64, 16, interpret),
        bool(interpret))
    do = jax.random.normal(jax.random.PRNGKey(2), (B, L, H, D))
    nbytes = lambda ts: sum(t.size * t.dtype.itemsize for t in ts)  # noqa
    states = 4 * B * H * D * D * (L // 64)
    least = {"fwd": nbytes(steps) + nbytes([do]),
             "bwd": 2 * nbytes(steps) + nbytes([do]) + states}
    least["saving"] = least["fwd"] + states
    least_ms = {k: 1e3 * v / HBM_BYTES_A_S for k, v in least.items()}

    def in_jnp(*s):
        return kda._scan_jnp(*s[:4], jnp.moveaxis(s[4], 2, 0)[..., None])[0]

    def with_backward(f):  # the last argument: o's cotangent
        def both(*s):
            out, vjp = jax.vjp(f, *s[:-1])
            return out, vjp(s[-1])
        return jax.jit(both)

    want = with_backward(in_jnp)(*steps, do)
    rows = []
    if not args.cpu and args.heads:
        rows.append({"scan": "jnp", "least_ms": least_ms,
                     "fwd_ms": timed(jax.jit(in_jnp), steps, args.iters),
                     "fwd_bwd_ms": timed(with_backward(in_jnp),
                                         tuple(steps) + (do,), args.iters)})
        print(json.dumps(rows[-1]), flush=True)
    for heads in args.heads:
        # a block of o [B, L, H, Dv] is a tile's eight sublanes, or all heads
        if H % heads or (not args.cpu and heads % 8 and heads != H):
            continue

        def kernels(*s):
            return kda._scan_kernels(*s, heads, bool(interpret))[0]

        def one(save):
            return lambda *s: kda._pallas_scan(
                *s, None, None, heads, save, bool(interpret))

        got = with_backward(kernels)(*steps, do)
        row = {"scan": "kernels", "heads": heads,
               "fwd_rel_err": worst(got[:1], want[:1]),
               "bwd_rel_err": worst(got[1], want[1])}
        if not args.cpu:
            saved = one(True)(*steps)[3]
            cot = (do, jnp.zeros((B, H, D, D), jnp.float32))
            row.update(
                fwd_ms=timed(jax.jit(kernels), steps, args.iters),
                fwd_bwd_ms=timed(with_backward(kernels),
                                 tuple(steps) + (do,), args.iters),
                scan_ms=timed(one(False), steps, args.iters),
                scan_saving_ms=timed(one(True), steps, args.iters),
                scan_bwd_ms=timed(
                    lambda *s: kda._pallas_scan(*s[:5], s[5], s[6], heads,
                                                False, False),
                    tuple(steps) + (cot, saved), args.iters))
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def chained(fn, n):
    """fn(proj, w, *rest) n times in one program, each call behind a barrier
    on the results of the one before and with taps of its own (w, 2 w, ...:
    equal calls are one call once XLA has dropped the barriers); every
    call's results are the program's."""
    def run(proj, w, *rest):
        outs = []
        for i in range(n):
            out = fn(proj, (1.0 + i) * w, *rest)
            (proj, w, rest), out = lax.optimization_barrier(
                ((proj, w, rest), out))
            outs.append(out)
        return outs
    return jax.jit(run)


def conv_sweep(args):
    """The short convolutions with SiLU and the head norms: the jnp pair,
    then the kernel pair by block, each held to the jnp one."""
    B, L, H, D, taps = (2, 128, 2, 128, 4) if args.cpu \
        else (1, 8192, 32, 128, 4)
    interpret = True if args.cpu else None
    inner, bf16 = H * D, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    # the layer's 3 H D + 2 D + H = 12576 columns as the chip lays them out
    # behind the in-projection's matmul, padded to whole lane tiles: an
    # array of 12576 columns made outside a program lies tokens-minor (no
    # padding that way) and every form here would start with a copy of it
    width = -(-(3 * inner + 2 * D + H) // 128) * 128
    proj = jax.random.normal(ks[0], (B, L, width)).astype(bf16)
    w = 0.5 * jax.random.normal(ks[1], (taps, 3 * inner))
    cot = tuple(jax.random.normal(kk, (B, L, inner)).astype(bf16)
                for kk in ks[2:])

    def with_backward(f):
        def both(proj, w, *cot):
            out, vjp = jax.vjp(f, proj, w)
            return out, vjp(cot)
        return both

    def in_jnp(proj, w):
        return kda_conv._qkv_jnp(proj, w, H, D)

    def kernels(proj, w):
        return kda_conv.kda_qkv(proj, w, H, D, interpret)

    def ms_and_rate(fn, operands, moved):
        ms = timed(chained(fn, args.chain), operands, args.iters) \
            / args.chain
        rate = moved / ms / 1e6
        return {"ms": ms, "GB/s": rate,
                "share_of_819": rate / (HBM_BYTES_A_S / 1e9)}

    want = jax.jit(with_backward(in_jnp))(proj, w, *cot)
    rows = []
    least = kda_conv.conv_plan(B, L, H, D, taps, bf16, interpret)["bytes"]
    if not args.cpu:
        rows.append({"conv": "jnp", "least_bytes": least,
                     "fwd": ms_and_rate(in_jnp, (proj, w), least["forward"]),
                     "fwd_bwd": ms_and_rate(
                         with_backward(in_jnp), (proj, w) + cot,
                         least["forward"] + least["backward"])})
        print(json.dumps(rows[-1]), flush=True)
    for block_rows, lanes, chunk_rows, unroll in itertools.product(
            args.rows, args.lanes, args.chunk_rows, args.unroll):
        kda_conv.BLOCK_ROWS, kda_conv.BLOCK_LANES, \
            kda_conv.CHUNK_ROWS, kda_conv.CHUNK_UNROLL = \
            block_rows, lanes, chunk_rows, unroll
        plan = kda_conv.conv_plan(B, L, H, D, taps, bf16, interpret)
        if plan["path"] != "kernel":
            continue
        got = jax.jit(with_backward(kernels))(proj, w, *cot)
        row = {"conv": "kernels", "plan": plan, "unroll": unroll,
               "fwd_rel_err": worst(got[0], want[0]),
               "bwd_rel_err": worst(got[1], want[1])}
        if not args.cpu:
            blocks = kda_conv._blocks(L, H, D, taps, interpret)
            moved = plan["bytes"]
            row.update(
                fwd=ms_and_rate(kernels, (proj, w), moved["forward"]),
                bwd=ms_and_rate(
                    lambda proj, w, *cot: kda_conv._pallas_qkv(
                        proj, w, cot, H, D, blocks, False),
                    (proj, w) + cot, moved["backward"]),
                fwd_bwd=ms_and_rate(
                    with_backward(kernels), (proj, w) + cot,
                    moved["forward"] + moved["backward"]))
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, nargs="*", default=[32, 64, 128])
    ap.add_argument("--groups", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--chunks", type=int, nargs="*", default=[4, 8, 16])
    ap.add_argument("--side", type=int, default=kda.SIDE)
    ap.add_argument("--heads", type=int, nargs="*", default=[8, 16, 32])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--conv", action="store_true")
    ap.add_argument("--rows", type=int, nargs="*",
                    default=[kda_conv.BLOCK_ROWS])
    ap.add_argument("--lanes", type=int, nargs="*",
                    default=[kda_conv.BLOCK_LANES])
    ap.add_argument("--chunk-rows", type=int, nargs="*",
                    default=[kda_conv.CHUNK_ROWS])
    ap.add_argument("--unroll", type=int, nargs="*",
                    default=[kda_conv.CHUNK_UNROLL])
    ap.add_argument("--chain", type=int, default=4)
    args = ap.parse_args()
    kda.SIDE = args.side
    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("kda_sweep: needs a TPU (or --cpu for the forms "
                         "alone)")
    if args.conv:
        print(json.dumps({"conv": conv_sweep(args)}), flush=True)
        return
    B, L, H, D = (1, 256, 2, 128) if args.cpu else (1, 8192, 32, 128)
    interpret = True if args.cpu else None
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = (unit(jax.random.normal(ks[0], (B, L, H, D))) * D ** -0.5).astype(
        jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (B, L, H, D))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, L, H, D)).astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, L, H, D), minval=-7.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    out = {"shape": [B, L, H, D]}

    def whole(*a):  # q, k, v, g as the mixer has them: [B, L, H D]
        return kda.kda_chunked(*(t.reshape(B, L, H, D) for t in a[:4]),
                               a[4], interpret=interpret)[0]

    def whole_grad(*a):
        return jax.grad(lambda *b: jnp.sum(jnp.square(whole(*b))),
                        argnums=(0, 1, 2, 3, 4))(*a)

    if not args.cpu:
        flat = tuple(t.reshape(B, L, H * D) for t in (q, k, v, g)) + (beta,)
        out["kda_chunked_fwd_ms"] = timed(jax.jit(whole), flat, args.iters)
        out["kda_chunked_fwd_bwd_ms"] = timed(jax.jit(whole_grad), flat,
                                              args.iters)
        print(json.dumps(out), flush=True)
    # the own-block kernels alone, on the layer's sub-blocks
    sub, N = 16, B * H * L // 16
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(N, sub, D)  # noqa: E731
    G = jnp.cumsum(flat(g), axis=1)
    fq, fk = flat(q), flat(k)
    cot = tuple(jax.random.normal(kk, (N, sub, sub)) for kk in ks[5:7])
    want = kda._own_jnp(fq[:64], fk[:64], G[:64])
    want_grad = jax.grad(lambda a, b, c: sum(
        jnp.sum(r * t[:64]) for r, t in zip(kda._own_jnp(a, b, c), cot)),
        argnums=(0, 1, 2))(fq[:64].astype(jnp.float32),
                           fk[:64].astype(jnp.float32), G[:64])
    rows = []
    for block in args.blocks:
        for group in args.groups:
            if block % group or N % block:
                continue
            kda.BLOCK_SUBS, kda.GROUP = block, group
            jax.clear_caches()

            def fwd(a, b, c):
                return kda.own_block_scores(a, b, c, interpret)

            def bwd(a, b, c):
                return jax.grad(lambda *x: sum(
                    jnp.sum(r * t[:x[0].shape[0]])
                    for r, t in zip(fwd(*x), cot)), argnums=(0, 1, 2))(a, b, c)

            got = fwd(fq[:64], fk[:64], G[:64])
            got_grad = bwd(fq[:64], fk[:64], G[:64])
            err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
            gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                             / jnp.max(jnp.abs(b)))
                       for a, b in zip(got_grad, want_grad))
            row = {"block": block, "group": group, "fwd_err": err,
                   "bwd_rel_err": gerr}
            if not args.cpu:
                row["fwd_ms"] = timed(jax.jit(fwd), (fq, fk, G), args.iters)
                row["fwd_bwd_ms"] = timed(jax.jit(bwd), (fq, fk, G),
                                          args.iters)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out["own_block_scores"] = rows
    out["chunk_stage"] = chunk_stage(args, (q, k, v, g, beta), interpret)
    out["scan"] = scan_alone(args, (q, k, v, g, beta), interpret)
    if not args.cpu:
        C = 64
        a = jnp.tril(jax.random.normal(ks[5], (B, H, L // C, C, C)), -1) \
            * 0.1 + jnp.eye(C)
        rhs = jax.random.normal(ks[6], (B, H, L // C, C, 2 * D))
        out["solve_ms"] = timed(jax.jit(lambda a, b: lax.linalg.triangular_solve(
            a, b, left_side=True, lower=True, unit_diagonal=True)),
            (a, rhs), args.iters)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
