"""Flash-kernel block-size sweep at a given attention shape, on every
path of the plain kernels.

`flash_plan` (ops/flash_attention.py) sends a kernel down the resident
path (the other sequence whole in VMEM, walked by a loop inside the
kernel) when its whole-sequence operands fit the VMEM budget, and down
the gridded path (one pipeline step a tile) otherwise; where the whole
backward is resident it is ONE kernel (`hvd_flash_bwd`), held by the k
block or, where that does not fit, by the q block; else dQ and dK/dV
apart. This sweeps (block_q, block_k) on each path: `resident` (the one
kernel held by the k block, whatever it holds), `q-held` (the one kernel
held by the q block: k, v, dk, dv whole and dK, dV summed in VMEM),
`split` (the two backward kernels as `flash_plan` chooses them where it
may not take the one: dK/dV resident where it fits) and `gridded`, for
the kernels alone: forward, dQ, dK/dV, and the whole backward (one kernel,
or the sum of the two). A backward kernel whose gradients are unused is
dropped by XLA, so each is timed by itself, with the single-dispatch
lax.scan recipe. The first row of a path is the plan's own blocks. The
block tables (`_resident_blocks`; `_default_blocks` and `_grouped_blocks`
for the gridded path) were read off it.

Usage: python examples/flash_block_sweep.py [--B 2 --L 2048 --H 16 --D 128]
           [--path all|resident|split|q-held|gridded] [--kernels all|bwd]
           [--mask-block N | --window W] [--cut-k 128,256,512]
`--mask-block N`: block-diffusion training's mask by rule in place of the
causal triangle (L counts both copies of the sequence, blocks of N tokens);
the forward and dQ take a rule resident only, so `gridded` then grids dK/dV
alone. The block-diffusion cell's call (`sdar30b_1chip`):
    --B 1 --H 32 --G 4 --L 8192 --mask-block 4 --path q-held,split \
    --kernels bwd --bqp 64,128 --bk 512,1024
`--window W`: the causal band of sliding-window attention (`ops.BandMask`:
a query sees itself and the W - 1 keys before it), a rule likewise. The
window layers' call of `mellum12b_1chip`:
    --B 1 --H 32 --G 4 --L 8192 --window 1024 --path q-held \
    --bqp 64,128,256 --bk 128,256,512,1024
The two calls of `laguna33b_1chip` (a full layer's 48 query heads on 8,
group 6, causal; a window layer's 64 on 8 under a band of ONE k block, every
tile visited cut at an edge; PERF.md s6, PR 62, has the tables):
    --B 1 --H 48 --G 8 --L 8192 --path q-held --bqp 64,128,256 \
    --bk 256,512,1024
    --B 1 --H 64 --G 8 --L 8192 --window 512 --path q-held --bqp 64,128 \
    --bk 256,512 --cut-k 128,256,512
`--cut-k`: under a rule, the widths of the sub-tile that the one-kernel
backward held by the q block takes alone where a cut k block has one in sight
(`flash_attention._CUT_K`; at the k block's own width it walks k blocks
alone), each candidate pair of blocks at each; the forward's time does not
follow it. The table in `_resident_blocks`' docstring is the two cells' calls
at the plan's blocks:
    --B 1 --H 32 --G 4 --L 8192 --mask-block 4 --path q-held \
    --bqp "" --bk "" --cut-k 128,256,512
    --B 1 --H 32 --G 4 --L 8192 --window 1024 --path q-held \
    --bqp "" --bk "" --cut-k 128,256,512
`--D2 N`: scores of TWO products (latent attention: q2 [.., N] on one key a
position for all heads beside q on k; no rule beside it). Every path has the
form since PR 56; held by the q block the whole-sequence operands have one
buffer each. The Kanana cell's call (`kanana30b_1chip`):
    --B 1 --H 32 --L 8192 --D2 64 --path q-held,split --bqp 256,512,1024 \
    --bk 512,1024
`--gate`: the call with a head gate (`flash_attention`'s ``gate``: one f32
scalar a head and position; the forward multiplies its rows' normalisers by
the gates' reciprocals, the backward kernels are the ungated ones on the
gated rows' lse and on delta / gate, which is the gate's gradient and is
timed with them). The two
calls of `laguna33b_1chip` at the plan's blocks, beside PR 62's ungated 8.865
| 13.877 and 5.083 | 5.579 (PERF.md s6, PR 63):
    --B 1 --H 48 --G 8 --L 8192 --path q-held --bqp "" --bk "" --gate
    --B 1 --H 64 --G 8 --L 8192 --window 512 --path q-held --bqp "" \
    --bk "" --gate
GQA/MQA (--G < --H) sweeps the grouped calls (a kv head's query heads the
rows of one tile, head by head): the q-block candidates become bqp*group
rows. The `_grouped_blocks` policy was
tuned from this sweep at two points — B2 H6 G2 L8192 D128 (1536/512)
and B2 H12 G3 L8192 D64 (2048/512; 2048/1024 overflows VMEM) —
grouped layouts want bigger row blocks and bk=512 at long L.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import importlib

from horovod_tpu import profile

# The ops package re-exports the flash_attention FUNCTION under the
# same name; import the module itself for the block-size internals.
fa = importlib.import_module("horovod_tpu.ops.flash_attention")

BWD = profile.FLASH_BWD


def forms(B, H, L, D, group, dtype, rule, D2=0):
    """{path: (`vmem_budget`, the one kernel's held sides `flash_plan` may
    try)} that forces a path: everything fits 2**40, so the order alone
    gives the one kernel by either side; with no side to try, the plan's
    own budget gives the two kernels (`split`), each resident where it
    fits; then gridded (under a rule dK/dV alone: what the forward holds,
    k + v, which dQ holds too and no form of dK/dV fits; else nothing
    fits 0)."""
    fwd = fa.flash_plan(B, H, L, D, group, dtype, vmem_budget=2 ** 40,
                        shared_dim=D2, mask=rule)[profile.FLASH_FWD]
    return {"resident": (2 ** 40, ("k",)), "q-held": (2 ** 40, ("q",)),
            "split": (fa.RESIDENT_VMEM_BUDGET, ()),
            "gridded": (0 if rule is None else fwd.resident_bytes, ())}


def timed(fn, args, iters=30):
    def body(carry, _):
        out = fn(*carry)
        # Cast: fwd returns a bf16 tensor but the backward probes
        # return an f32 scalar, which would promote the carry.
        return (carry[0] + (1e-30 * out).astype(carry[0].dtype),) \
            + carry[1:], ()

    def run(*args):
        carry, _ = lax.scan(body, args, None, length=iters)
        return jnp.sum(carry[0].astype(jnp.float32))

    jitted = jax.jit(run)
    float(jitted(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(*args))
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1]


def total(*grads):
    return sum(jnp.sum(x.astype(jnp.float32)) for x in grads)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=2)
    ap.add_argument("--L", type=int, default=2048)
    ap.add_argument("--H", type=int, default=16)
    ap.add_argument("--G", type=int, default=0,
                    help="kv heads (GQA/MQA; 0 = H, plain MHA). The "
                         "q-block candidates become bqp*group rows in "
                         "the grouped layout")
    ap.add_argument("--D", type=int, default=128)
    ap.add_argument("--D2", type=int, default=0,
                    help="width of a second score product on one key a "
                         "position shared by the heads (latent attention's "
                         "rotary slice); 0: one product")
    ap.add_argument("--path", default="all",
                    help="all, or of resident, split, q-held, gridded, "
                         "with commas")
    ap.add_argument("--mask-block", type=int, default=0,
                    help="block-diffusion's mask by rule over 2 x L/2 "
                         "positions in blocks of this many tokens, in "
                         "place of the causal triangle")
    ap.add_argument("--window", type=int, default=0,
                    help="the causal band by rule: a query sees itself and "
                         "this many keys less one before it, in place of "
                         "the causal triangle")
    ap.add_argument("--cut-k", default="",
                    help="under a rule: widths of the sub-tile the q-held "
                         "backward takes alone, to try in turn, with commas "
                         "(none: the program's own)")
    ap.add_argument("--gate", action="store_true",
                    help="the call with a head gate [B, H, L] f32, applied "
                         "inside the forward kernel")
    ap.add_argument("--kernels", default="all", choices=("all", "bwd"),
                    help="bwd: leave the forward kernel out")
    ap.add_argument("--bqp", default="128,256,512",
                    help="q-block candidates, in positions")
    ap.add_argument("--bk", default="256,512,1024")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()
    B, L, H, D = args.B, args.L, args.H, args.D
    G = args.G or H
    group = H // G
    if args.mask_block and args.window:
        ap.error("--mask-block and --window are one rule each: give one")
    D2 = args.D2
    if D2 and (args.mask_block or args.window or args.gate):
        ap.error("a mask by rule, and a gated call, has one score product: "
                 "--D2, or a rule and --gate")
    rule = (fa.BlockDiffusionMask(L // 2, args.mask_block)
            if args.mask_block else
            fa.BandMask(args.window) if args.window else None)
    causal = rule is None

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, G, L, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, G, L, D), jnp.bfloat16)
    g = jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)
    shared = (jnp.asarray(rng.randn(B, H, L, D2), jnp.bfloat16),
              jnp.asarray(rng.randn(B, 1, L, D2), jnp.bfloat16)) if D2 \
        else None
    # what a gated call hands the kernels beside the rest, and its plan
    gated = {"gate": jax.nn.sigmoid(jnp.asarray(
        rng.randn(B, H, L), jnp.float32))} if args.gate else {}
    gated_plan = {"gate": True} if gated else {}
    scale = (D + D2) ** -0.5
    rows = L * group
    out, lse = jax.jit(lambda q, k, v: fa._pallas_forward_lse(
        q, k, v, scale, causal, False, shared=shared, rule=rule, **gated))(
            q, k, v)

    print("shape B=%d L=%d H=%d G=%d D=%d%s%s (kernel layout, %d rows/slab)"
          % (B, L, H, G, D, " D2=%d" % D2 if D2 else "",
             "" if rule is None else " %r" % (rule,), rows))
    for backward in (False, True):
        for name, plan in fa.flash_plan(B, H, L, D, group, q.dtype,
                                        backward, shared_dim=D2,
                                        mask=rule, **gated_plan).items():
            print("default plan %s: %s" % (name, plan._asdict()))
    by_path = forms(B, H, L, D, group, q.dtype, rule, D2)
    paths = tuple(by_path) if args.path == "all" else tuple(
        args.path.split(","))
    cuts = [int(x) for x in args.cut_k.split(",") if x] or [fa._CUT_K]
    if rule is None and args.cut_k:
        ap.error("--cut-k is of the walk of a rule's cut runs: give a rule")
    print("%9s %6s %6s %6s | %9s %9s %9s %9s" % (
        "path", "bq", "bk", "cut_k", "fwd ms", "dq ms", "dkv ms", "bwd ms"))

    def ms(probe):
        try:
            return timed(probe, (q,), args.iters) * 1e3
        except Exception as e:  # a block table Mosaic refuses
            return "failed: %s" % str(e)[:40]

    def cell(x):
        return "%9s" % x if isinstance(x, str) else "%9.3f" % x

    for path in paths:
        budget, fa._BWD_HELD = by_path[path]
        # The plan's own blocks first, then the candidates.
        candidates = [(None, None)] + [
            (bqp * group, bk)
            for bqp in (int(x) for x in args.bqp.split(",") if x)
            for bk in (int(x) for x in args.bk.split(",") if x)
            if not (rows % (bqp * group) or L % bk or L % bqp)]
        for bq, bk, fa._CUT_K in [c + (cut,) for c in candidates
                                  for cut in cuts]:
            def fwd(q, bq=bq, bk=bk):
                return fa._pallas_forward_lse(
                    q, k, v, scale, causal, False, bq, bk, budget,
                    shared=shared, rule=rule, **gated)[0]

            def bwd(q, bq=bq, bk=bk):
                return fa._pallas_backward(
                    q, k, v, out, lse, g, scale, causal, False, bq, bk,
                    budget, shared=shared, rule=rule, **gated)

            try:
                plan = fa.flash_plan(B, H, L, D, group, q.dtype, True, bq,
                                     bk, budget, D2, rule, **gated_plan)
            except ValueError:  # blocks the rule's length does not take
                continue
            t_fwd = ms(fwd) if args.kernels == "all" else "-"
            if BWD in plan:  # one kernel: its three results at once
                t_dq = t_dkv = "-"
                t_bwd = ms(lambda q: total(*bwd(q)))
            else:
                if path == "resident" and bq is not None:
                    continue  # blocks that do not tile: the split row's
                # (dq, dk, dv) and, under a second product, (dq2, dk2);
                # gated, (dq, dk, dv, dgate): the gate's gradient with dQ
                t_dq = ms(lambda q: total(*bwd(q)[0::3]))
                t_dkv = ms(lambda q: total(*[
                    x for i, x in enumerate(bwd(q)) if i % 3]))
                t_bwd = (t_dq + t_dkv if isinstance(t_dq, float)
                         and isinstance(t_dkv, float) else "-")
            print("%9s %6s %6s %6s | %s" % (
                path, bq or "plan", bk or "plan",
                plan[BWD].cut_k if BWD in plan and rule is not None else "-",
                " ".join(cell(x) for x in (t_fwd, t_dq, t_dkv, t_bwd))),
                flush=True)


if __name__ == "__main__":
    main()
