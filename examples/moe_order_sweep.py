"""How a routed layer that holds a part of the experts should form the sorted
order of its rows: the candidates, timed on the chip.

`parallel/expert.py::moe_ffn` with ``held=(first, count)`` reads only the run
of the `count` held experts' rows out of the k*T assignments' sorted order.
Until PR 55 it sorted them all, twice (`sort_assignments`: an argsort and a
second to invert it), turned the run to the front with two gathers and left
`order % T` and `weights.reshape(-1)[order]` to the row ops: all of it XLA's
work on [k*T] vectors, whatever is live. This times what goes from the
router's choice (`experts` [T, k], `weights` [k, T]) to what the row kernels
take (the token and the weight at each sorted position of the live front,
the sorted position of each assignment, the held sizes) in each form:

- ``parent``: the two argsorts and the rotation.
- the position of an assignment by COUNTING over the `count` bins held (its
  bin's start + how many of the bin came before it; anything behind every
  row where no held expert takes it), the running count three ways
  (``cumsum``: `jnp.cumsum`; ``tri-bf16`` / ``tri-int8``: a triangular
  matmul a block of `--block` assignments and a short sum over the blocks),
  and then, for the order of the live front:
  - ``argsort``: one stable argsort of a key that puts dead assignments
    last, `% T` and the weights' gather behind it;
  - ``sort3``: the same sort carrying the token and the weight with it, so
    that no gather is left;
  - ``sort1``: ONE operand sorted, a key that is unique and holds its
    assignment (bin * k*T + a), `% T` and the weights' gather behind it;
  - ``sort2``: that key carrying the weight;
  - ``scatter``: no sort, the token and the weight scattered to the counted
    positions;
  - ``compact``: the live assignments compacted first (`jnp.nonzero` with
    the static bound), a sort of that many keys.
- ``library``: what `moe_ffn` runs (`expert.held_order`).

And the way back, the weights' gradient from the live front's products into
the assignments' order: ``gather`` (``dots[inv]``, the parent's) against
``sort`` (the products sorted by the assignment each position holds), each
with what forms the order and `inv` before it (``prepare-only``: that
alone).

Each form is held to the parent's on the live front before it is timed. A
call is a fraction of a millisecond, under the host's cost of issuing one:
a timed program runs `CHAIN` routings one after the other (`lax.map`).

Usage: python examples/moe_order_sweep.py [--block 128 256 512] [--iters 20]
"""

import argparse
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

from horovod_tpu.parallel import expert  # noqa: E402

# (T, k, E, first, count): the routed layers of the benchmark's cells that
# hold a part of their experts.
CASES = {"sdar_65536_16of128": (8192, 8, 128, 0, 16),
         "mellum_65536_16of64": (8192, 8, 64, 16, 16),
         "nemo3_90112_8of512": (4096, 22, 512, 8, 8),
         "xing_16384_8of64": (4096, 4, 64, 0, 8)}

CHAIN = 8  # routings a timed program forms, one after the other


def parent(experts, weights, E, first, count):
    T, k = experts.shape
    kT = k * T
    _, order, inv, group_sizes = expert.sort_assignments(experts, E)
    bound = min(kT, count * T)
    start = jnp.sum(group_sizes[:first])
    sizes = group_sizes[first:first + count]
    order = order[(jnp.arange(bound, dtype=jnp.int32) + start) % kT]
    inv = (inv - start) % kT
    return (order % T, weights.reshape(-1)[order], inv, sizes,
            jnp.sum(sizes))


def running_count(hit, how, block):
    """hit [count, kT] bool -> the inclusive running count along each row,
    int32."""
    count, kT = hit.shape
    if how == "cumsum":
        return jnp.cumsum(hit.astype(jnp.int32), axis=1)
    dtype, acc = {"tri-bf16": (jnp.bfloat16, jnp.float32),
                  "tri-int8": (jnp.int8, jnp.int32)}[how]
    upper = jnp.triu(jnp.ones((block, block), dtype))
    inside = lax.dot(hit.reshape(-1, block).astype(dtype), upper,
                     preferred_element_type=acc).astype(jnp.int32)
    inside = inside.reshape(count, kT // block, block)
    totals = inside[:, :, -1]
    before = jnp.cumsum(totals, axis=1) - totals
    return (inside + before[:, :, None]).reshape(count, kT)


def counted(experts, first, count, how, block):
    """(flat, live [kT], pos [kT]: the sorted position among the held
    experts' rows, kT where none takes it; sizes [count]; n_live)."""
    T, k = experts.shape
    kT = k * T
    flat = experts.T.reshape(-1)
    hit = flat[None, :] == (first + jnp.arange(count, dtype=flat.dtype)
                            )[:, None]
    upto = running_count(hit, how, block)
    sizes = upto[:, -1]
    starts = jnp.cumsum(sizes) - sizes
    pos = jnp.sum(jnp.where(hit, starts[:, None] + upto - 1, 0), axis=0)
    live = jnp.any(hit, axis=0)
    return flat, live, jnp.where(live, pos, kT), sizes, jnp.sum(sizes)


def by_count(form, how, block):
    def run(experts, weights, E, first, count):
        T, k = experts.shape
        kT = k * T
        bound = min(kT, count * T)
        flat, live, pos, sizes, n_live = counted(experts, first, count,
                                                 how, block)
        a = jnp.arange(kT, dtype=jnp.int32)
        w = weights.reshape(-1)
        if form == "count-only":
            return (jnp.zeros((bound,), jnp.int32),
                    jnp.zeros((bound,), jnp.float32), pos, sizes, n_live)
        if form == "argsort":
            order = jnp.argsort(jnp.where(live, flat, E),
                                stable=True).astype(jnp.int32)[:bound]
            return order % T, w[order], pos, sizes, n_live
        if form == "sort3":
            _, tok, scale = lax.sort(
                (jnp.where(live, flat, E), a % T, w), num_keys=1,
                is_stable=True)
            return tok[:bound], scale[:bound], pos, sizes, n_live
        big = jnp.iinfo(jnp.int32).max
        key = jnp.where(live, (flat - first) * kT + a, big)
        if form == "sort1":
            order = (lax.sort(key)[:bound]) % kT
            return order % T, w[order], pos, sizes, n_live
        if form == "sort2":
            key, scale = lax.sort((key, w), num_keys=1)
            return key[:bound] % T, scale[:bound], pos, sizes, n_live
        if form == "scatter":
            tok = jnp.zeros((bound,), jnp.int32).at[pos].set(
                a % T, mode="drop")
            scale = jnp.zeros((bound,), jnp.float32).at[pos].set(
                w, mode="drop")
            return tok, scale, pos, sizes, n_live
        if form == "compact":
            mine = jnp.nonzero(live, size=bound, fill_value=kT - 1)[0]
            keys = jnp.where(jnp.arange(bound) < n_live, flat[mine], E)
            order = mine[jnp.argsort(keys, stable=True)].astype(jnp.int32)
            return order % T, w[order], pos, sizes, n_live
        raise ValueError(form)
    return run


def library(experts, weights, E, first, count):
    T, k = experts.shape
    bound = min(k * T, count * T)
    flat = experts.T.reshape(-1)
    sizes = expert._group_sizes(flat, E)[first:first + count]
    order, inv, scale = expert.held_order(flat, weights, first, sizes)
    return order[:bound] % T, scale[:bound], inv, sizes, jnp.sum(sizes)


def back(form):
    """The weights' gradient from the live front's products `dots` [bound],
    into the assignments' order [kT]."""
    def run(experts, dots, E, first, count):
        kT = experts.size
        flat = experts.T.reshape(-1)
        sizes = expert._group_sizes(flat, E)[first:first + count]
        order, inv, _ = expert.held_order(
            flat, jnp.zeros(experts.shape[::-1], jnp.float32), first, sizes)
        n_live = jnp.sum(sizes)
        dots = jnp.where(jnp.arange(dots.shape[0]) < n_live, dots, 0.0)
        if form == "prepare-only":
            return jnp.pad(dots, (0, kT - dots.shape[0])) + inv + order
        if form == "gather":
            return jnp.where(inv < n_live, dots[inv], 0.0)
        dots = jnp.pad(dots, (0, kT - dots.shape[0]))
        return lax.sort((order, dots), num_keys=1, is_stable=False)[1]
    return run


def timed(step, args, iters):
    """ms a routing, the best of three rounds of `iters` calls."""
    jax.block_until_ready(step(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters / CHAIN * 1e3)
    return min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--block", nargs="+", type=int, default=[256],
                    help="assignments a triangular matmul counts at once")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cases", nargs="+", default=list(CASES))
    ap.add_argument("--cpu", action="store_true",
                    help="check the forms against the parent's, time "
                    "nothing (a CPU timing is no device metric)")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu:
        sys.exit("moe_order_sweep: a CPU timing is no device metric "
                 "(backend: %s); --cpu checks the forms only"
                 % jax.default_backend())
    print("device:", jax.devices()[0].device_kind)
    forms = [("parent", None, None, parent)]
    for how in ("cumsum", "tri-bf16", "tri-int8"):
        for block in (args.block if how != "cumsum" else [0]):
            forms.append(("count-only", how, block,
                          by_count("count-only", how, block)))
    best = ("tri-bf16", args.block[0])
    for form in ("argsort", "sort3", "sort1", "sort2", "scatter", "compact"):
        forms.append((form, *best, by_count(form, *best)))
    if hasattr(expert, "held_order"):
        forms.append(("library", None, None, library))
    for case in args.cases:
        T, k, E, first, count = CASES[case]
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        experts = lax.top_k(jax.random.uniform(keys[0], (CHAIN, T, E)),
                            k)[1].astype(jnp.int32)
        weights = jax.random.uniform(keys[1], (CHAIN, k, T), jnp.float32)
        want = None
        for form, how, block, fn in forms:
            if how not in (None, "cumsum") and (k * T) % block:
                continue
            step = jax.jit(lambda e, w, fn=fn: lax.map(
                lambda ew: fn(ew[0], ew[1], E, first, count), (e, w)))
            got = jax.block_until_ready(step(experts, weights))
            if want is None:
                want = got
            n = want[4][:, None]
            front = jnp.arange(want[0].shape[1])[None, :] < n
            live_a = want[2] < n
            same = bool(jnp.all(got[3] == want[3])) \
                and bool(jnp.all(got[4] == want[4])) \
                and bool(jnp.all(jnp.where(live_a, got[2] == want[2],
                                           got[2] >= n)))
            if form != "count-only":
                same = same and bool(jnp.all(jnp.where(
                    front, (got[0] == want[0]) & (got[1] == want[1]), True)))
            line = {"case": case, "form": form, "running_count": how,
                    "block": block, "kT": k * T, "count": count,
                    "live": [int(v) for v in want[4][:2]],
                    "as_parent_on_the_live_front": same}
            if on_tpu:
                line["ms_a_routing"] = round(
                    timed(step, (experts, weights), args.iters), 4)
            print(json.dumps(line), flush=True)
        # The way back: the weights' gradient into the assignments' order.
        bound = min(k * T, count * T)
        dots = jax.random.normal(keys[1], (CHAIN, bound), jnp.float32)
        want = None
        for form in ("gather", "sort", "prepare-only"):
            step = jax.jit(lambda e, d, fn=back(form): lax.map(
                lambda ed: fn(ed[0], ed[1], E, first, count), (e, d)))
            got = jax.block_until_ready(step(experts, dots))
            want = got if want is None else want
            line = {"case": case, "weights_gradient_back_by": form,
                    "kT": k * T, "as_the_gather": form == "prepare-only"
                    or bool(jnp.all(got == want))}
            if on_tpu:
                line["ms_a_routing"] = round(
                    timed(step, (experts, dots), args.iters), 4)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
