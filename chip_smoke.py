#!/usr/bin/env python3
"""chip_smoke.py — proves that the training main path still starts on the TPU.

    python chip_smoke.py             # one chip: train, kernels, host plane
    python chip_smoke.py --chips 4   # four chips: mesh + ring attention only

The script drives the entry points a user calls (``hvd.init``, the mesh
helpers, ``make_train_step``, the launcher) at the published widths of the
repo's own benchmark rows, with random weights from ``--seed``, and checks
what comes out by the repo's own means. It fails — no result line, exit
code other than 0 — when JAX finds no TPU, when a phase fails, or when a
Pallas kernel that was expected is not in the compiled program
(``tpu_custom_call`` is read from the program text: "kernel expected,
reference ran" is a failed assertion, not a fallback).

A chip belongs to one process at a time. This parent never imports jax:
each phase that needs the chip runs in a child of its own, one after the
other, and the device line is taken from the child that ran on it. The
last line of stdout on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Step times, compile seconds and peak bytes on the earlier lines are
information only; they are written nowhere under the name of a metric.
"""

import argparse
import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

try:
    from horovod_tpu.run.util import use_compile_cache
except ImportError as e:
    sys.exit("chip_smoke: the horovod_tpu package is not beside this "
             "script (%s)" % e)

# Sizes of the one-chip phases: ResNet-50 at batch 256 of 224 x 224
# images, a 12-layer 768-wide LM (12 heads of 64) at 8 x 1024 tokens, a
# small routed-MoE LM. A rehearsal on the CPU overrides these from a
# scratch script; the program has no option for it.
SIZES = dict(
    resnet_batch=256, image=224, classes=1000, train_steps=6,
    lm=dict(vocab_size=32000, num_layers=12, num_heads=12, embed_dim=768,
            mlp_dim=3072, max_seq_len=8192),
    lm_batch=8, lm_len=1024, lm_steps=4,
    # A small OLMoE-shaped LM (QK-norm, every layer 16 gated experts, top-4,
    # dropless): 4 x 1024 tokens put 16384 rows on 16 experts.
    moe=dict(vocab_size=32000, num_layers=2, num_heads=8, embed_dim=1024,
             mlp_dim=512, max_seq_len=1024, qk_norm=True, norm_eps=1e-5,
             moe_experts=16, moe_every=1, moe_top_k=4, moe_gated=True,
             moe_renormalize=False, moe_capacity_factor=None),
    moe_batch=4, moe_len=1024, moe_steps=4,
    # (B, H, G, L, D): the L=1024 LM row's attention, a long grouped call's
    # (its backward two kernels, dK/dV GRIDDED by its length alone: held by
    # the q block it would have k, v, the results and two accumulators
    # whole, 48 MiB of the 24) and that of the benchmark's 2 x 2048 LM cells
    # (its backward one kernel, as at L=1024).
    attn=[(8, 12, 12, 1024, 64), (1, 4, 2, 16384, 128),
          (2, 16, 16, 2048, 128)],
    # (B, H, L, D, D2) of latent attention's scores of two products at the
    # benchmark's `xing29b_1chip` (printed only: that cell's own comparison
    # with its reference runs the kernels).
    attn_two_products=(1, 32, 4096, 128, 64),
    # (B, H, G, data length, D, block) of block-diffusion training's
    # attention at the benchmark's `sdar30b_1chip`: 32 heads on 4, a noisy
    # and a clean copy of 4096 tokens (8192 positions) under the block mask
    # the kernels take by rule; dK/dV resident and held by the q block.
    attn_block_diffusion=(1, 32, 4, 4096, 128, 4),
    # (B, H, G, L, D, window) of a window layer's attention at the
    # benchmark's `mellum12b_1chip`: the same call under the causal band
    # the kernels take by rule, a query on itself and the 1023 keys before.
    attn_band=(1, 32, 4, 8192, 128, 1024),
    # (B, H, G, L, D, window or None) of the two calls of the benchmark's
    # `laguna33b_1chip`: a full layer's 48 heads on 8 (group 6) under the
    # causal triangle, a window layer's 64 on 8 (group 8) under a band of
    # 512 keys, one k block wide: every tile a window layer visits is cut.
    attn_by_kind=[(1, 48, 8, 8192, 128, None), (1, 64, 8, 8192, 128, 512)],
    # (n, T, C, K) of a hyper-connection at the benchmark's `xing29b_1chip`:
    # four streams of 4096 tokens, 3584 wide, onto phi's 24 columns.
    hc=(4, 4096, 3584, 24),
    # (T, k, D, experts, held) of a routed layer there: 4 x 4096
    # assignments over 64 experts of which this rank holds 8; and of
    # `olmoe1b7_1chip`'s: 8 x 4096 over 64 experts, all held, every row of
    # [32768, 2048] live.
    moe_rows=[(4096, 4, 3584, 64, 8), (4096, 8, 2048, 64, 64)],
    # (rows, F, activation, gated, live rows) of the buffers between the
    # grouped matmuls of the three cells whose layers hold a part of their
    # experts: `sdar30b_1chip`, `nemo3s120b_1chip`, `xing29b_1chip`, a live
    # share like each cell's.
    moe_act=[(65536, 768, "silu", True, 9000),
             (32768, 2688, "relu2", False, 1700),
             (16384, 1024, "silu", True, 2000)],
    # four chips
    resnet_batch_4=64, ring_len=8192, ring_batch=2, ring_heads=6,
)

# bf16 agreement between two programs that do the same arithmetic in a
# different order; f32 tolerances are the interpret-mode tests' own
# (tests/test_ops.py).
TOL = dict(attn_bf16=2e-2, loss_rel=1e-3,
           checksum_rel=1e-6, update_cosine=0.99, grad_rel_l2=2e-2,
           host=1e-4, hc_stat=1e-5)


class PhaseFailed(Exception):
    pass


def check(cond, what):
    """A failed check fails the phase; nothing downgrades it."""
    print("  %s %s" % ("ok  " if cond else "FAIL", what), flush=True)
    if not cond:
        raise PhaseFailed(what)


# --------------------------------------------------------------------------
# Children: everything below imports jax and runs on the chip.
# --------------------------------------------------------------------------

def tpu_devices(expect=None):
    """jax.devices(), or a failure: this script never runs on the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            "chip_smoke: JAX found no TPU (jax.devices() -> %d x %s); "
            "this script does not fall back to the CPU"
            % (len(devs), devs[0].platform))
    if expect is not None and len(devs) != expect:
        raise SystemExit("chip_smoke: expected %d chips, JAX reports %d"
                         % (expect, len(devs)))
    print("DEVICE " + json.dumps({"platform": devs[0].platform,
                                  "kind": devs[0].device_kind,
                                  "count": len(devs)}), flush=True)
    return devs


def on_tpu(tree):
    import jax

    return all(d.platform == "tpu"
               for x in jax.tree_util.tree_leaves(tree)
               for d in x.devices())


def kernel_calls(text):
    return text.count('custom_call_target="tpu_custom_call"')


def kernel_named(text, name):
    """Whether the program holds a Pallas kernel called `name`:
    `jvp(<name>)/pallas_call`, or `.../<name>/pallas_call` inside a
    model's scopes."""
    return re.search(r"\b%s\)*/pallas_call" % name, text) is not None


def host_leaves(tree):
    """The tree's leaves as float32 numpy arrays."""
    import jax
    import numpy as np

    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(tree)]


def rel_err(a, b):
    """max |a-b| over max |b|, in float32 on the host."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1e-30, np.max(np.abs(b))))


def resnet_step(model_cls, mesh, per_chip_batch, seed, donate=True,
                **model_kw):
    """make_train_step on a ResNet: bf16, SGD 0.01 with momentum 0.9, a
    synthetic ImageNet-shaped batch from a seed. Returns the
    step and its (params, opt_state, batch), not yet placed."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    s = SIZES["image"]
    model = model_cls(num_classes=SIZES["classes"], dtype=jnp.bfloat16,
                      **model_kw)
    rng = jax.random.PRNGKey(seed)
    variables = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, s, s, 3)), train=False))(rng)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, batch["x"],
            train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, batch["y"])

    opt = optax.sgd(0.01, momentum=0.9)
    step = make_train_step(loss_fn, opt, mesh, donate=donate)
    n = per_chip_batch * mesh.size
    kx, ky = jax.random.split(jax.random.PRNGKey(seed + 1))
    batch = {"x": jax.random.normal(kx, (n, s, s, 3), jnp.float32),
             "y": jax.random.randint(ky, (n,), 0, SIZES["classes"])}
    return step, (params, opt.init(params), batch)


def run_steps(step, params, opt_state, batch, n):
    """n steps, each ending in block_until_ready; returns the losses and
    the seconds of each step (the first includes compilation)."""
    import jax

    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, loss))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, secs


def check_losses(losses):
    print("  losses: " + " ".join("%.4f" % v for v in losses), flush=True)
    check(all(math.isfinite(v) for v in losses), "every loss is finite")
    check(losses[-1] < losses[0],
          "loss falls on the repeated batch (%.4f -> %.4f)"
          % (losses[0], losses[-1]))


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_train(args):
    devs = tpu_devices()
    import horovod_tpu as hvd
    from horovod_tpu import models, parallel

    hvd.init()
    check(hvd.size() == 1 and hvd.rank() == 0, "hvd.init(): rank 0 of 1")
    mesh = parallel.data_parallel_mesh(devices=devs)
    step, state = resnet_step(models.ResNet50, mesh, SIZES["resnet_batch"],
                              args.seed)
    params, opt_state, batch = step.place(*state)
    params, opt_state, losses, secs = run_steps(
        step, params, opt_state, batch, SIZES["train_steps"])
    print("  ResNet-50 bf16 %dx%d batch %d: first step (with compile) "
          "%.1f s, then %s ms; peak bytes %s"
          % (SIZES["image"], SIZES["image"], SIZES["resnet_batch"], secs[0],
             " ".join("%.1f" % (1e3 * s) for s in secs[1:]),
             peak_bytes(devs[0])), flush=True)
    check_losses(losses)
    check(on_tpu((params, opt_state, batch)),
          "parameters, optimizer state and batch live on the tpu")
    hvd.shutdown()


def print_loss_plan(B, L, D, V, chunk, dtype):
    """How the chunked vocabulary loss cuts a [B, L] call (`hvd.profile`)."""
    from horovod_tpu import profile

    plan = profile.loss_plan(B, L, D, V, chunk, dtype)
    print("  hvd_loss [%d, %d] x [%d, %d]: %d x %d rows, %d passes of the "
          "head, logits %.0f MiB a chunk, residuals %.0f MiB"
          % (B, L, D, V, plan["iterations"], plan["rows"],
             plan["head_passes"], plan["logits_bytes"] / 2 ** 20,
             plan["residual_bytes"] / 2 ** 20), flush=True)


def lm_step(mesh, seed):
    """The 12-layer 768-wide LM of SIZES["lm"]: flash attention, dense
    log-softmax loss, adam, through make_train_step. Returns the step and
    its (params, opt_state, batch), not yet placed."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.parallel import make_train_step

    cfg = models.TransformerConfig(attention="flash", dtype=jnp.bfloat16,
                                   **SIZES["lm"])
    model = models.Transformer(cfg)
    L = SIZES["lm_len"]
    rng = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(rng, (SIZES["lm_batch"] * mesh.size, L), 0,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 tokens.shape)
    params = jax.jit(lambda r: model.init(
        r, tokens[:1], positions[:1]))(rng)["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"], batch["pos"])
        tgt = jnp.roll(batch["x"], -1, axis=1)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    opt = optax.adam(1e-4)
    step = make_train_step(loss_fn, opt, mesh)
    return step, (params, opt.init(params), {"x": tokens, "pos": positions})


def moe_step(mesh, seed, attention="flash"):
    """A dropless routed-feed-forward LM through make_train_step: the
    cross-entropy plus the router's two auxiliary losses, adamw. Returns
    the step, its (params, opt_state, batch), not yet placed, and
    `routing(params, batch)`: the routing statistics of a forward pass."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.parallel import (make_train_step, router_aux_losses,
                                      routing_stats)

    cfg = models.TransformerConfig(attention=attention, dtype=jnp.bfloat16,
                                   **SIZES["moe"])
    model = models.Transformer(cfg)
    L = SIZES["moe_len"]
    rng = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(rng, (SIZES["moe_batch"] * mesh.size, L), 0,
                                cfg.vocab_size)
    params = jax.jit(lambda r: model.init(r, tokens[:1]))(rng)["params"]

    def forward(params, x):
        logits, state = model.apply({"params": params}, x,
                                    mutable=["intermediates"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        xent = -jnp.mean(jnp.take_along_axis(
            logp, jnp.roll(x, -1, axis=1)[..., None], axis=-1))
        balance, z = router_aux_losses(state["intermediates"])
        return (xent + 0.01 * balance + 0.001 * z,
                routing_stats(state["intermediates"]))

    opt = optax.adamw(1e-4)
    step = make_train_step(lambda p, batch: forward(p, batch["x"])[0], opt,
                           mesh)
    routing = jax.jit(lambda p, batch: forward(p, batch["x"])[1])
    return step, (params, opt.init(params), {"x": tokens}), routing


def check_routing(stats, assigned):
    """No assignment dropped: every layer's group sizes sum to `assigned`
    (top_k x tokens)."""
    sums = [int(v) for v in stats["assignments"].sum(axis=1)]
    print("  routing: assignments a layer %s, dropped %d, largest expert "
          "%d rows" % (sums, int(stats["dropped"]),
                       int(stats["assignments"].max())), flush=True)
    check(all(n == assigned for n in sums) and int(stats["dropped"]) == 0,
          "no assignment dropped: every layer's group sizes sum to %d"
          % assigned)


def compile_with_text(jitted, *call_args):
    """AOT-compiles a jitted callable; returns (compiled, text, seconds)."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*call_args).compile()
    return compiled, compiled.as_text(), time.perf_counter() - t0


def attention_case(B, H, G, L, D, dtype, seed, mask=None,
                   dense_causal=False, gated=False):
    """flash_attention forward and backward alone at one shape, and
    _blockwise_reference doing the same: (name, kernel, reference,
    (q, k, v, cotangent)), both jitted and returning (out, dq, dk, dv).
    `gated`: the call with a head gate [B, L, H] (`flash_attention`'s
    ``gate``: a sigmoid's values in f32, applied inside the kernels as a
    change of the rows' normalisers) against the reference's output times
    the gate in f32; the operands are then (q, k, v, gate, cotangent) and
    dgate is the fifth result.
    `mask`: a rule in place of the causal triangle (L counts all its
    positions); the reference is then the dense masked softmax of the
    benchmark's plain reference of the rule's model (`references/sdar.py`:
    the mask from the block-diffusion rule's three clauses;
    `references/mellum.py`: the band's two comparisons), a block of query
    rows at a time. `dense_causal`: the causal call against that dense
    masked softmax too (a band as long as the sequence)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (_blockwise_reference,
                                                 flash_attention)

    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, L, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, L, G, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, L, G, D), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (B, L, H, D), jnp.float32)
    gate = (jax.nn.sigmoid(jax.random.normal(
        jax.random.fold_in(kw, 1), (B, L, H), jnp.float32)),) * gated

    def kernel(q, k, v, *gate):
        gate = {"gate": gate[0]} if gate else {}
        if mask is not None:
            return flash_attention(q, k, v, mask=mask, **gate)
        return flash_attention(q, k, v, causal=True, **gate)

    def reference(q, k, v, *gate, mask=mask):
        if gate:  # in f32, rounded once, as the kernels round
            return (reference(q, k, v, mask=mask).astype(jnp.float32)
                    * gate[0][..., None]).astype(q.dtype)
        if mask is None and dense_causal:
            from horovod_tpu.ops import BandMask
            mask = BandMask(L)
        if mask is not None:
            # Not the program's `rule.visible`: the dense mask written
            # from the rule's three clauses, as the benchmark's reference
            # has it, in float32.
            from benchmark.references import mellum, sdar
            f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
            dense = (lambda q, k, v: mellum.attention(  # noqa: E731
                q, k, v, mask.window)) if hasattr(mask, "window") else (
                    lambda q, k, v: sdar.attention(
                        q, k, v, mask.length, mask.block, 0))
            return jax.vmap(dense)(f32(q), f32(k), f32(v)).astype(q.dtype)
        t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
        return t(_blockwise_reference(t(q), t(k), t(v), D ** -0.5, True))

    def both(fn):
        def f(*operands):
            *operands, w = operands
            out, vjp = jax.vjp(fn, *operands)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(f)

    name = "B%d H%d G%d L%d D%d%s%s %s" % (
        B, H, G, L, D,
        "" if mask is None else " %s%r" % (type(mask).__name__,
                                            tuple(mask)),
        " gated" * gated, jnp.dtype(dtype).name)
    return name, both(kernel), both(reference), (q, k, v) + gate + (w,)


def flash_kernels(B, H, G, L, D, dtype, mask=None, gate=False):
    """The names of the kernels a forward and backward of this shape run
    (`hvd.profile.flash_plan`): the forward's, then the backward's one
    (`hvd_flash_bwd`) or two."""
    from horovod_tpu import profile

    return [name for backward in (False, True)
            for name in profile.flash_plan(B, H, L, D, H // G, dtype,
                                           backward, mask=mask, gate=gate)]


def model_flash_kernels(model, batch, length, dtype):
    """`flash_kernels` of a layer of one of SIZES' LMs (plain heads, rotary
    outside the kernels)."""
    heads = model["num_heads"]
    return flash_kernels(batch, heads, heads, length,
                         model["embed_dim"] // heads, dtype)


def print_flash_plan(B, H, G, L, D, dtype, shared_dim=0, mask=None,
                     gate=False):
    """Which path each flash kernel of this shape takes (`hvd.profile`);
    `shared_dim`: the width of a second score product on one shared key;
    `mask`: a rule in place of the causal triangle, whose plans count the
    score tiles each kernel visits, masks and skips, and the key step at
    which it takes a cut k block with one sub-tile in sight, with the
    sub-tiles it visits and masks (`cut_k` under the k block, fewer
    sub-tiles than four a tile: the one-kernel backward's walk engaged). A
    backward kernel's line says which side a grid step holds a block of (the
    one kernel has a resident form of either kind). `gate`: the call has a
    head gate, and a line ends in how it reaches the kernel (`kernel`: the
    forward takes its reciprocals, a factor of the rows' normalisers; `lse`:
    a backward kernel is the ungated one on the gated rows' lse and delta /
    gate)."""
    from horovod_tpu import profile

    for backward in (False, True):
        for name, plan in profile.flash_plan(
                B, H, L, D, H // G, dtype, backward, shared_dim=shared_dim,
                mask=mask, gate=gate).items():
            path = plan.path + (" held by the %s block" % plan.held
                                if name in (profile.FLASH_DKV,
                                            profile.FLASH_BWD) else "")
            print("  %s: %s, blocks %d x %d, grid %s = %d steps, VMEM %.1f "
                  "MiB%s%s%s" % (name, path, plan.block_q, plan.block_k,
                               plan.grid, plan.grid_steps,
                               plan.vmem_bytes / 2 ** 20,
                               "" if plan.vmem_limit_bytes is None else
                               " of a limit of %.0f" % (
                                   plan.vmem_limit_bytes / 2 ** 20),
                               "" if mask is None else
                               "; tiles visited %d (masked %d), skipped %d; "
                               "a lone sub-tile is of %d keys: sub-tiles "
                               "visited %d (masked %d)"
                               % (plan.tiles_visited, plan.tiles_masked,
                                  plan.tiles_skipped, plan.cut_k,
                                  plan.subtiles_visited,
                                  plan.subtiles_masked),
                               "" if plan.gate is None else
                               "; the gate: %s" % plan.gate),
                  flush=True)


def attention_vs_reference(case, tol, kernels):
    """The kernels the plan names are in the program, and on the chip they
    agree with the reference."""
    import jax

    name, kernel, reference, qkvw = case
    compiled, text, secs = compile_with_text(kernel, *qkvw)
    n = kernel_calls(text)
    check(n == len(kernels) and all(kernel_named(text, k) for k in kernels),
          "flash %s: %d tpu_custom_call in the program (%s; compiled in "
          "%.1f s)" % (name, n, ", ".join(kernels), secs))
    got = compiled(*qkvw)
    with jax.default_matmul_precision("highest"):
        want = reference(*qkvw)
    jax.block_until_ready((got, want))
    errs = [rel_err(g, r) for g, r in zip(got, want)]
    check(max(errs) <= tol,
          "flash %s vs _blockwise_reference on the chip: %s (max rel to max "
          "|ref|, tol %.0e)" % (name, " ".join(
              "%s %.2e" % pair for pair in zip(
                  ("out", "dq", "dk", "dv", "dgate"), errs)), tol))


def attention_block_copies(B, H, G, L, D, dtype, seed, mask=None,
                           window=None, gate=False):
    """What lies between an attention block's projections and its flash
    kernels, forward and backward: `hvd.profile.attention_layout_copies` of
    the model's own `Attention` (the two claimed cells' width: 2048 onto H
    heads of D on G kv heads, each head's q and k normed and rotated) under
    the causal triangle, `mask`, or the band of `window` keys, compiled as a
    forward alone and as the gradient of everything. The kernels take every
    operand in the public layout, so none is re-laid for THEIR sake; XLA's
    own copies between its matmuls' layouts and the kernels' are counted and
    named (information: the count is no measure of time, PERF.md s6, PR
    57). `gate`: the block with `attention_gate="head"`, whose gate is
    applied inside the flash kernels (PR 63): between `hvd_flash_fwd` and the
    out-projection the program holds no fusion that multiplies an o-sized
    array, at most a plain copy; what carries the gate's scope
    (`hvd.profile.fused_scopes`) is printed with each result's shape."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.models.transformer import Attention, TransformerConfig

    cfg = TransformerConfig(
        num_layers=1, num_heads=H, num_kv_heads=G, head_dim=D,
        embed_dim=2048, attention="flash", qk_norm="head", dtype=dtype,
        attention_mask=mask, attention_gate="head" if gate else None, **(
            {} if window is None else
            {"attention_types": ("window",), "attention_window": window}))
    layer = Attention(cfg, kind=None if window is None else "window")
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, L, cfg.embed_dim),
                          jnp.float32).astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    params = jax.jit(layer.init)(jax.random.PRNGKey(seed + 1), x, positions)

    def loss(params, x):
        return jnp.sum(layer.apply(params, x, positions).astype(jnp.float32)
                       ** 2)

    what = ("causal" if mask is None and window is None else (
        "%s%r" % (type(mask).__name__, tuple(mask)) if window is None
        else "BandMask(%d)" % window)) + ", gated" * gate
    for name, fn, calls in (
            ("forward", jax.jit(lambda p, x: layer.apply(p, x, positions)),
             1), ("gradient", jax.jit(jax.grad(loss, argnums=(0, 1))), 2)):
        _, text, secs = compile_with_text(fn, params, x)
        got = profile.attention_layout_copies(text)
        check(got["calls"] == calls,
              "attention block H%d on G%d, L%d, %s, %s: %d flash calls in "
              "the program; q-sized copies and transposes %d, %.0f MiB (%s; "
              "compiled in %.1f s)"
              % (H, G, L, what, name, got["calls"], got["copies"],
                 got["bytes"] / 2 ** 20,
                 ", ".join(got["instructions"]) or "none", secs))
        if gate:
            print("    fusions under %s: %s" % (profile.ATTN_GATE, "; ".join(
                "%s %s" % (fusion, re.search(
                    r"%%?%s = (.+?) fusion\(" % re.escape(fusion),
                    text).group(1))
                for fusion in profile.fused_scopes(
                    text, (profile.ATTN_GATE,))) or "none"), flush=True)


def backward_forms_agree(B, H, G, L, D, dtype, seed, mask, tol):
    """Where the plan holds the one-kernel backward by the q block (`held`
    "q": k, v, dk and dv whole in VMEM, dQ carried by the loop, dK and dV
    summed there): its dQ, dK and dV against the two kernels with dK/dV
    GRIDDED on the same inputs, forced by a budget one byte short of what
    the form holds. Both add in f32 and round once; dK and dV add a k
    block's q tiles in ascending order in both forms. dQ's keys are summed
    in another order in each since PR 53 (the one kernel by the rule's runs
    in ascending order, a cut k block's lone sub-tile alone; dQ's own
    kernel the whole k blocks first, then the cut ones): a difference in
    dQ's last bits, where PR 52 read 0.0, is that order and no fault."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.ops.flash_attention import (_pallas_backward,
                                                 _pallas_forward_lse)

    def plans(**budget):
        return profile.flash_plan(B, H, L, D, H // G, dtype, True, mask=mask,
                                  **budget)

    plan = plans()[profile.FLASH_BWD]
    check((plan.path, plan.held) == ("resident", "q") and {
        name: (p.path, p.held) for name, p in plans(
            vmem_budget=plan.resident_bytes - 1).items()} == {
                profile.FLASH_DQ: ("resident", "q"),
                profile.FLASH_DKV: ("gridded", "k")},
          "%s at this shape is resident and held by the q block, and one "
          "byte under what it holds %s beside the gridded %s"
          % (profile.FLASH_BWD, profile.FLASH_DQ, profile.FLASH_DKV))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(key, (B, heads, L, D),
                                    jnp.float32).astype(dtype)
                  for key, heads in zip(ks, (H, G, G, H)))

    def grads(budget):
        def f(q, k, v, w):
            kw = dict(vmem_budget=budget, rule=mask)
            out, lse = _pallas_forward_lse(q, k, v, D ** -0.5, mask is None,
                                           False, **kw)
            return _pallas_backward(q, k, v, out, lse, w, D ** -0.5,
                                    mask is None, False, **kw)
        return jax.jit(f)(q, k, v, w)

    held, gridded = grads(plan.resident_bytes), grads(plan.resident_bytes - 1)
    errs = [rel_err(a, b) for a, b in zip(held, gridded)]
    check(max(errs) <= tol,
          "%s held by the q block vs %s + gridded %s on the chip: dq %.2e "
          "(its keys summed in another order in each) dk %.2e dv %.2e (max "
          "rel to max |gridded|, tol %.0e)"
          % ((profile.FLASH_BWD, profile.FLASH_DQ, profile.FLASH_DKV)
             + tuple(errs) + (tol,)))


def hc_stat_vs_jnp(n, T, C, K, dtype, seed):
    """How a hyper-connection's statistic is formed at this shape
    (`hvd.profile.hc_plan`), that the two kernels it names are in the
    program, and that on the chip the two results and both gradients agree
    with the plain sums and products formed in f32."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.ops.hc_stat import hc_stat

    plan = profile.hc_plan(n, T, C, K, dtype, hc_remat=True,
                           block_remat=True)
    print("  %s [%d, %d, %d] x %d columns: %s, %d x %d tokens, VMEM %.1f "
          "MiB, %d pass over the streams, %d evaluation a step under both "
          "recomputations"
          % (profile.HC_STAT, n, T, C, K, plan["path"], plan["steps"],
             plan["rows"], plan["vmem_bytes"] / 2 ** 20, plan["passes"],
             plan["evaluations"]), flush=True)
    kx, kp, ks, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(kx, (n, 1, T, C), dtype)
    phi = 0.02 * jax.random.normal(kp, (n * C, K), jnp.float32)
    w = (jax.random.normal(ks, (1, T)), jax.random.normal(kw, (1, T, K)))

    def both(fn):
        def f(X, phi):
            out, vjp = jax.vjp(fn, X, phi)
            return out + vjp(w)
        return jax.jit(f)

    def plain(X, phi):
        xf = X.astype(jnp.float32)
        return (jnp.sum(xf * xf, axis=(0, -1)), jnp.einsum(
            "n...c,nck->...k", xf,
            phi.astype(dtype).astype(jnp.float32).reshape(n, C, K)))

    kernels = (profile.HC_STAT, profile.HC_STAT_DPHI)
    compiled, text, secs = compile_with_text(both(hc_stat), X, phi)
    check(plan["path"] == "kernel" and kernel_calls(text) == len(kernels)
          and all(kernel_named(text, k) for k in kernels),
          "%s: %d tpu_custom_call in the program (%s; compiled in %.1f s)"
          % (profile.HC_STAT, kernel_calls(text), ", ".join(kernels), secs))
    got = compiled(X, phi)
    with jax.default_matmul_precision("highest"):
        want = both(plain)(X, phi)
    errs = [rel_err(g, r) for g, r in zip(got, want)]
    check(max(errs[:2]) <= TOL["hc_stat"]
          and max(errs[2:]) <= TOL["attn_bf16"],
          "%s vs jnp in f32 on the chip: sum of squares %.2e projection "
          "%.2e (tol %.0e) dX %.2e dphi %.2e (bf16 operands, tol %.0e; max "
          "rel to max |ref|)"
          % ((profile.HC_STAT,) + tuple(errs[:2]) + (TOL["hc_stat"],)
             + tuple(errs[2:]) + (TOL["attn_bf16"],)))


def moe_rows_vs_jnp(T, k, D, experts, held, dtype, seed):
    """How a routed layer that holds `held` of its experts moves its rows
    at this shape (`hvd.profile.moe_rows_plan`), that the two kernels it
    names are in the program, and that on the chip the dispatch, the
    combine and every gradient agree with jnp's gathers and selects over
    all k*T rows, with the held experts' rows live (an eighth at Xing's
    shape) and with every row live (OLMoE's layer holds them all)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.ops import moe_rows
    from horovod_tpu.parallel import expert

    plan = profile.moe_rows_plan(T, k, D, dtype, experts=experts,
                                 held=(0, held))
    print("  %s, %s [%d, %d] x %d choices: %s, tiles of %d of the buffer's "
          "%d rows, %d columns of the tokens resident, VMEM %.1f MiB, %d + "
          "%d kernel calls a layer; the order by %s (%d bins)"
          % (profile.MOE_ROWS, profile.MOE_SUM, T, D, k, plan["path"],
             plan["tile_rows"], plan["buffer_rows"], plan["block_cols"],
             plan["vmem_bytes"] / 2 ** 20, plan["calls_a_layer"]["forward"],
             plan["calls_a_layer"]["backward"], plan["order"],
             plan["bins"]), flush=True)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (T, D), dtype)
    ys, g_xs = (jax.random.normal(key, (k * T, D), dtype)
                for key in keys[1:3])
    g_y = jax.random.normal(keys[3], (T, D), dtype)
    weights = jax.random.uniform(keys[4], (k, T), jnp.float32)
    chosen = jnp.argsort(jax.random.uniform(keys[5], (T, experts)),
                         axis=1)[:, :k].astype(jnp.int32)
    flat, order, inv, sizes = expert.sort_assignments(chosen, experts)

    def both(fn):
        def f(x, ys, weights, n_live, g_xs, g_y):
            out, vjp = jax.vjp(lambda x, ys, w: fn(x, ys, w, n_live),
                               x, ys, weights)
            return out + vjp((g_xs, g_y))
        return jax.jit(f)

    def through_the_op(x, ys, w, n_live):
        return (moe_rows.dispatch(x, order, inv, n_live, k)[0],
                moe_rows.combine(ys, w, order, inv, n_live))

    def plain(x, ys, w, n_live):  # gathers and selects over all k*T rows
        mine = (jnp.arange(k * T) < n_live)[:, None]
        rows = jnp.where(mine, ys, 0)[inv].reshape(k, T, D)
        y = jnp.einsum(
            "ktd,kt->td", rows,
            jnp.where(inv.reshape(k, T) < n_live, w, 0.0),
            preferred_element_type=jnp.float32)
        return jnp.where(mine, x[order % T], 0), y.astype(x.dtype)

    kernels = profile.MOE_ROWS_KERNELS
    args = (x, ys, weights, jnp.sum(sizes[:held]), g_xs, g_y)
    compiled, text, secs = compile_with_text(both(through_the_op), *args)
    check(plan["path"] == "kernel" and kernel_calls(text) == 4
          and all(kernel_named(text, name) for name in kernels),
          "%s: %d tpu_custom_call in the program (%s, forward and "
          "backward; compiled in %.1f s)"
          % (profile.MOE_ROWS, kernel_calls(text), ", ".join(kernels), secs))
    counts = [("every row", jnp.int32(k * T))]
    if held < experts:
        counts.insert(0, ("the held experts' rows", args[3]))
    for what, n_live in counts:
        args = args[:3] + (n_live,) + args[4:]
        got, want = compiled(*args), both(plain)(*args)
        live = (jnp.arange(k * T) < n_live)[:, None]
        errs = [rel_err(jnp.where(live, g, 0) if g.shape[0] == k * T else g,
                        r) for g, r in zip(got, want)]
        check(max(errs) <= TOL["attn_bf16"],
              "%s vs jnp on the chip, %d of %d rows live (%s): xs %.2e y "
              "%.2e dx %.2e dys %.2e dw %.2e (max rel to max |ref|, tol "
              "%.0e)" % ((profile.MOE_ROWS, int(n_live), k * T, what)
                         + tuple(errs) + (TOL["attn_bf16"],)))
    if plan["order"] != "count":
        return
    # The order `moe_ffn` forms where it holds a part (`expert.held_order`:
    # counted positions, one sort that carries the weights, the weights'
    # gradient sorted back): the same live front, the same results.
    n_live = jnp.sum(sizes[:held])

    def counted(x, ys, w, n_live):
        every, at, scale = expert.held_order(flat, w, 0, sizes[:held])
        return (moe_rows.dispatch(x, every, at, n_live, k)[0],
                moe_rows.combine(ys, w, every, at, n_live,
                                 carried=(scale, every)))

    every, at, _ = jax.jit(expert.held_order, static_argnums=2)(
        flat, weights, 0, sizes[:held])
    live = inv < n_live
    check(bool(jnp.all(every[:int(n_live)] == order[:int(n_live)]))
          and bool(jnp.all(jnp.where(live, at == inv, at >= n_live))),
          "the counted order's live front of %d rows is the sorted order's"
          % int(n_live))
    args = args[:3] + (n_live,) + args[4:]
    got, want = both(counted)(*args), compiled(*args)
    live = (jnp.arange(k * T) < n_live)[:, None]
    errs = [rel_err(*(jnp.where(live, a, 0) if a.shape[0] == k * T else a
                      for a in pair)) for pair in zip(got, want)]
    check(max(errs) == 0.0,
          "%s on the counted order vs on the argsorts, %d rows live: xs "
          "%.2e y %.2e dx %.2e dys %.2e dw %.2e (the same rows in the same "
          "order: 0)" % ((profile.MOE_ROWS, int(n_live)) + tuple(errs)))


def moe_act_vs_jnp(rows, F, act, gated, live, dtype, seed):
    """How the activation between a held routed layer's grouped matmuls
    runs at this shape (`hvd.profile.moe_act_plan`), that the two kernels
    it names are in the program, and that on the chip the result, the
    gradients and the backward's second `a` agree with jnp in f32 on the
    live rows, are zeros from the count to its tile's end, and are finite
    there though the operands' dead rows hold NaN."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profile
    from horovod_tpu.ops import moe_act
    from horovod_tpu.parallel import expert

    plan = profile.moe_act_plan(rows, F, dtype, gated=gated)
    print("  %s, %s [%d, %d] %s%s: %s, tiles of %d of the buffer's rows x "
          "%d columns, %d grid steps a call, VMEM %.1f MiB"
          % (profile.MOE_ACT, profile.MOE_ACT_BWD, rows, F, act,
             " gated" if gated else "", plan["path"], plan["tile_rows"],
             plan["block_cols"], plan["grid_steps"],
             plan["vmem_bytes"] / 2 ** 20), flush=True)
    fn = expert.ACTIVATIONS[act]
    dead = (jnp.arange(rows) >= live)[:, None]
    g, h, da = (jnp.where(dead, jnp.nan, jax.random.normal(key, (rows, F))
                          ).astype(dtype)
                for key in jax.random.split(jax.random.PRNGKey(seed), 3))
    tiles = (plan["tile_rows"], plan["block_cols"])

    @jax.jit
    def kernels(g, h, da):  # as `activated_matmul`'s rule calls them
        args = (fn, g if gated else None, h, jnp.int32(live))
        return (moe_act._pallas_act(*args, None, tiles, False)
                + moe_act._pallas_act(*args, da, tiles, False))

    @jax.jit
    def plain(g, h, da):  # in f32, the dead rows selected away
        g, h, da = (jnp.where(dead, 0, x).astype(jnp.float32)
                    for x in (g, h, da))
        a, vjp = jax.vjp(lambda g, h: fn(g) * h if gated else fn(h), g, h)
        return tuple(x.astype(dtype)
                     for x in (a,) + vjp(da)[0 if gated else 1:] + (a,))

    names = profile.MOE_ACT_KERNELS
    compiled, text, secs = compile_with_text(kernels, g, h, da)
    check(plan["path"] == "kernel" and kernel_calls(text) == 2
          and all(kernel_named(text, name) for name in names),
          "%s: %d tpu_custom_call in the program (%s; compiled in %.1f s)"
          % (profile.MOE_ACT, kernel_calls(text), ", ".join(names), secs))
    got, want = compiled(g, h, da), plain(g, h, da)
    written = -(-live // tiles[0]) * tiles[0]
    errs = [rel_err(a[:written], b[:written]) for a, b in zip(got, want)]
    zeros = max(float(jnp.max(jnp.abs(a[live:written].astype(jnp.float32)),
                              initial=0.0))  # a count on a tile's edge
                for a in got)
    check(max(errs) <= TOL["attn_bf16"] and zeros == 0.0,
          "%s vs jnp in f32 on the chip, %d of %d rows live: %s (max rel to "
          "max |ref|, tol %.0e); from the count to the tile's end |.| <= "
          "%.1e (NaN operands there)"
          % (profile.MOE_ACT, live, rows, " ".join(
              "%s %.2e" % pair for pair in zip(
                  ("a", "dg", "dh", "a again") if gated
                  else ("a", "dh", "a again"), errs)),
             TOL["attn_bf16"], zeros))


def phase_kernels(args):
    devs = tpu_devices()
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models, parallel

    mesh = parallel.data_parallel_mesh(devices=devs)

    # The train phase's program again, in a second process: the persistent
    # compile cache must have it.
    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    step, state = resnet_step(models.ResNet50, mesh, SIZES["resnet_batch"],
                              args.seed)
    params, opt_state, batch = step.place(*state)
    del events[:]
    t0 = time.perf_counter()
    jax.block_until_ready(step(params, opt_state, batch))
    hits = events.count("/jax/compilation_cache/cache_hits")
    misses = events.count("/jax/compilation_cache/cache_misses")
    print("  compile cache %s: second process, ResNet-50 step: hits %d "
          "misses %d, first call %.1f s"
          % (os.environ["JAX_COMPILATION_CACHE_DIR"], hits, misses,
             time.perf_counter() - t0), flush=True)
    check(hits >= 1 and misses == 0,
          "the second process found the train step in the compile cache")
    del step, state, params, opt_state, batch

    step, state = lm_step(mesh, args.seed)
    state = step.place(*state)
    compiled, text, secs = compile_with_text(step, *state)
    n = kernel_calls(text)
    flash = model_flash_kernels(SIZES["lm"], SIZES["lm_batch"],
                                SIZES["lm_len"], jnp.bfloat16)
    check(n >= len(flash) * SIZES["lm"]["num_layers"],
          "LM L=%d flash: %d tpu_custom_call in the train step (%s for "
          "each of %d layers; compiled in %.1f s)"
          % (SIZES["lm_len"], n, ", ".join(flash),
             SIZES["lm"]["num_layers"], secs))
    params, opt_state, losses, secs = run_steps(compiled, *state,
                                                SIZES["lm_steps"])
    print("  LM %dx%d L=%d batch %d: steps %s ms; peak bytes %s"
          % (SIZES["lm"]["embed_dim"], SIZES["lm"]["num_layers"],
             SIZES["lm_len"], SIZES["lm_batch"],
             " ".join("%.1f" % (1e3 * s) for s in secs),
             peak_bytes(devs[0])), flush=True)
    check_losses(losses)
    check(on_tpu((params, opt_state)), "LM state lives on the tpu")
    del step, state, compiled, params, opt_state

    step, state, routing = moe_step(mesh, args.seed)
    state = step.place(*state)
    compiled, text, secs = compile_with_text(step, *state)
    n, layers = kernel_calls(text), SIZES["moe"]["num_layers"]
    flash = model_flash_kernels(SIZES["moe"], SIZES["moe_batch"],
                                SIZES["moe_len"], jnp.bfloat16)
    check(n >= (len(flash) + 9) * layers and "hvd_moe_gmm_drhs" in text,
          "MoE LM: %d tpu_custom_call in the train step (%d flash and 9 "
          "grouped-matmul kernels for each of %d layers; compiled in %.1f s)"
          % (n, len(flash), layers, secs))
    assigned = (SIZES["moe"]["moe_top_k"] * SIZES["moe_batch"]
                * SIZES["moe_len"])
    check_routing(routing(state[0], state[2]), assigned)
    params, opt_state, losses, secs = run_steps(compiled, *state,
                                                SIZES["moe_steps"])
    print("  MoE LM %dx%d, %d experts top-%d, L=%d batch %d: steps %s ms"
          % (SIZES["moe"]["embed_dim"], layers, SIZES["moe"]["moe_experts"],
             SIZES["moe"]["moe_top_k"], SIZES["moe_len"], SIZES["moe_batch"],
             " ".join("%.1f" % (1e3 * s) for s in secs)), flush=True)
    check_losses(losses)
    check_routing(routing(params, state[2]), assigned)
    check(on_tpu((params, opt_state)), "MoE LM state lives on the tpu")
    del step, state, compiled, params, opt_state, routing

    for i, shape in enumerate(SIZES["attn"]):
        print_flash_plan(*shape, jnp.bfloat16)
        attention_vs_reference(
            attention_case(*shape, jnp.bfloat16, args.seed + i),
            TOL["attn_bf16"], flash_kernels(*shape, jnp.bfloat16))
    B, H, L, D, D2 = SIZES["attn_two_products"]
    print("  scores of two products, %d + %d wide on %d heads at L=%d:"
          % (D, D2, H, L), flush=True)
    print_flash_plan(B, H, H, L, D, jnp.bfloat16, shared_dim=D2)
    from horovod_tpu.ops import BlockDiffusionMask

    B, H, G, L, D, block = SIZES["attn_block_diffusion"]
    rule = BlockDiffusionMask(L, block)
    shape = (B, H, G, 2 * L, D, jnp.bfloat16)
    print("  block diffusion, a noisy and a clean copy of %d tokens in "
          "blocks of %d:" % (L, block), flush=True)
    print_flash_plan(*shape, mask=rule)
    attention_vs_reference(
        attention_case(*shape, args.seed + len(SIZES["attn"]), mask=rule),
        TOL["attn_bf16"], flash_kernels(*shape, mask=rule))
    backward_forms_agree(B, H, G, 2 * L, D, jnp.bfloat16, args.seed, rule,
                         TOL["attn_bf16"])
    from horovod_tpu.ops import BandMask

    B, H, G, L, D, window = SIZES["attn_band"]
    rule = BandMask(window)
    shape = (B, H, G, L, D, jnp.bfloat16)
    print("  a causal band, each of %d queries on itself and the %d keys "
          "before it:" % (L, window - 1), flush=True)
    print_flash_plan(*shape, mask=rule)
    attention_vs_reference(
        attention_case(*shape, args.seed + len(SIZES["attn"]) + 1,
                       mask=rule),
        TOL["attn_bf16"], flash_kernels(*shape, mask=rule))
    backward_forms_agree(B, H, G, L, D, jnp.bfloat16, args.seed + 1, rule,
                         TOL["attn_bf16"])
    for i, (B, H, G, L, D, window) in enumerate(SIZES["attn_by_kind"]):
        rule = None if window is None else BandMask(window)
        shape = (B, H, G, L, D, jnp.bfloat16)
        print("  %d query heads on %d at L=%d, %s:" % (
            H, G, L, "causal" if rule is None else "a band of %d keys"
            % window), flush=True)
        # the triangle's tiles are counted by the band as long as it
        print_flash_plan(*shape, mask=rule or BandMask(L))
        attention_vs_reference(
            attention_case(*shape, args.seed + 7 + i, mask=rule,
                           dense_causal=True),
            TOL["attn_bf16"], flash_kernels(*shape, mask=rule))
        # The same call with the cell's head gate, applied inside the
        # forward kernel (the backward's is the ungated one on the gated
        # rows' lse and delta / gate): out, dq, dk, dv and dgate.
        print_flash_plan(*shape, mask=rule, gate=True)
        attention_vs_reference(
            attention_case(*shape, args.seed + 9 + i, mask=rule,
                           dense_causal=True, gated=True),
            TOL["attn_bf16"], flash_kernels(*shape, mask=rule, gate=True))
    print("  between the projections and the kernels (a grouped call takes "
          "every operand in the public layout, [B, L, heads x D]):",
          flush=True)
    attention_block_copies(B, H, G, L, D, jnp.bfloat16, args.seed)
    attention_block_copies(B, H, G, L, D, jnp.bfloat16, args.seed,
                           window=window)
    for B, H, G, L, D, window in SIZES["attn_by_kind"]:
        attention_block_copies(B, H, G, L, D, jnp.bfloat16, args.seed,
                               window=window, gate=True)
    B, H, G, half, D, block = SIZES["attn_block_diffusion"]
    attention_block_copies(B, H, G, 2 * half, D, jnp.bfloat16, args.seed,
                           mask=BlockDiffusionMask(half, block))

    hc_stat_vs_jnp(*SIZES["hc"], jnp.bfloat16, args.seed)
    for shape in SIZES["moe_rows"]:
        moe_rows_vs_jnp(*shape, jnp.bfloat16, args.seed)
    for shape in SIZES["moe_act"]:
        moe_act_vs_jnp(*shape, jnp.bfloat16, args.seed)


def _mlp_grads_numpy(params, x, y):
    """The host-plane worker's model in numpy: loss = mean((tanh(x w0)
    w1 - y)^2), gradients for (w0, w1)."""
    import numpy as np

    w0, w1 = (np.asarray(p, np.float64) for p in params)
    h = np.tanh(x @ w0)
    d = 2.0 * (h @ w1 - y) / y.size
    return [x.T @ ((d @ w1.T) * (1.0 - h * h)), h.T @ d]


def phase_hostplane_worker(args):
    """One of the launcher's two local workers. Local rank 0 keeps the
    chip; every other rank pins itself to the CPU before importing jax."""
    rank = int(os.environ["HVD_TPU_RANK"])
    if rank != 0:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu.jax as hvd

    platform = (tpu_devices() if rank == 0 else jax.devices())[0].platform
    if rank != 0 and platform != "cpu":
        raise SystemExit("chip_smoke: rank %d runs on %s" % (rank, platform))
    hvd.init()
    size = hvd.size()

    def data(r):
        g = np.random.RandomState(args.seed + 100 * r)
        return g.randn(64, 256), g.randn(64, 128)

    g = np.random.RandomState(args.seed)
    params0 = [g.randn(256, 256) * 0.05, g.randn(256, 128) * 0.05]
    want = [sum(t) / size for t in zip(*(
        _mlp_grads_numpy(params0, *data(r)) for r in range(size)))]

    params = [jnp.asarray(p, jnp.float32) for p in params0]
    x, y = (jnp.asarray(t, jnp.float32) for t in data(rank))

    def loss(params, x, y):
        return jnp.mean((jnp.tanh(x @ params[0]) @ params[1] - y) ** 2)

    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(loss))(params, x, y)
        check(on_tpu(grads) == (rank == 0),
              "rank %d: gradients computed on the %s" % (rank, platform))
        eager = [hvd.allreduce(t, average=True, name="smoke.eager.%d" % i)
                 for i, t in enumerate(grads)]

        @jax.jit
        def reduced_grads(params, x, y):
            return [hvd.allreduce(t, average=True, name="smoke.jit.%d" % i)
                    for i, t in enumerate(jax.grad(loss)(params, x, y))]

        in_jit = jax.block_until_ready(reduced_grads(params, x, y))
    e_eager = max(rel_err(a, b) for a, b in zip(eager, want))
    e_jit = max(rel_err(a, b) for a, b in zip(in_jit, want))
    check(e_eager <= TOL["host"] and e_jit <= TOL["host"],
          "rank %d (%s): allreduce through the C++ core vs numpy: eager "
          "%.2e, ordered io_callback inside jit %.2e (tol %.0e)"
          % (rank, platform, e_eager, e_jit, TOL["host"]))
    hvd.shutdown()
    print("HOSTPLANE_OK rank=%d platform=%s" % (rank, platform), flush=True)


def ring_lm_case(mesh, seed):
    """The LM's loss and gradients with ring attention over the mesh's
    (dp, sp) axes, and with flash attention on one device: (ring, flash,
    params, (tokens, positions, targets)), both jitted. Parameters come
    from the flash twin's init: a ring model cannot be initialised
    outside shard_map."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import models
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy

    lm = dict(SIZES["lm"], num_heads=SIZES["ring_heads"])
    flash = models.Transformer(models.TransformerConfig(
        attention="flash", dtype=jnp.bfloat16, **lm))
    ring = models.Transformer(models.TransformerConfig(
        attention="ring", sp_axis="sp", dtype=jnp.bfloat16, **lm))
    L, B = SIZES["ring_len"], SIZES["ring_batch"]
    rng = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(rng, (B, L), 0, lm["vocab_size"])
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 tokens.shape)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(lambda r: flash.init(
        r, tokens[:1], positions[:1]))(rng)["params"]

    def loss_of(model):
        def loss(params, x, pos, tgt):
            hidden = model.apply({"params": params}, x, pos,
                                 return_hidden=True)
            return chunked_softmax_cross_entropy(
                hidden, params["lm_head"]["kernel"], tgt,
                chunk=min(512, x.shape[1]))
        return loss

    axes = ("dp", "sp")

    def ring_shard(params, x, pos, tgt):
        loss, grads = jax.value_and_grad(loss_of(ring))(params, x, pos, tgt)
        grads = jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, axes), grads)
        return jax.lax.pmean(loss, axes), grads

    ring_fn = jax.jit(jax.shard_map(
        ring_shard, mesh=mesh, in_specs=(P(), P(*axes), P(*axes), P(*axes)),
        out_specs=(P(), P()), check_vma=False))
    flash_fn = jax.jit(jax.value_and_grad(loss_of(flash)))
    return ring_fn, flash_fn, params, (tokens, positions, targets)


def phase_fourchip(args):
    devs = tpu_devices(4)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import models, parallel

    # (a) ResNet-50, sync BN over the mesh axis: four devices x 64 against
    # one device x 256 on the same images.
    results = {}
    for n in (4, 1):
        mesh = parallel.data_parallel_mesh(devices=devs[:n])
        step, state = resnet_step(
            models.ResNet50, mesh,
            SIZES["resnet_batch_4"] * 4 // n, args.seed, donate=False,
            bn_axis_name="hvd")
        params, opt_state, batch = step.place(*state)
        compiled, text, secs = compile_with_text(step, params, opt_state,
                                                 batch)
        if n == 4:
            check("all-reduce" in text, "four-device ResNet-50 step holds "
                  "all-reduce (compiled in %.1f s)" % secs)
            shard_devs = {s.device for s in batch["x"].addressable_shards}
            check(len(shard_devs) == 4,
                  "the batch's addressable_shards sit on 4 distinct devices")
            check(all(len(p.sharding.device_set) == 4
                      for p in jax.tree_util.tree_leaves(params)),
                  "every parameter is on all 4 devices")
        new, _, loss = compiled(params, opt_state, batch)
        old, new = host_leaves(params), host_leaves(new)
        results[n] = dict(
            loss=float(loss),
            checksum=float(sum(np.abs(x).sum(dtype=np.float64)
                               for x in new)),
            delta=np.concatenate([(a - b).ravel()
                                  for a, b in zip(new, old)]))
        del compiled, state, params, opt_state, batch
    r4, r1 = results[4], results[1]
    e_loss = abs(r4["loss"] - r1["loss"]) / abs(r1["loss"])
    e_sum = abs(r4["checksum"] - r1["checksum"]) / r1["checksum"]
    cos = float(r4["delta"] @ r1["delta"] / (
        np.linalg.norm(r4["delta"]) * np.linalg.norm(r1["delta"])))
    check(np.isfinite(r4["loss"]) and e_loss <= TOL["loss_rel"]
          and e_sum <= TOL["checksum_rel"] and cos >= TOL["update_cosine"],
          "ResNet-50 4x%d vs 1x%d on the same images: loss %.5f vs %.5f "
          "(rel %.1e, tol %.0e), updated-parameter checksum rel %.1e "
          "(tol %.0e), cosine of the two updates %.4f (at least %.2f)"
          % (SIZES["resnet_batch_4"], 4 * SIZES["resnet_batch_4"],
             r4["loss"], r1["loss"], e_loss, TOL["loss_rel"], e_sum,
             TOL["checksum_rel"], cos, TOL["update_cosine"]))
    del results, r4, r1

    # (b) The LM with ring attention over (dp=1, sp=4) against its flash
    # twin on one device.
    mesh = parallel.hybrid_mesh((1, 4), ("dp", "sp"), devices=devs)
    ring_fn, flash_fn, params, (tokens, positions, targets) = ring_lm_case(
        mesh, args.seed)
    L = SIZES["ring_len"]
    for length in (L // 4, L):  # a ring shard's call, the flash twin's
        print_loss_plan(SIZES["ring_batch"], length,
                        SIZES["lm"]["embed_dim"], SIZES["lm"]["vocab_size"],
                        min(512, length), jnp.bfloat16)
    axes = ("dp", "sp")
    rep, seq = NamedSharding(mesh, P()), NamedSharding(mesh, P(*axes))
    ring_args = (jax.device_put(params, rep),) + tuple(
        jax.device_put(t, seq) for t in (tokens, positions, targets))
    compiled, text, secs = compile_with_text(ring_fn, *ring_args)
    n = kernel_calls(text)
    check(n >= 3 and "collective-permute" in text,
          "ring LM L=%d (%d per chip): %d tpu_custom_call (the Pallas ring "
          "kernel) and collective-permute in the program (compiled in "
          "%.1f s)" % (L, L // 4, n, secs))
    t0 = time.perf_counter()
    loss_r, grads_r = jax.block_until_ready(compiled(*ring_args))
    print("  ring LM step (loss and gradients): %.1f ms"
          % (1e3 * (time.perf_counter() - t0)), flush=True)
    grads_r = host_leaves(grads_r)
    del compiled, ring_args

    one = devs[0]
    flash_args = tuple(jax.device_put(t, one)
                       for t in (params, tokens, positions, targets))
    loss_f, grads_f = jax.block_until_ready(flash_fn(*flash_args))
    grads_f = host_leaves(grads_f)
    e_loss = abs(float(loss_r) - float(loss_f)) / abs(float(loss_f))
    num = np.sqrt(sum(float(((a - b) ** 2).sum(dtype=np.float64))
                      for a, b in zip(grads_r, grads_f)))
    den = np.sqrt(sum(float((b ** 2).sum(dtype=np.float64))
                      for b in grads_f))
    check(np.isfinite(float(loss_r)) and e_loss <= TOL["loss_rel"]
          and num / den <= TOL["grad_rel_l2"],
          "ring LM on 4 chips vs flash LM on 1: loss %.5f vs %.5f (rel "
          "%.1e, tol %.0e), gradients relative L2 error %.2e (tol %.0e)"
          % (float(loss_r), float(loss_f), e_loss, TOL["loss_rel"],
             num / den, TOL["grad_rel_l2"]))


PHASES = {"train": phase_train, "kernels": phase_kernels,
          "hostplane-worker": phase_hostplane_worker,
          "fourchip": phase_fourchip}


# --------------------------------------------------------------------------
# Parent: no jax here.
# --------------------------------------------------------------------------

def run_child(name, cmd, env, timeout):
    """Runs one child to its end, echoing its stdout; returns its lines.
    The child and whatever it started are gone when this returns."""
    print("== phase %s" % name, flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    killer = threading.Timer(timeout, kill_group)
    killer.start()
    lines = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            lines.append(line.rstrip("\n"))
        rc = proc.wait()
    finally:
        killer.cancel()
        kill_group()
        proc.wait()
    if rc != 0:
        sys.exit("chip_smoke: phase %s FAILED (exit code %d after %.0f s)"
                 % (name, rc, time.monotonic() - t0))
    print("== phase %s passed in %.0f s" % (name, time.monotonic() - t0),
          flush=True)
    return lines


def device_line(lines):
    for line in lines:
        if line.startswith("DEVICE "):
            return json.loads(line[len("DEVICE "):])
    sys.exit("chip_smoke: the child printed no DEVICE line")


def rebuild_native():
    """The C++ core, rebuilt from the sources in the tree: a library that
    was copied along with them may be older than they are."""
    t0 = time.monotonic()
    proc = subprocess.run(
        ["make", "-B", "-j", str(os.cpu_count() or 4)],
        cwd=os.path.join(REPO, "horovod_tpu", "native"),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout[-4000:])
        sys.exit("chip_smoke: phase hostplane FAILED (make exit code %d)"
                 % proc.returncode)
    print("== libhorovod_tpu.so rebuilt from the tree's sources in %.0f s"
          % (time.monotonic() - t0), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase and what it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase:
        try:
            PHASES[args.phase](args)
        except PhaseFailed as e:
            sys.exit("chip_smoke: check failed in phase %s: %s"
                     % (args.phase, e))
        return 0

    assert "jax" not in sys.modules, "the parent must stay off jax"
    env = dict(os.environ)
    use_compile_cache(env)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PYTHONUNBUFFERED", None)
    me = [sys.executable, os.path.abspath(__file__), "--seed",
          str(args.seed), "--phase"]

    if args.chips == 4:
        device = device_line(run_child("fourchip", me + ["fourchip"], env,
                                       1100))
    else:
        device = device_line(run_child("train", me + ["train"], env, 500))
        run_child("kernels", me + ["kernels"], env, 700)
        rebuild_native()
        lines = run_child(
            "hostplane",
            [sys.executable, "-m", "horovod_tpu.run.run", "-np", "2", "--"]
            + me + ["hostplane-worker"], env, 300)
        for want in ("HOSTPLANE_OK rank=0 platform=tpu",
                     "HOSTPLANE_OK rank=1 platform=cpu"):
            if not any(want in line for line in lines):
                sys.exit("chip_smoke: phase hostplane FAILED (no %r line)"
                         % want)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
