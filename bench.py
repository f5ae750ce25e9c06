"""ResNet-50 synthetic benchmark — the TPU equivalent of the reference's
`examples/tensorflow2_synthetic_benchmark.py:110-131` (batch 64/device,
synthetic ImageNet-shaped data, warmup then timed rounds, images/sec).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "step_time_ms": N, "tflops_per_chip": N, "mfu": N, "baseline": "...",
   "platform": "tpu", "device_kind": "...", "device_count": N}

The throughput path measures the chip: it fails when JAX finds no TPU
(it never times the CPU under the name of a device metric), and a
device_kind that is missing from the peaks table is an error.

Baseline: the reference's only published absolute throughput is ResNet-101
at 1656.82 images/sec over 16 Pascal P100s (`docs/benchmarks.rst:43`) =
103.55 images/sec/GPU; `vs_baseline` is images/sec/chip over that number
(cross-model when --model != resnet101 — the `baseline` field says so).
Rows with no reference measurement at all (LM configs, word2vec, the
zoo aggregate) emit `"vs_baseline": null` — never a literal 0.0 that an
aggregator would read as a measured 0% delta.

MFU honesty: FLOPs per step come from XLA's own cost analysis of the
compiled train step (not a hand-count), divided by measured step time and
the chip's peak bf16 FLOP/s.
"""

import argparse
import json
import os
import re
import socket
import statistics
import subprocess
from functools import partial
import sys
import time

import numpy as np

from horovod_tpu.run.util import use_compile_cache

REPO = os.path.dirname(os.path.abspath(__file__))

# Before anything imports jax: every zoo row and every --all-models child
# shares one persistent compile cache (placed by JAX_COMPILATION_CACHE_DIR
# when set, else fixed inside the checkout).
use_compile_cache()

# Peak bf16 dense FLOP/s per chip, by jax device_kind substring (public
# TPU spec sheet numbers). Used only for the MFU denominator.
_PEAK_BF16 = [
    ("v6", 918e12), ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5", 459e12), ("v4 lite", 138e12), ("v4", 275e12), ("v3", 123e12),
    ("v2", 45e12),
]


def peak_flops(device):
    """Peak bf16 FLOP/s of `device`; a device_kind that is not in the
    table is an error, never a missing `mfu` field."""
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK_BF16:
        if key in kind:
            return val
    raise ValueError(
        "bench: device_kind %r is not in the peaks table (_PEAK_BF16); "
        "add its published peak before measuring on it"
        % getattr(device, "device_kind", None))


def require_tpu():
    """The devices of the measured path. The throughput benchmark
    measures the chip: a run that finds no TPU fails instead of timing
    the CPU under the name of a device metric."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            "bench: the throughput benchmark needs a TPU, found "
            "platform=%s (%d device(s)); CPU-only count modes such as "
            "--serve or --trace-overhead need no chip"
            % (devices[0].platform, len(devices)))
    return devices


def device_fields(devices):
    """What every throughput row says about where it ran."""
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def compiled_flops(step, *args):
    """Per-device FLOPs of the compiled step, from XLA's own cost
    analysis (no hand-counting)."""
    try:
        cost = step.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost["flops"])
    except Exception as e:  # cost analysis is best-effort diagnostics
        print("bench: cost_analysis unavailable (%s)" % e, file=sys.stderr)
        return None


def _time_steps(step, state, batch, iters, warmup=3):
    """Median-of-3 step time (seconds), each round ending in
    block_until_ready."""
    import jax

    params_p, opt_state = state
    for _ in range(warmup):
        params_p, opt_state, loss = step(params_p, opt_state, batch)
    jax.block_until_ready(loss)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            params_p, opt_state, loss = step(params_p, opt_state, batch)
        jax.block_until_ready(loss)
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1]


def scaling_worker(args):
    """Weak-scaling measurement subprocess (virtual CPU mesh): runs the
    full jitted DP train step over an `n`-device mesh (or the same total
    work on one device with --scaling-single — the contention-fair
    baseline on a shared-core host) and prints a JSON step-time line."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import data_parallel_mesh, make_train_step

    n = args.scaling_worker
    b = args.scaling_batch
    width, layers = 1024, 4
    rng = jax.random.PRNGKey(0)

    def init_params():
        ks = jax.random.split(rng, layers)
        return [jax.random.normal(k, (width, width), jnp.float32) * 0.02
                for k in ks]

    def loss_fn(params, batch):
        h = batch["x"]
        for w in params:
            h = jnp.tanh(h @ w)
        return jnp.mean((h - batch["y"]) ** 2)

    params = init_params()
    opt = optax.sgd(0.01)
    total_batch = b * n
    x = jax.random.normal(rng, (total_batch, width), jnp.float32)
    y = jax.random.normal(rng, (total_batch, width), jnp.float32)

    # Explicitly the cpu backend: the scaling evidence is a virtual
    # CPU-mesh measurement wherever it runs.
    cpus = jax.devices("cpu")
    if len(cpus) < n:
        raise RuntimeError(
            "scaling worker expected >=%d cpu devices, got %d (XLA_FLAGS "
            "device-count override lost?)" % (n, len(cpus)))
    devices = cpus[:1] if args.scaling_single else cpus[:n]
    mesh = data_parallel_mesh(devices=devices)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    params_p, opt_state, batch = step.place(params, opt.init(params),
                                            {"x": x, "y": y})
    dt = _time_steps(step, (params_p, opt_state), batch, args.num_iters)
    print(json.dumps({"n": n, "single": bool(args.scaling_single),
                      "step_ms": round(dt * 1000.0, 3)}))


def _run_weak_scaling(batch, iters):
    """Spawns scaling_worker subprocesses on a virtual CPU mesh; returns
    rows of {n, mesh_ms, single_ms, efficiency}."""
    rows = []
    for n in (1, 2, 4, 8):
        res = {}
        for single in (False, True):
            from horovod_tpu.run.util import cpu_worker_env
            env = cpu_worker_env()
            # Hard platform pin (not just NAME-priority): the mesh MUST
            # be the virtual CPU devices.
            env["JAX_PLATFORMS"] = "cpu"
            # Appended last: XLA's flag parsing takes the last
            # occurrence, so an inherited device-count flag can't
            # silently shrink the mesh under us.
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=%d" % n)
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--scaling-worker", str(n),
                   "--scaling-batch", str(batch),
                   "--num-iters", str(iters)]
            if single:
                cmd.append("--scaling-single")
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 env=env, timeout=1200)
            if out.returncode != 0:
                raise RuntimeError("scaling worker n=%d failed:\n%s" %
                                   (n, out.stderr))
            res[single] = json.loads(out.stdout.strip().splitlines()[-1])
        mesh_ms = res[False]["step_ms"]
        single_ms = res[True]["step_ms"]
        rows.append({"n": n, "mesh_step_ms": mesh_ms,
                     "single_device_same_work_ms": single_ms,
                     "efficiency": round(single_ms / mesh_ms, 3)})
        print("weak-scaling n=%d: mesh %.1f ms, single-device-same-work "
              "%.1f ms, efficiency %.3f" %
              (n, mesh_ms, single_ms, rows[-1]["efficiency"]),
              file=sys.stderr)
    return rows


def _reserve_ports(n):
    """Reserves n ephemeral ports, HOLDING the sockets (SO_REUSEPORT)
    so no other process can be handed one before the slowest worker
    binds; workers bind alongside via HVD_TPU_LISTEN_REUSEPORT=1 (the
    same mechanism rendezvous.reserve_port(hold=True) uses)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        if hasattr(socket, "SO_REUSEPORT"):
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    return socks, ports


def _spawn_local_workers(n, script, extra_env=None, rank_env=None):
    """Reserves ports and spawns n local control-plane worker
    subprocesses (numpy+ctypes only) of tests/`script` with the shared
    rank/rendezvous env; returns (procs, socks) — the caller owns
    communicate/kill and closing the sockets. `rank_env[r]` adds
    per-rank overrides (e.g. a forced (local, cross) topology)."""
    socks, ports = _reserve_ports(n)
    addrs = ",".join("127.0.0.1:%d" % p for p in ports)
    procs = []
    for r in range(n):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update({
            "HVD_TPU_RANK": str(r), "HVD_TPU_SIZE": str(n),
            "HVD_TPU_LOCAL_RANK": str(r), "HVD_TPU_LOCAL_SIZE": str(n),
            "HVD_TPU_CROSS_RANK": "0", "HVD_TPU_CROSS_SIZE": "1",
            "HVD_TPU_ADDRS": addrs, "HVD_TPU_CYCLE_TIME": "0",
            "HVD_TPU_LISTEN_REUSEPORT": "1",
            # Interpreter startup for n ranks is serialized on small
            # hosts; the default 60s accept timeout starves out at
            # high rank counts.
            "HVD_TPU_START_TIMEOUT": str(max(120, 4 * n)),
        })
        if extra_env:
            # A None value REMOVES the key — e.g. the autotune A/B must
            # drop the harness's HVD_TPU_CYCLE_TIME=0 pin (an env-pinned
            # knob is excluded from tuning; the A/B measures defaults).
            for k, v in extra_env.items():
                if v is None:
                    env.pop(k, None)
                else:
                    env[k] = v
        if rank_env and r in rank_env:
            env.update(rank_env[r])
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", script)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    return procs, socks


def _run_negotiation_bench(n, iters, extra_env=None, timeout=1800):
    """Launches n local control-plane workers (numpy+ctypes only);
    returns (rank-0 negotiation latency us/op, protocol counters by
    rank for ranks 0 and 1 — bytes/messages/cycle kinds)."""
    env = {"HVD_TPU_BENCH_ITERS": str(iters)}
    env.update(extra_env or {})
    procs, socks = _spawn_local_workers(n, "negotiation_bench_worker.py",
                                        env)
    outputs = []
    us = None
    counters = {}
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            if p.returncode != 0:
                raise RuntimeError("rank %d failed:\n%s" % (r, out))
            m = re.search(r"NEGOTIATION_US_PER_OP ([\d.]+)", out)
            if m:
                us = float(m.group(1))
            m = re.search(r"PROTOCOL_COUNTERS (\{.*\})", out)
            if m:
                d = json.loads(m.group(1))
                counters[d["rank"]] = d
            m = re.search(r"METRICS_SNAPSHOT (\{.*\})", out)
            if m:
                counters.setdefault(r, {})["metrics"] = json.loads(m.group(1))
            m = re.search(r"TRACE_COUNTERS (\{.*\})", out)
            if m:
                counters.setdefault(r, {})["trace"] = json.loads(m.group(1))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()
    if us is None:
        raise RuntimeError(
            "no NEGOTIATION_US_PER_OP line in any worker output; rank 0 "
            "said:\n%s" % (outputs[0] if outputs else "<no output>"))
    return us, counters


# Model-zoo sweep configs: the models in the reference's published
# scaling table (docs/benchmarks.rst:13-14) plus the long-context
# transformer and the GroupNorm roofline experiment. Batch/size choices
# are each model's measured-fastest from PERF.md.
_ZOO = [
    ("resnet50", ["--batch-size", "256"]),
    # Fused Pallas BN statistics vs the XLA lowering — the round-4
    # kernel's primary and secondary measurement targets.
    ("resnet50pbn", ["--batch-size", "256"]),
    ("resnet50gn", ["--batch-size", "256"]),
    ("resnet50nf", ["--batch-size", "256"]),
    # Round 10: the traffic-lean graph-level BN (custom-VJP x_hat/mask
    # recompute, ops/batch_norm.py — the island-tax lesson turned into
    # shipped code) and the AGC-trainable norm-free depth row.
    ("resnet50lean", ["--batch-size", "256"]),
    ("resnet101nf", ["--batch-size", "128"]),
    ("resnet101", ["--batch-size", "128"]),
    ("vgg16", ["--batch-size", "64"]),
    ("inception3", ["--batch-size", "128", "--image-size", "299"]),
    ("inception3pbn", ["--batch-size", "128", "--image-size", "299"]),
    ("transformer", []),
    ("transformer", ["--moe-experts", "8", "--fused-xent"]),
    # Long-context row (VERDICT r3 item 8): L=8192 MUST use the fused
    # streaming xent (dense f32 logits at this length exceed v5e HBM)
    # and a reduced batch.
    ("transformer", ["--seq-len", "8192", "--fused-xent",
                     "--tokens-batch", "2"]),
    # TPU-native head shape at long context: 6 x D=128 heads, identical
    # FLOPs to GPT-2's 12 x D=64, but every attention matmul runs the
    # MXU at full width (D=64 caps contraction/output at 64 of 128
    # lanes). Measured v5e: 36.4% vs 27.6% kernel-counted MFU.
    ("transformer", ["--seq-len", "8192", "--fused-xent",
                     "--tokens-batch", "2", "--num-heads", "6"]),
    # Fused rotary alone (isolates the saved q/k HBM round trip), then
    # GQA G=2 on top (kv projections a third the size, grouped-rows
    # kernel layout) — the modern-LM kernel surface at the same
    # long-context shape as the h6 row above.
    ("transformer", ["--seq-len", "8192", "--fused-xent",
                     "--tokens-batch", "2", "--num-heads", "6",
                     "--fused-rope"]),
    ("transformer", ["--seq-len", "8192", "--fused-xent",
                     "--tokens-batch", "2", "--num-heads", "6",
                     "--num-kv-heads", "2", "--fused-rope"]),
    # Sparse (indices,values) embedding-gradient plane vs the dense
    # full-table path — BASELINE.json config #4's IndexedSlices
    # rationale with an on-chip number (both variants in one row;
    # vocab matches the reference example's 50000 — the sparse win
    # grows linearly with vocab, see PERF.md's V-sweep).
    ("word2vec", ["--vocab-size", "50000", "--num-iters", "100"]),
]


def all_models_main(args):
    """bench.py --all-models: runs every zoo config in a subprocess
    (clean device state per model) and prints one JSON line with all
    results. A chip belongs to one process at a time, so this parent
    must never initialise JAX: each child takes the chip in turn and
    fails by itself when there is none."""
    assert "jax" not in sys.modules, \
        "--all-models parent imported jax: its children could not " \
        "have the chip"
    results = []
    for model, extra in _ZOO:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--model", model,
               "--num-warmup", str(args.num_warmup),
               "--num-rounds", str(args.num_rounds),
               "--num-iters", str(args.num_iters)] + extra
        print("=== %s ===" % model, file=sys.stderr)
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=3600)
        sys.stderr.write(proc.stderr[-2000:])
        if proc.returncode != 0:
            raise RuntimeError("bench for %s failed:\n%s"
                               % (model, proc.stderr[-4000:]))
        results.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    best_mfu = max(r.get("mfu", 0.0) or 0.0 for r in results)
    emit({
        "metric": "model_zoo_sweep",
        "value": round(best_mfu, 3),
        "unit": "best_mfu",
        "vs_baseline": None,
        "baseline": "per-model details in `models`",
        "models": results,
    })


def zoo_headroom_main(args):
    """bench.py --zoo-headroom (PERF.md "Sharded-update memory
    headroom"): per zoo model, the TRAINING-STATE residency — params,
    gradients, Adam moments — against the v5e 16 GiB HBM budget, with
    the ZeRO-style sharded update (HVD_TPU_SHARDED_UPDATE=1) applied to
    the optimizer state at N ranks.

    Byte accounting is exact: parameter trees come from
    jax.eval_shape over the real model init (no compute, no chip), the
    Adam state from optax.adam's init over the same tree, and the
    sharded per-rank optimizer bytes divide by N per the 1/N law
    BENCH_r07 measured EXACTLY on the wire (opt_state_bytes gauge:
    8388608 -> 4194304/2097152 B at N=2/4). Activations are deliberately
    excluded (they depend on the measured step context; see the
    per-model sections of PERF.md).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models

    n_shard = int(os.environ.get("HVD_TPU_HEADROOM_RANKS", "8"))
    hbm = 16 * (1 << 30)  # v5e
    rng = jax.random.PRNGKey(0)

    def tree_bytes(tree):
        return int(sum(int(np.prod(l.shape, dtype=np.int64)) *
                       np.dtype(l.dtype).itemsize
                       for l in jax.tree_util.tree_leaves(tree)))

    # One row per DISTINCT parameter tree — the zoo's seq-len/kernel
    # variants share params with these base configs, so this list IS
    # the deduplicated zoo.
    rows = []
    zoo_cases = [
        ("resnet50", lambda: models.ResNet50()),
        ("resnet101", lambda: models.ResNet101()),
        ("vgg16", lambda: models.VGG16()),
        ("inception3", lambda: models.InceptionV3()),
        ("transformer_gpt2s", lambda: models.Transformer(
            models.TransformerConfig(
                vocab_size=32000, num_layers=12, num_heads=12,
                embed_dim=768, mlp_dim=3072, attention="dense",
                dtype=jnp.float32, max_seq_len=2048))),
        ("transformer_moe8", lambda: models.Transformer(
            models.TransformerConfig(
                vocab_size=32000, num_layers=12, num_heads=12,
                embed_dim=768, mlp_dim=3072, attention="dense",
                dtype=jnp.float32, max_seq_len=2048, moe_experts=8,
                moe_every=2, moe_capacity_factor=1.25))),
    ]
    for name, build in zoo_cases:
        model = build()
        if name.startswith("transformer"):
            tokens = jnp.zeros((1, 128), jnp.int32)
            pos = jnp.zeros((1, 128), jnp.int32)
            shapes = jax.eval_shape(model.init, rng, tokens, pos)
        else:
            img = jnp.zeros((1, 224, 224, 3), jnp.float32)
            shapes = jax.eval_shape(model.init, rng, img)
        params = shapes["params"] if "params" in shapes else shapes
        p_bytes = tree_bytes(params)
        opt_shapes = jax.eval_shape(
            lambda p: optax.adam(1e-3).init(p), params)
        o_bytes = tree_bytes(opt_shapes)
        repl_state = p_bytes * 2 + o_bytes  # params + grads + moments
        shard_state = p_bytes * 2 + o_bytes // n_shard
        rows.append({
            "model": name,
            "param_bytes": p_bytes,
            "grad_bytes": p_bytes,
            "adam_state_bytes": o_bytes,
            "sharded_adam_state_bytes_per_rank": o_bytes // n_shard,
            "train_state_replicated": repl_state,
            "train_state_sharded": shard_state,
            "headroom_replicated": hbm - repl_state,
            "headroom_sharded": hbm - shard_state,
            "headroom_delta_bytes": (hbm - shard_state) -
                                    (hbm - repl_state),
            "headroom_delta_pct_of_hbm": round(
                100.0 * (o_bytes - o_bytes // n_shard) / hbm, 3),
        })
        print("%-20s params %8.1f MB  adam %8.1f MB -> %7.1f MB/rank "
              "(N=%d)  headroom +%5.1f MB"
              % (name, p_bytes / 2**20, o_bytes / 2**20,
                 o_bytes / n_shard / 2**20, n_shard,
                 (o_bytes - o_bytes // n_shard) / 2**20),
              file=sys.stderr)

    emit({
        "metric": "zoo_sharded_headroom_delta",
        "unit": "bytes_headroom_gained_max_model_n%d" % n_shard,
        "value": max(r["headroom_delta_bytes"] for r in rows),
        "ranks": n_shard,
        "hbm_budget_bytes": hbm,
        # Provenance, honestly: this is MODELED accounting (eval_shape
        # bytes + the r07-measured 1/N law), not a job that ran with
        # the env knob — record the env as it actually was.
        "sharded_update_env": os.environ.get("HVD_TPU_SHARDED_UPDATE",
                                             "<unset>"),
        "accounting": "modeled (eval_shape bytes x BENCH_r07 1/N law)",
        "models": rows,
        "vs_baseline": None,
        "baseline": "same-run replicated Adam state; sharded per-rank "
                    "bytes apply BENCH_r07's exactly-measured 1/N "
                    "opt_state_bytes law; activations excluded (see "
                    "the measured per-model step contexts in PERF.md)",
    })
    return 0


def durable_commit_main(args):
    """bench.py --durable-commit: measures ElasticState.commit() latency
    with the durable writer OFF vs ON (async sharded CRC'd writes to a
    tmp dir, elastic/durable.py) — the "training never blocks on
    storage" claim measured, not asserted. Acceptance (ISSUE 5):
    durable-on commit latency within 10% of durable-off."""
    import shutil
    import statistics
    import tempfile

    from horovod_tpu.elastic.state import ElasticState

    mb = 8
    n_arrays = 8
    params = {"p%d" % i: np.arange(mb * 1024 * 1024 // n_arrays // 4,
                                   dtype=np.float32) + i
              for i in range(n_arrays)}
    state = ElasticState(params=params, step=0)
    iters = 30

    def time_commits(count):
        times = []
        for _ in range(count):
            state.step += 1
            t0 = time.perf_counter()
            state.commit()
            times.append(time.perf_counter() - t0)
        return times

    time_commits(3)  # warmup (page in the deep-copy path)
    off = time_commits(iters)
    tmpdir = tempfile.mkdtemp(prefix="hvd_durable_bench_")
    try:
        state.enable_durable(tmpdir)
        on = time_commits(iters)
        drained = state._durable.flush(timeout=120)
        wrote = state._durable.last_durable_step
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    off_ms = statistics.median(off) * 1e3
    on_ms = statistics.median(on) * 1e3
    emit({
        "metric": "durable_commit_overhead",
        "value": round(on_ms / off_ms, 3),
        "unit": "x_commit_latency_durable_on_vs_off",
        "commit_ms_off": round(off_ms, 3),
        "commit_ms_on": round(on_ms, 3),
        "state_mb": mb,
        "writer_drained": bool(drained),
        "last_durable_step": wrote,
        "vs_baseline": None,
        "baseline": "durable-off in-memory commit (same %dMB state); "
                    "acceptance: <= 1.10 (writes overlap training)" % mb,
    })
    return 0


def _serve_port_block(n):
    """A base port with n consecutive free ports (probe-and-release;
    the serve plane needs CONTIGUOUS ports: endpoint = base + wid)."""
    import random
    for _ in range(64):
        base = random.randint(21000, 55000)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
        return base
    raise RuntimeError("no free port block found")


def serve_main(args):
    """bench.py --serve (docs/SERVE.md, PERF.md round 12): the serving
    plane under seeded open-loop load on this container's CPUs.

    Phase 1, the RPS/latency curve: a fixed 2-replica pool (numpy
    forward, HVD_TPU_SERVE_JIT=0 — the bench measures the SERVING
    machinery: admission, micro-batching, HTTP, split-back; not XLA)
    takes open-loop load at stepped offered rates; each row records
    achieved RPS and p50/p99 latency, with every response verified
    against the weight set its fingerprint names (ok must equal
    offered — the curve is invalid if the pool dropped or mislabeled
    anything).

    Phase 2, the autoscale row: a pool deliberately born TOO SMALL
    (1 replica, ceiling 2) takes a traffic step; the supervisor's
    queue-pressure autoscaler must absorb the freed capacity (grow to
    2) DURING the step, and the step must still finish loss-free —
    elasticity as a serving property, not just a training one.
    """
    import tempfile
    import threading

    from horovod_tpu.elastic.state import EXIT_DRAINED
    from horovod_tpu.serve import model as smodel
    from horovod_tpu.serve.loadgen import run_load
    from horovod_tpu.serve.supervisor import ServeSupervisor
    from horovod_tpu.serve.swap import publish_leaves

    tmpdir = tempfile.mkdtemp(prefix="hvd-serve-bench-")

    def pool(np_initial, max_np, port_base, model_name, dim, ckpt,
             **sup_kwargs):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_SERVE_JIT": "0",
            "HVD_TPU_SERVE_MODEL": model_name,
            "HVD_TPU_SERVE_DIM": str(dim),
            "HVD_TPU_SERVE_PORT": str(port_base),
            "HVD_TPU_CKPT_DIR": ckpt,
        })
        sup = ServeSupervisor(
            [sys.executable, "-m", "horovod_tpu.serve.replica"],
            {"localhost": max_np}, min_replicas=1, max_replicas=max_np,
            np_initial=np_initial, port_base=port_base, env=env,
            **sup_kwargs)
        box = {}
        t = threading.Thread(
            target=lambda: box.update(
                rc=sup.driver.run(install_signal_handlers=False)),
            daemon=True)
        t.start()
        deadline = time.time() + 60
        while True:
            up = sum(1 for v in sup.replica_views(timeout=1.0)
                     if v.get("state") == "serving")
            if up >= np_initial:
                break
            if time.time() > deadline:
                raise RuntimeError("serve pool never became healthy")
            time.sleep(0.1)
        return sup, t, box

    def shutdown(sup, t, box):
        sup.driver.request_drain("all")
        t.join(timeout=90)
        return box.get("rc")

    # --- Phase 1: the curve on a fixed 2-replica pool (cheap affine
    # forward — this phase measures the serving MACHINERY's latency).
    dim = 16
    leaves = smodel.init_leaves("affine", dim, seed=1)
    crc = smodel.fingerprint(leaves)
    by_crc = {crc: leaves}
    ckpt1 = os.path.join(tmpdir, "curve")
    publish_leaves(ckpt1, 10, leaves)
    rates = [20, 40, 80]
    curve = []
    sup, t, box = pool(2, 2, _serve_port_block(2), "affine", dim, ckpt1)
    try:
        for i, rate in enumerate(rates):
            res, wall = run_load(sup.endpoints, rate=rate,
                                 duration=3.0, dim=dim, seed=12,
                                 leaves_by_crc=by_crc, workers=8,
                                 total_deadline=10.0,
                                 rid_base=i * 100000)
            row = res.summary(wall)
            assert not res.mismatches, res.mismatches[:3]
            curve.append({
                "offered_rps": rate,
                "achieved_rps": row["rps_achieved"],
                "ok": row["ok"], "errors": row["errors"],
                "p50_ms": row["p50_ms"], "p99_ms": row["p99_ms"],
            })
            print("bench: serve curve %d rps -> %.1f achieved, "
                  "p50 %.1fms p99 %.1fms (%d ok, %d err)"
                  % (rate, row["rps_achieved"], row["p50_ms"],
                     row["p99_ms"], row["ok"], row["errors"]),
                  file=sys.stderr)
    finally:
        rc = shutdown(sup, t, box)
    curve_ok = (rc == EXIT_DRAINED and
                all(r["errors"] == 0 for r in curve))

    # --- Phase 2: the traffic step against a 1-replica pool that may
    # grow to 2; the autoscaler runs on its own cadence thread. The
    # forward is a dim-2048 mlp (~4ms/row in numpy — one replica tops
    # out around 200-250 rps), so the 280 rps step is a GENUINE
    # overload only the scale-up can absorb.
    step_dim, step_rate = 2048, 280
    step_leaves = smodel.init_leaves("mlp", step_dim, seed=2)
    step_by_crc = {smodel.fingerprint(step_leaves): step_leaves}
    ckpt2 = os.path.join(tmpdir, "step")
    publish_leaves(ckpt2, 10, step_leaves)
    sup, t, box = pool(1, 2, _serve_port_block(2), "mlp", step_dim,
                       ckpt2, scale_up_queue=2.0,
                       autoscale_interval=0.2)
    stop = threading.Event()

    def autoscale_loop():
        while not stop.wait(0.2):
            try:
                sup.autoscale_once()
            except Exception:
                pass

    scaler = threading.Thread(target=autoscale_loop, daemon=True)
    scaler.start()
    try:
        replicas_before = len(sup.driver.live_workers())
        res, wall = run_load(sup.endpoints, rate=step_rate,
                             duration=4.0, dim=step_dim, seed=13,
                             model_name="mlp",
                             leaves_by_crc=step_by_crc, workers=8,
                             total_deadline=30.0, rid_base=900000)
        row = res.summary(wall)
        replicas_after = len(sup.driver.live_workers())
        events = list(sup.scale_events)
    finally:
        stop.set()
        rc2 = shutdown(sup, t, box)
    autoscale_row = {
        "offered_rps": step_rate,
        "model": "mlp", "dim": step_dim,
        "replicas_before": replicas_before,
        "replicas_after": replicas_after,
        "scale_events": len(events),
        "achieved_rps": row["rps_achieved"],
        "ok": row["ok"], "errors": row["errors"],
        "p99_ms": row["p99_ms"],
    }
    print("bench: serve autoscale step %d rps: %d -> %d replicas "
          "(%d event(s)), %d ok, %d err"
          % (step_rate, replicas_before, replicas_after, len(events),
             row["ok"], row["errors"]), file=sys.stderr)
    import shutil
    shutil.rmtree(tmpdir, ignore_errors=True)

    scaled = replicas_after > replicas_before and len(events) >= 1
    emit({
        "metric": "serve_open_loop_p99_ms",
        "value": curve[-1]["p99_ms"],
        "unit": "ms_p99_at_%drps_2_replicas" % rates[-1],
        "dim": dim,
        "curve": curve,
        "autoscale": autoscale_row,
        "autoscaled_on_traffic_step": bool(scaled),
        "drained_clean": bool(curve_ok and rc2 == EXIT_DRAINED),
        "vs_baseline": None,
        "baseline": "no prior serving round (BENCH_r12 introduces the "
                    "plane); acceptance: zero errors/mismatches on the "
                    "curve, autoscale 1->2 during the traffic step",
    })
    return 0 if (curve_ok and scaled and rc2 == EXIT_DRAINED) else 1


def _run_compression_bench(n, iters, mb, mode, timeout=900):
    """Launches n local workers allreducing an `mb`-MB f32 payload under
    compression `mode` (control-plane + numpy only, no jax); returns
    per-rank dicts of wall time and socket-layer wire counters."""
    procs, socks = _spawn_local_workers(
        n, "compression_bench_worker.py",
        {"HVD_TPU_BENCH_ITERS": str(iters),
         "HVD_TPU_BENCH_MB": str(mb),
         "HVD_TPU_COMPRESSION": mode})
    outputs = []
    rows = {}
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            if p.returncode != 0:
                raise RuntimeError("compression bench rank %d (mode %s) "
                                   "failed:\n%s" % (r, mode, out))
            m = re.search(r"COMPRESSION_BENCH (\{.*\})", out)
            if m:
                d = json.loads(m.group(1))
                rows[d["rank"]] = d
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()
    if 0 not in rows:
        raise RuntimeError("no COMPRESSION_BENCH line from rank 0:\n%s"
                           % (outputs[0] if outputs else "<no output>"))
    return rows


def _compression_convergence(steps=40, tolerance=0.05):
    """Trains the same tiny MLP regression twice on an 8-device virtual
    CPU mesh — exact fp32 psum gradients vs the int8 block-quantized
    ring — and compares the loss curves. Returns the curve stats; the
    caller asserts `loss_match`."""
    # The int8 ring only engages over a >= 2-device mesh: force the
    # virtual CPU device count BEFORE jax initializes, and fail loudly
    # if a pre-initialized 1-device jax sneaks through — a 1-device
    # "A/B" would be two identical fp32 runs and a vacuous loss_match.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ring import ring_allreduce

    cpus = jax.devices("cpu")
    n = min(8, len(cpus))
    if n < 2:
        raise RuntimeError(
            "compression convergence A/B needs >= 2 cpu devices; got %d "
            "(jax initialized before the device-count flag applied?)" % n)
    mesh = Mesh(np.array(cpus[:n]), ("dp",))
    rng = np.random.RandomState(0)
    d_in, d_h, batch = 64, 128, 32 * n
    x = rng.randn(batch, d_in).astype(np.float32)
    w_true = rng.randn(d_in, 1).astype(np.float32)
    y = np.tanh(x @ w_true) + 0.01 * rng.randn(batch, 1).astype(np.float32)

    def init_params():
        r = np.random.RandomState(1)
        return {"w1": jnp.asarray(r.randn(d_in, d_h).astype(np.float32)
                                  * 0.1),
                "w2": jnp.asarray(r.randn(d_h, 1).astype(np.float32) * 0.1)}

    def make_step(mode, lr=0.05):
        def step(params, bx, by):
            def loss_fn(p):
                h = jnp.tanh(bx @ p["w1"])
                return jnp.mean((h @ p["w2"] - by) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(params)
            if mode == "none":
                g = {k: lax.psum(v, "dp") / n for k, v in g.items()}
            else:
                g = {k: ring_allreduce(v, "dp", compression=mode) / n
                     for k, v in g.items()}
            params = {k: params[k] - lr * g[k] for k in params}
            return params, lax.pmean(loss, "dp")

        return jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
            out_specs=(P(), P()), check_vma=False))

    curves = {}
    for mode in ("none", "int8"):
        step = make_step(mode)
        params = init_params()
        losses = []
        for _ in range(steps):
            params, loss = step(params, x, y)
            losses.append(float(loss))
        curves[mode] = losses

    ref = np.asarray(curves["none"])
    got = np.asarray(curves["int8"])
    # Relative divergence after the first few steps (early steps have
    # near-zero denominators as both curves drop fast).
    rel = np.abs(got[3:] - ref[3:]) / (np.abs(ref[3:]) + 1e-8)
    return {
        "steps": steps, "devices": n,
        "fp32_final_loss": round(float(ref[-1]), 6),
        "int8_final_loss": round(float(got[-1]), 6),
        "max_rel_divergence_after_step3": round(float(rel.max()), 4),
        "tolerance": tolerance,
        "loss_match": bool(rel.max() < tolerance),
    }


def compression_main(args):
    """bench.py --compression {none,bf16,int8}: A/B the host data
    plane's wire compression stage (docs/COMPRESSION.md). Measures the
    actual data-ring socket bytes (net_ring_bytes counters, headers
    included) and wall time per 4MB allreduce with compression off vs
    the requested mode, plus the int8-vs-fp32 convergence run.
    Acceptance (ISSUE 6): bf16 moves >= 1.9x fewer allreduce wire bytes
    than none, and the int8 loss curve matches fp32 within tolerance."""
    mode = args.compression
    iters, mb = max(10, args.num_iters), 4
    rows = {"none": _run_compression_bench(2, iters, mb, "none")}
    if mode != "none":
        rows[mode] = _run_compression_bench(2, iters, mb, mode)

    def rank0(m, field):
        return rows[m][0][field]

    none_bytes = rank0("none", "ring_bytes_sent")
    out = {
        "metric": "compression_allreduce_wire_reduction",
        "unit": "x_ring_bytes_none_over_%s" % mode,
        "mode": mode,
        "payload_mb": mb, "iters": iters, "ranks": 2,
        "none_ring_bytes_sent": none_bytes,
        "none_us_per_op": rank0("none", "us_per_op"),
    }
    if mode != "none":
        mode_bytes = rank0(mode, "ring_bytes_sent")
        out["value"] = round(none_bytes / mode_bytes, 3)
        out["%s_ring_bytes_sent" % mode] = mode_bytes
        out["%s_us_per_op" % mode] = rank0(mode, "us_per_op")
        out["codec_ratio"] = round(
            rank0(mode, "codec_bytes_in") /
            max(1, rank0(mode, "codec_bytes_out")), 3)
        print("compression %s: wire %.2fx smaller (%d -> %d B), "
              "%.0f -> %.0f us/op"
              % (mode, out["value"], none_bytes, mode_bytes,
                 out["none_us_per_op"], out["%s_us_per_op" % mode]),
              file=sys.stderr)
    else:
        out["value"] = 1.0

    out["convergence_int8_vs_fp32"] = _compression_convergence()
    if not out["convergence_int8_vs_fp32"]["loss_match"]:
        raise RuntimeError("int8 convergence diverged from fp32: %s"
                           % out["convergence_int8_vs_fp32"])
    # No earlier record has the compression stage, so the baseline is the
    # same-run compression=none wire bytes; vs_baseline is the measured
    # reduction over that baseline.
    out["vs_baseline"] = out["value"]
    out["baseline"] = ("same-run compression=none data-ring bytes "
                      "(no earlier record has the compression stage); "
                      "acceptance: bf16 >= 1.9x, int8 convergence "
                      "loss_match true")
    emit(out)
    return 0


def _run_shm_bench(n, iters, mode, shm, extra_env=None, rank_env=None,
                   timeout=900):
    """Launches n local workers allreducing several payload sizes under
    compression `mode` with the shared-memory plane forced on or off;
    returns per-rank dicts of per-size wall time and transport
    counters."""
    env = {"HVD_TPU_BENCH_ITERS": str(iters),
           "HVD_TPU_COMPRESSION": mode,
           "HVD_TPU_SHM": "1" if shm else "0",
           # Deterministic transport + knobs: the A/B measures the
           # transport, not the tuner's exploration.
           "HVD_TPU_AUTOTUNE": "0"}
    if extra_env:
        env.update(extra_env)
    procs, socks = _spawn_local_workers(n, "shm_bench_worker.py", env,
                                        rank_env)
    outputs = []
    rows = {}
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            if p.returncode != 0:
                raise RuntimeError("shm bench rank %d (mode %s, shm %s) "
                                   "failed:\n%s" % (r, mode, shm, out))
            m = re.search(r"SHM_BENCH (\{.*\})", out)
            if m:
                d = json.loads(m.group(1))
                rows[d["rank"]] = d
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()
    if 0 not in rows:
        raise RuntimeError("no SHM_BENCH line from rank 0:\n%s"
                           % (outputs[0] if outputs else "<no output>"))
    return rows


def shm_main(args):
    """bench.py --shm: A/B the shared-memory intra-host data plane
    (docs/TRANSPORT.md) against TCP loopback. Same-host 2- and 4-rank
    allreduce wall time across payload sizes and none/bf16/int8 wire
    codecs (values verified every iteration; tests/test_shm.py pins the
    bitwise shm-vs-TCP parity), plus a hierarchical-composite A/B on the
    emulated cross-host link (forced 2x2 grid + the bandwidth throttle —
    shm legs are intra-host by construction and exempt from the
    emulated NIC). Acceptance (ISSUE 15): shm strictly faster than TCP
    loopback at >= 1MB payloads on this container; small payloads may be
    ~parity and are reported honestly."""
    import ctypes
    iters = max(10, args.num_iters)
    sizes = [4096, 65536, 1048576, 4194304]
    repeats = 3  # alternate A/B runs; medians tame this 2-core box's noise

    # --- per-hop latency (the acceptance headline) ---------------------
    # One ring hop = a full-duplex neighbor exchange (header + CRC, the
    # production pump shape), measured in-process by the native
    # microbench so the control-plane negotiation — which dominates
    # end-to-end op time on this 2-core container — does not drown the
    # transport signal. The TCP baseline is a genuine 127.0.0.1 TCP
    # connection (ConfigureSocket discipline), not an AF_UNIX pair.
    lib = ctypes.CDLL(os.path.join(REPO, "horovod_tpu", "native",
                                   "libhorovod_tpu.so"))
    lib.horovod_tpu_hop_bench.restype = ctypes.c_double
    lib.horovod_tpu_hop_bench.argtypes = [ctypes.c_int, ctypes.c_int64,
                                          ctypes.c_int]
    hop = {}
    for nbytes in sizes:
        ts, ss = [], []
        for _ in range(5):
            t = lib.horovod_tpu_hop_bench(0, nbytes, 50)
            s = lib.horovod_tpu_hop_bench(1, nbytes, 50)
            if t <= 0 or s <= 0:
                raise RuntimeError("hop bench failed at %d bytes" % nbytes)
            ts.append(t)
            ss.append(s)
        t_med, s_med = statistics.median(ts), statistics.median(ss)
        hop[str(nbytes)] = {
            "us_per_hop_tcp": round(t_med, 1),
            "us_per_hop_shm": round(s_med, 1),
            "tcp_over_shm": round(t_med / s_med, 3),
        }
        print("per-hop %d B: tcp %.1f us, shm %.1f us (%.3fx)"
              % (nbytes, t_med, s_med, t_med / s_med), file=sys.stderr)

    def ab_medians(n, mode, extra_env=None, rank_env=None):
        accum = {"tcp": {}, "shm": {}}
        last = {}
        for _ in range(repeats):
            for key, shm_on in (("tcp", False), ("shm", True)):
                rows = _run_shm_bench(n, iters, mode, shm=shm_on,
                                      extra_env=extra_env,
                                      rank_env=rank_env)
                last[key] = rows[0]
                for s, v in rows[0]["us_per_op"].items():
                    accum[key].setdefault(s, []).append(v)
        # Engagement proof, both directions of the A/B. The byte counter
        # is the signal — the segments gauge can already read 0 when a
        # faster-finishing peer's exit tore the job down before this
        # rank's final metrics read.
        if last["shm"]["shm_bytes_sent"] <= 0:
            raise RuntimeError("shm run did not engage the shm plane: %r"
                               % last["shm"])
        if last["tcp"]["shm_bytes_sent"] != 0:
            raise RuntimeError("tcp run moved shm bytes: %r" % last["tcp"])
        med = {key: {s: round(statistics.median(vs), 1)
                     for s, vs in accum[key].items()}
               for key in accum}
        med["tcp_over_shm"] = {s: round(med["tcp"][s] / med["shm"][s], 3)
                               for s in med["tcp"]}
        med["shm_bytes_sent"] = last["shm"]["shm_bytes_sent"]
        return med

    out = {
        "metric": "shm_intra_host_speedup",
        "unit": "x_us_per_hop_tcp_over_shm_4MB",
        "iters": iters,
        "repeats": repeats,
        "sizes_bytes": sizes,
        "per_hop": hop,
        "per_ranks": {},
    }
    for n in (2, 4):
        per_mode = {}
        for mode in ("none", "bf16", "int8"):
            med = ab_medians(n, mode)
            per_mode[mode] = {
                "us_per_op_tcp": med["tcp"],
                "us_per_op_shm": med["shm"],
                "tcp_over_shm": med["tcp_over_shm"],
                # 2 ranks: an allreduce is exactly 2 neighbor exchanges.
                "per_hop_us_shm_smallest": round(
                    med["shm"][str(sizes[0])] / 2.0, 1) if n == 2 else None,
            }
            print("shm A/B n=%d mode=%s: tcp/shm per size %s"
                  % (n, mode, med["tcp_over_shm"]), file=sys.stderr)
        out["per_ranks"][str(n)] = per_mode
    out["value"] = hop["4194304"]["tcp_over_shm"]

    # Hierarchical composite on the emulated cross-host link: forced 2x2
    # grid, 1000 MB/s throttle on socket sends, hierarchical allreduce
    # pinned on — the intra-host legs are the shm consumers.
    rank_env = {r: {"HVD_TPU_LOCAL_RANK": str(r % 2),
                    "HVD_TPU_LOCAL_SIZE": "2",
                    "HVD_TPU_CROSS_RANK": str(r // 2),
                    "HVD_TPU_CROSS_SIZE": "2"} for r in range(4)}
    hier_env = {"HVD_TPU_HIERARCHICAL_ALLREDUCE": "1",
                "HVD_TPU_RING_BANDWIDTH_MBPS": "1000",
                "HVD_TPU_BENCH_SIZES": "4194304"}
    h = ab_medians(4, "none", extra_env=hier_env, rank_env=rank_env)
    out["hierarchical_emulated_link"] = {
        "ranks": 4, "grid": "2x2", "link_mbps": 1000,
        "payload_bytes": 4194304,
        "us_per_op_tcp": h["tcp"]["4194304"],
        "us_per_op_shm": h["shm"]["4194304"],
        "tcp_over_shm": h["tcp_over_shm"]["4194304"],
        "shm_bytes_sent_rank0": h["shm_bytes_sent"],
    }

    # Acceptance: ring hops strictly faster at >= 1MB (the end-to-end
    # allreduce step times above are reported honestly but are
    # negotiation-dominated on this container — the per-hop measurement
    # is the transport A/B).
    for s in ("1048576", "4194304"):
        r = hop[s]["tcp_over_shm"]
        if r <= 1.0:
            raise RuntimeError(
                "shm hop not faster than TCP loopback at %s bytes "
                "(tcp/shm = %.3f <= 1.0)" % (s, r))
    out["vs_baseline"] = out["value"]
    out["baseline"] = ("same-run TCP-loopback per-hop latency "
                       "(BENCH_r10 predates the shm plane); acceptance: "
                       "per-hop tcp/shm > 1.0 at >= 1MB payloads "
                       "(small payloads may be ~parity), bitwise "
                       "shm-vs-TCP parity pinned by tests/test_shm.py")
    emit(out)
    return 0


def _run_sharded_bench(n, iters, mb, sharded, conv=False, timeout=900):
    """Launches n local workers running `iters` Adam steps over an
    `mb`-MB flat parameter buffer, replicated (sharded=False) or
    ZeRO-sharded (sharded=True); returns per-rank dicts of wall time,
    data-ring wire counters and optimizer-state bytes."""
    procs, socks = _spawn_local_workers(
        n, "sharded_bench_worker.py",
        {"HVD_TPU_BENCH_ITERS": str(iters),
         "HVD_TPU_BENCH_MB": str(mb),
         "HVD_TPU_BENCH_SHARDED": "1" if sharded else "0",
         "SHARDED_BENCH_CONV": "1" if conv else "0",
         "JAX_PLATFORMS": "cpu"})
    outputs = []
    rows = {}
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            if p.returncode != 0:
                raise RuntimeError(
                    "sharded bench rank %d (sharded=%s) failed:\n%s"
                    % (r, sharded, out))
            m = re.search(r"SHARDED_BENCH (\{.*\})", out)
            if m:
                d = json.loads(m.group(1))
                rows[d["rank"]] = d
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()
    if 0 not in rows:
        raise RuntimeError("no SHARDED_BENCH line from rank 0:\n%s"
                           % (outputs[0] if outputs else "<no output>"))
    return rows


def sharded_update_main(args):
    """bench.py --sharded-update: A/B the ZeRO-style sharded weight
    update against the replicated allreduce path at 2 and 4 local
    ranks (docs/ZERO.md). Acceptance (ISSUE 8): per-rank
    optimizer-state bytes <= replicated/world_size + one shard of
    padding, data-ring wire bytes within 5% of the allreduce's, and
    the 2-rank replicated-vs-sharded convergence run diverging by at
    most 1e-4 relative loss."""
    iters, mb = max(10, args.num_iters), 4
    ab = []
    for n in (2, 4):
        repl = _run_sharded_bench(n, iters, mb, sharded=False)
        shd = _run_sharded_bench(n, iters, mb, sharded=True,
                                 conv=(n == 2))
        # Both modes walked the same trajectory (collective regression
        # guard, not a perf stat).
        ps_r, ps_s = repl[0]["params_sum"], shd[0]["params_sum"]
        if abs(ps_s - ps_r) > 1e-3 * max(1.0, abs(ps_r)):
            raise RuntimeError(
                "sharded trajectory diverged from replicated at %d "
                "ranks: params_sum %r vs %r" % (n, ps_s, ps_r))
        opt_repl = repl[0]["opt_state_bytes"]
        opt_shard = max(row["opt_state_bytes"] for row in shd.values())
        # One shard of padding slack: the largest shard (uneven
        # partitions) may carry ceil(total/n) - floor(total/n) extra
        # elements per moment; allow a whole extra element row to stay
        # robust.
        shard_pad = 2 * 4 * (max(row["shard_elems"]
                                 for row in shd.values()) -
                             min(row["shard_elems"]
                                 for row in shd.values()) + 1)
        wire_repl = repl[0]["ring_bytes_sent"]
        wire_shard = shd[0]["ring_bytes_sent"]
        entry = {
            "ranks": n, "payload_mb": mb, "iters": iters,
            "replicated_us_per_step": repl[0]["us_per_step"],
            "sharded_us_per_step": shd[0]["us_per_step"],
            "replicated_opt_state_bytes": opt_repl,
            "sharded_opt_state_bytes_max_rank": opt_shard,
            "opt_state_reduction": round(opt_repl / max(1, opt_shard),
                                         3),
            "replicated_ring_bytes_sent": wire_repl,
            "sharded_ring_bytes_sent": wire_shard,
            "wire_ratio_sharded_over_replicated": round(
                wire_shard / max(1, wire_repl), 4),
            "reduce_scatter_ops": shd[0]["reduce_scatter_ops"],
        }
        if not opt_shard <= opt_repl / n + shard_pad:
            raise RuntimeError(
                "sharded optimizer state is not 1/N: %d > %d/%d + %d"
                % (opt_shard, opt_repl, n, shard_pad))
        if abs(wire_shard - wire_repl) > 0.05 * wire_repl:
            raise RuntimeError(
                "sharded wire bytes not within 5%% of allreduce at %d "
                "ranks: %d vs %d" % (n, wire_shard, wire_repl))
        if n == 2:
            conv = shd[0].get("convergence")
            if not conv or not conv["loss_match"]:
                raise RuntimeError(
                    "sharded convergence diverged from replicated: %s"
                    % conv)
            entry["convergence_sharded_vs_replicated"] = conv
        ab.append(entry)
        print("sharded-update %d ranks: opt state %.2fx smaller "
              "(%d -> %d B/rank), wire %.4fx, %.0f -> %.0f us/step"
              % (n, entry["opt_state_reduction"], opt_repl, opt_shard,
                 entry["wire_ratio_sharded_over_replicated"],
                 entry["replicated_us_per_step"],
                 entry["sharded_us_per_step"]), file=sys.stderr)

    out = dict(ab[0])
    out.update({
        "metric": "sharded_update_opt_state_reduction",
        "unit": "x_opt_state_bytes_replicated_over_sharded_2_ranks",
        "value": ab[0]["opt_state_reduction"],
        "ab": ab,
        # BENCH_r06 predates the sharded update, so the baseline is the
        # same-run replicated path (the r06-era execution mode).
        "vs_baseline": ab[0]["opt_state_reduction"],
        "baseline": "same-run replicated allreduce + full-state Adam "
                    "(BENCH_r06 predates sharded_update); acceptance: "
                    "opt bytes <= replicated/N + shard padding, wire "
                    "within 5% of allreduce, convergence max rel loss "
                    "divergence <= 1e-4",
    })
    emit(out)
    return 0


def model_parallel_main(args):
    """bench.py --model-parallel K (docs/GROUPS.md, BENCH_r09): the
    process-group A/B at 2*K ranks on the (batch, model) mesh.

    1. Wire bytes: a MODEL-group allreduce of the payload tensor must
       move <= (K/world + 5%) of the full-world allreduce of the same
       tensor, per collective (summed over the group's members; a true
       subgroup ring moves 2(K-1)S vs the world's 2(world-1)S, so the
       measured ratio lands well under the bound).
    2. Step time: per-op latency for world vs model-group vs batch-group
       allreduces — subgroup rings cut hops from world-1 to group-1 and
       the disjoint rings run concurrently.
    3. Convergence: examples/jax_tp_lm.py at world ranks with
       model_parallel=K must match the single-process reference loss
       trajectory (max rel divergence <= 1e-3) — the acceptance model
       that cannot run pure-DP at its width.
    """
    k = args.model_parallel
    n = 2 * k
    iters = max(4, args.num_iters)
    env = {
        "HVD_TPU_BENCH_MODEL_PARALLEL": str(k),
        "HVD_TPU_BENCH_PAYLOAD_MB": "1",
        "HVD_TPU_BENCH_ITERS": str(iters),
        # Clean byte accounting: no knob flips mid-measurement, no
        # per-segment pipeline headers.
        "HVD_TPU_AUTOTUNE": "0",
        "HVD_TPU_PIPELINE_CHUNK_BYTES": "0",
    }
    procs, socks = _spawn_local_workers(n, "group_bench_worker.py", env)
    rows = {}
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=900)
            if p.returncode != 0:
                raise RuntimeError("group bench rank %d failed:\n%s"
                                   % (r, out))
            m = re.search(r"GB_RESULT (\{.*\})", out)
            if not m:
                raise RuntimeError("no GB_RESULT from rank %d:\n%s"
                                   % (r, out))
            rows[r] = json.loads(m.group(1))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()

    world_total = sum(rows[r]["world"]["bytes_per_iter"] for r in rows)
    # Every rank reports ITS model group's traffic; with n/k symmetric
    # groups running concurrently, one group's per-collective bytes are
    # the all-rank sum divided by the number of groups.
    model_groups = n // k
    model_per_collective = sum(
        rows[r]["model_group"]["bytes_per_iter"] for r in rows) / \
        model_groups
    batch_groups = k
    batch_per_collective = sum(
        rows[r]["batch_group"]["bytes_per_iter"] for r in rows) / \
        batch_groups
    wire_ratio = model_per_collective / world_total
    bound = k / n + 0.05
    if wire_ratio > bound:
        raise RuntimeError(
            "model-group allreduce wire bytes not <= group/world + 5%%: "
            "ratio %.4f > %.4f" % (wire_ratio, bound))

    step = {
        "world_us_per_op": round(np.mean(
            [rows[r]["world"]["us_per_iter"] for r in rows]), 1),
        "model_group_us_per_op": round(np.mean(
            [rows[r]["model_group"]["us_per_iter"] for r in rows]), 1),
        "batch_group_us_per_op": round(np.mean(
            [rows[r]["batch_group"]["us_per_iter"] for r in rows]), 1),
    }
    print("model-parallel %d of %d: wire ratio %.4f (bound %.4f), "
          "us/op world=%.0f model=%.0f batch=%.0f"
          % (k, n, wire_ratio, bound, step["world_us_per_op"],
             step["model_group_us_per_op"], step["batch_group_us_per_op"]),
          file=sys.stderr)

    # Convergence: the TP example vs its single-process reference.
    import tempfile
    example = os.path.join(REPO, "examples", "jax_tp_lm.py")
    with tempfile.TemporaryDirectory() as td:
        ref_out = os.path.join(td, "ref.json")
        mesh_out = os.path.join(td, "mesh.json")
        conv_env = dict(os.environ)
        conv_env.update({"JAX_PLATFORMS": "cpu",
                         "PYTHONPATH": REPO,
                         "HVD_TPU_TP_REF_ROWS": str(n // k)})
        for key in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_ADDRS"):
            conv_env.pop(key, None)
        steps = "10"
        # Captured output: the bench's stdout is the one-JSON-line
        # contract; the example's per-step loss lines stay out of it.
        ref = subprocess.run(
            [sys.executable, example, "--reference", "--steps", steps,
             "--loss-out", ref_out],
            env=conv_env, timeout=600, capture_output=True, text=True)
        if ref.returncode != 0:
            raise RuntimeError("TP reference run failed:\n%s"
                               % (ref.stdout + ref.stderr))
        mesh = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run.run", "-np", str(n),
             "--", sys.executable, example, "--model-parallel", str(k),
             "--steps", steps, "--loss-out", mesh_out],
            env=conv_env, timeout=1200, capture_output=True, text=True)
        if mesh.returncode != 0:
            raise RuntimeError("TP mesh run failed:\n%s"
                               % (mesh.stdout + mesh.stderr))
        with open(ref_out) as f:
            ref_losses = json.load(f)["losses"]
        with open(mesh_out) as f:
            mesh_losses = json.load(f)["losses"]
    divergence = max(abs(a - b) / max(abs(a), 1e-9)
                     for a, b in zip(ref_losses, mesh_losses))
    if divergence > 1e-3:
        raise RuntimeError("TP loss trajectory diverged from the "
                           "single-process reference: %.3e" % divergence)
    print("model-parallel convergence: max rel loss divergence %.2e "
          "over %s steps" % (divergence, steps), file=sys.stderr)

    emit({
        "metric": "model_parallel_wire_ratio",
        "unit": "model_group_bytes_over_world_bytes_per_collective",
        "value": round(wire_ratio, 4),
        "ranks": n, "model_parallel": k,
        "payload_mb": 1, "iters": iters,
        "world_bytes_per_collective": int(world_total),
        "model_group_bytes_per_collective": int(model_per_collective),
        "batch_group_bytes_per_collective": int(batch_per_collective),
        "acceptance_bound": round(bound, 4),
        "step_time": step,
        "concurrent_mesh_bytes_all_model_groups": int(
            model_per_collective * model_groups),
        "convergence": {
            "steps": int(steps),
            "reference_losses": ref_losses,
            "mesh_losses": mesh_losses,
            "max_rel_divergence": divergence,
            "loss_match": divergence <= 1e-3,
        },
        # First round with process groups: the baseline is the same
        # tensor's full-world allreduce measured in the same run.
        "vs_baseline": round(wire_ratio, 4),
        "baseline": "same-run full-world allreduce of the same tensor "
                    "(BENCH_r08 predates process groups); acceptance: "
                    "wire ratio <= group/world + 5%, convergence max "
                    "rel loss divergence <= 1e-3 vs the single-process "
                    "reference",
    })
    return 0


def _run_autotune_ab(n, extra_env, timeout=900):
    """Launches n local autotune A/B workers (tests/autotune_ab_worker:
    48 x 128KB gradient allreduces per step, rank-0-gated convergence
    wait under HVD_TPU_AUTOTUNE=1); returns the AB_RESULT dict."""
    env = {"HVD_TPU_CYCLE_TIME": None}  # un-pin: the tuner owns pacing
    env.update(extra_env or {})
    procs, socks = _spawn_local_workers(n, "autotune_ab_worker.py", env)
    outputs, result = [], None
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            if p.returncode != 0:
                raise RuntimeError("autotune A/B rank %d failed:\n%s"
                                   % (r, out))
            m = re.search(r"AB_RESULT (\{.*\})", out)
            if m:
                result = json.loads(m.group(1))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for s in socks:
            s.close()
    if result is None:
        raise RuntimeError("no AB_RESULT line:\n%s"
                           % (outputs[0] if outputs else "<no output>"))
    return result


def autotune_main(args):
    """bench.py --autotune (docs/AUTOTUNE.md): two measurements.

    1. Closed-loop A/B at 4 ranks on the AUTOTUNE_AB_r05 workload
       (48 x 128KB gradients/step): untuned defaults vs the always-on
       tuner converging on its own, ZERO hand-set knobs. Acceptance
       (ISSUE 9): closed-loop steps/s >= AUTOTUNE_AB_r05's
       tuned_env_replay (the number that previously required manually
       replaying the converged knobs) and >= 1.15x the untuned run.
    2. Pipelined-ring chunk sweep at 2 and 4 ranks on a 16MB fused
       buffer (4 x 4MB gradients/step), autotune off so the chunk knob
       is the only variable, on an emulated 1000 MB/s inter-host link:
       unsliced (0) vs swept HVD_TPU_PIPELINE_CHUNK_BYTES under
       none/bf16/int8 wire modes, interleaved A/B pairs. Acceptance:
       the best (mode, chunk) beats unsliced on step time at EACH rank
       count."""
    with open(os.path.join(REPO, "AUTOTUNE_AB_r05.json")) as f:
        r05 = json.load(f)
    target = r05["tuned_env_replay"]["steps_per_s"]

    ab_iters = str(max(40, args.num_iters * 4))
    untuned = _run_autotune_ab(4, {"HVD_TPU_AUTOTUNE": "0",
                                   "AB_ITERS": ab_iters})
    closed = _run_autotune_ab(4, {"HVD_TPU_AUTOTUNE": "1",
                                  "AB_ITERS": ab_iters,
                                  "AB_TUNE_TIMEOUT": "420"},
                              timeout=1200)
    speedup = round(closed["steps_per_s"] / untuned["steps_per_s"], 3)
    print("autotune closed loop: %.2f -> %.2f steps/s (%.3fx untuned, "
          "target tuned_env_replay %.2f)"
          % (untuned["steps_per_s"], closed["steps_per_s"], speedup,
             target), file=sys.stderr)

    # Pipelined-ring chunk sweep on an EMULATED 8 Gbps inter-host link
    # (HVD_TPU_RING_BANDWIDTH_MBPS=1000): thread overlap cannot
    # manufacture throughput on this container's 2 saturated cores —
    # loopback "transport" is itself CPU work — so the pipelining win is
    # measured where it exists in production: against a link with real
    # serialization delay. A/B pairs run INTERLEAVED (unsliced then
    # sliced, repeated) so host drift cancels; the unsliced loopback
    # numbers ride along for transparency.
    import statistics as _stats

    def _paired(n, mode, chunk, rate, pairs=3):
        a_ms, b_ms = [], []
        for _ in range(pairs):
            for chunk_bytes, acc in ((0, a_ms), (chunk, b_ms)):
                r = _run_autotune_ab(
                    n, {"HVD_TPU_AUTOTUNE": "0",
                        "HVD_TPU_CYCLE_TIME": "0",
                        "HVD_TPU_RING_BANDWIDTH_MBPS": str(rate),
                        "HVD_TPU_PIPELINE_CHUNK_BYTES": str(chunk_bytes),
                        "HVD_TPU_COMPRESSION": mode,
                        "AB_TENSORS": "4", "AB_ELEMS": "1048576",
                        "AB_ITERS": str(max(20, args.num_iters * 2))})
                acc.append(r["ms_per_step"])
        return _stats.median(a_ms), _stats.median(b_ms)

    sweep = {}
    link_mbps = 1000
    for n in (2, 4):
        for mode in ("none", "bf16", "int8"):
            rows = {"workload": "4 x 4MB gradients/step (16MB fused)",
                    "link_mbps": link_mbps}
            best = 0.0
            for chunk in (1048576, 2097152):
                unsliced, sliced = _paired(n, mode, chunk, link_mbps)
                rows["chunk_%d" % chunk] = {
                    "unsliced_ms_per_step": unsliced,
                    "pipelined_ms_per_step": sliced,
                    "speedup": round(unsliced / sliced, 3),
                }
                best = max(best, unsliced / sliced)
                print("pipeline sweep n=%d mode=%s chunk=%d @%dMB/s: "
                      "%.1f -> %.1f ms/step (%.3fx)"
                      % (n, mode, chunk, link_mbps, unsliced, sliced,
                         unsliced / sliced), file=sys.stderr)
            rows["best_speedup_vs_unsliced"] = round(best, 3)
            sweep["%dranks_%s" % (n, mode)] = rows

    pipelined_wins = {k: v["best_speedup_vs_unsliced"]
                      for k, v in sweep.items()}
    # Per-rank-count acceptance: the ISSUE 9 criterion is a measured
    # reduction at 2-4 ranks, so a single lucky cell must not green the
    # whole sweep — each rank count needs a winning (mode, chunk).
    per_rank_best = {
        n: max(v for k, v in pipelined_wins.items()
               if k.startswith("%dranks" % n))
        for n in (2, 4)
    }
    out = {
        "metric": "autotune_closed_loop_steps_per_s",
        "unit": "steps/s_4rank_48x128KB",
        "value": closed["steps_per_s"],
        "workload": r05["workload"],
        "untuned_defaults": untuned,
        "closed_loop": closed,
        "speedup_closed_loop_vs_untuned": speedup,
        "pipelined_ring_sweep": sweep,
        "pipelined_best_speedup_vs_unsliced": pipelined_wins,
        "pipelined_best_speedup_per_rank_count": per_rank_best,
        # The r05 baseline IS this metric's reference measurement: the
        # throughput that used to require a manual tuned-env replay.
        "vs_baseline": round(closed["steps_per_s"] / target, 3),
        "baseline": "AUTOTUNE_AB_r05.json tuned_env_replay %.2f steps/s "
                    "(manually replayed converged knobs); acceptance: "
                    "closed-loop >= that with zero hand-set knobs, "
                    ">= 1.15x untuned, and a measured pipelined-ring "
                    "step-time win on >=1MB fused buffers at 2-4 ranks"
                    % target,
        "acceptance": {
            "closed_loop_vs_tuned_env_replay":
                round(closed["steps_per_s"] / target, 3),
            "closed_loop_vs_untuned": speedup,
            "required": ">= 1.0x replay, >= 1.15x untuned, pipelined "
                        "win > 1.0x",
        },
    }
    if closed["steps_per_s"] < target:
        raise RuntimeError(
            "closed-loop autotune (%.2f steps/s) fell short of the "
            "tuned-env replay target (%.2f)"
            % (closed["steps_per_s"], target))
    if speedup < 1.15:
        raise RuntimeError(
            "closed-loop speedup %.3fx < required 1.15x over untuned"
            % speedup)
    if not all(v > 1.0 for v in per_rank_best.values()):
        raise RuntimeError(
            "pipelined ring did not beat the unsliced path at every "
            "rank count: %r (per-cell: %r)"
            % (per_rank_best, pipelined_wins))
    emit(out)
    return 0


def bn_traffic_step_stats(norm, batch=32, image_size=64, dtype="bfloat16",
                          bn_remat=False, num_classes=1000):
    """Compiles the REAL resnet50 train step (make_train_step over a
    1-device mesh — the same step the throughput bench times) for the
    given norm variant and returns XLA's own accounting of it:
    ``{"bytes_accessed", "flops", "temp_bytes"}``.

    Abstract lowering only (eval_shape params, ShapeDtypeStruct batch):
    no training compute, no chip — reproducible under
    ``JAX_PLATFORMS=cpu``, which is the whole point of the metric
    (PERF.md round 10). Shared with the tier-1 bytes-regression guard
    (tests/test_bn_traffic.py)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet, BottleneckBlock
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    model = ResNet(stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock,
                   norm=norm, num_classes=num_classes,
                   dtype=getattr(jnp, dtype), bn_remat=bn_remat)
    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda: model.init(rng, jnp.zeros((1, image_size, image_size, 3)),
                           train=False))
    params = jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype),
        shapes["params"])
    # Running-stat VALUES are irrelevant to the lowering; zeros of the
    # right shape avoid paying a real model init.
    batch_stats = jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype),
        shapes.get("batch_stats", {}))
    mutable = ["batch_stats"] if batch_stats else []

    def loss_fn(p, b):
        state = {"params": p}
        if batch_stats:
            state["batch_stats"] = batch_stats
            logits, _ = model.apply(state, b["x"], train=True,
                                    mutable=mutable)
        else:
            logits = model.apply(state, b["x"], train=True)
        return cross_entropy_loss(logits, b["y"])

    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:1])
    opt = optax.sgd(0.01, momentum=0.9)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    opt_state = jax.eval_shape(opt.init, params)
    x = jax.ShapeDtypeStruct((batch, image_size, image_size, 3),
                             jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    compiled = step.lower(params, opt_state, {"x": x, "y": y}).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = compiled.memory_analysis()
    return {
        "bytes_accessed": float(cost["bytes accessed"]),
        "flops": float(cost.get("flops", 0.0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


def _nf_agc_convergence(steps=30, lr=0.5, clipping=0.02):
    """The AGC-makes-norm-free-trainable check on the synthetic task
    (CPU): a small ResNet trained three ways on the same fixed
    synthetic classification batch — BatchNorm baseline, norm-free with
    AGC, norm-free without. The convergence gate: the AGC run must
    reach the BN baseline's end state (final loss within an absolute
    ``tolerance`` of BN's — both runs effectively solve the task) with
    a real decrease; the no-AGC run rides along to show what the clip
    buys (measured: stuck near its initial loss at this lr while AGC
    converges — calibrated on CPU, see BENCH_r10)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet, BottleneckBlock
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 16, 16, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=32).astype(np.int32))
    mesh = data_parallel_mesh(devices=None)

    def run(norm, agc):
        model = ResNet(stage_sizes=[2], block_cls=BottleneckBlock,
                       num_classes=10, num_filters=8,
                       dtype=jnp.float32, norm=norm)
        variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        mutable = ["batch_stats"] if batch_stats else []

        def loss_fn(p, b):
            state = {"params": p}
            if batch_stats:
                state["batch_stats"] = batch_stats
                logits, _ = model.apply(state, b["x"], train=True,
                                        mutable=mutable)
            else:
                logits = model.apply(state, b["x"], train=True)
            return cross_entropy_loss(logits, b["y"])

        opt = optax.sgd(lr, momentum=0.9)
        step = make_train_step(loss_fn, opt, mesh, donate=False, agc=agc)
        pp, os_, batch = step.place(params, opt.init(params),
                                    {"x": x, "y": y})
        losses = []
        for _ in range(steps):
            pp, os_, loss = step(pp, os_, batch)
            losses.append(float(loss))
        return losses

    bn = run("batch", None)
    nf_agc = run("none", clipping)
    nf_plain = run("none", None)
    tolerance = 0.15  # absolute final-loss gap; both runs solve the task
    final_ok = np.isfinite(nf_agc[-1]) and \
        nf_agc[-1] <= bn[-1] + tolerance
    decreased = np.isfinite(nf_agc[-1]) and nf_agc[-1] < nf_agc[0] * 0.3
    return {
        "steps": steps, "lr": lr, "agc_clipping": clipping,
        "tolerance_abs_final_loss": tolerance,
        "bn_losses": [round(v, 4) for v in bn],
        "nf_agc_losses": [round(v, 4) for v in nf_agc],
        "nf_no_agc_final_loss": round(nf_plain[-1], 4)
        if np.isfinite(nf_plain[-1]) else None,
        "bn_final_loss": round(bn[-1], 4),
        "nf_agc_final_loss": round(nf_agc[-1], 4)
        if np.isfinite(nf_agc[-1]) else None,
        "loss_match": bool(final_ok and decreased),
    }


def bn_traffic_main(args):
    """bench.py --bn-traffic (PERF.md round 10): the graph-level BN
    A/B, fully reproducible off-chip. Per-step ``cost_analysis()``
    bytes-accessed for the resnet50 train step under stock flax BN vs
    the traffic-lean custom-VJP BN (`norm="lean"`), with the norm-free
    step as the conv-only floor.

    Headline (`value`): the BN-TAX reduction — the share of
    (step - norm-free-floor) bytes the lean path eliminates. The
    whole-step reduction and the zero-BN ceiling ride in the row:
    BN-attributable bytes are ~24% of this step's total on the CPU
    cost model, so the whole-step number is bounded by that ceiling no
    matter how lean the BN is — the tax metric is the honest A/B for
    the BN data path itself. Acceptance: tax reduction >= 20%, AGC
    norm-free convergence gate green."""
    batch, s = args.bn_traffic_batch, args.bn_traffic_image_size
    rows = {}
    for norm in ("batch", "lean", "none"):
        rows[norm] = bn_traffic_step_stats(norm, batch, s)
        print("bn-traffic %-5s: %.4e bytes, temp %.3e" %
              (norm, rows[norm]["bytes_accessed"],
               rows[norm]["temp_bytes"]), file=sys.stderr)
    rows["lean_remat"] = bn_traffic_step_stats("lean", batch, s,
                                               bn_remat=True)

    stock = rows["batch"]["bytes_accessed"]
    lean = rows["lean"]["bytes_accessed"]
    floor = rows["none"]["bytes_accessed"]
    tax_stock = stock - floor
    tax_lean = lean - floor
    tax_reduction = 1.0 - tax_lean / tax_stock
    step_reduction = 1.0 - lean / stock
    ceiling = 1.0 - floor / stock

    conv = _nf_agc_convergence()
    if not conv["loss_match"]:
        raise RuntimeError(
            "norm-free + AGC convergence gate failed: %s" % conv)
    if tax_reduction < 0.20:
        raise RuntimeError(
            "lean BN removed only %.1f%% of the BN-attributable bytes "
            "(acceptance >= 20%%): stock tax %.3e, lean tax %.3e"
            % (100 * tax_reduction, tax_stock, tax_lean))

    emit({
        "metric": "bn_traffic_tax_reduction",
        "value": round(tax_reduction, 4),
        "unit": "frac_bn_attributable_bytes_removed_resnet50_cpu",
        "config": {"model": "resnet50", "batch": batch,
                   "image_size": s, "dtype": "bfloat16",
                   "platform": "cpu_cost_analysis"},
        "stock_bytes_accessed": stock,
        "lean_bytes_accessed": lean,
        "normfree_floor_bytes_accessed": floor,
        "step_bytes_reduction": round(step_reduction, 4),
        "zero_bn_step_ceiling": round(ceiling, 4),
        "bn_tax_bytes": {"stock": tax_stock, "lean": tax_lean},
        "temp_bytes": {k: v["temp_bytes"] for k, v in rows.items()},
        # temp_bytes is 0 on toolchains whose memory_analysis lacks the
        # field — the ratio is diagnostics, never worth crashing the
        # headline metric over.
        "temp_bytes_reduction_lean_vs_stock": round(
            1.0 - rows["lean"]["temp_bytes"] /
            rows["batch"]["temp_bytes"], 4)
        if rows["batch"]["temp_bytes"] else None,
        "lean_remat_bytes_accessed": rows["lean_remat"]["bytes_accessed"],
        "agc_convergence": conv,
        "vs_baseline": None,
        "baseline": "same-run stock flax-BN resnet50 train step "
                    "(cost_analysis bytes; norm='none' is the conv-only "
                    "floor). The whole-step reduction is bounded by the "
                    "zero-BN ceiling (~%.0f%% here): BN-attributable "
                    "bytes are that share of the step on the CPU cost "
                    "model, so the acceptance gate applies to the BN "
                    "tax the lean path actually owns. Acceptance: tax "
                    "reduction >= 20%%, AGC norm-free convergence green"
                    % (100 * ceiling),
    })
    return 0


def _prior_round_value(metric):
    """Newest prior-round row with the same metric name, scanned from
    the BENCH_r*.json / BENCH_ZOO_r*.json artifacts at the repo root
    (single rows under "parsed", per-model rows under "models").
    Returns (filename, value) or None."""
    import glob

    best = None
    for path in (sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))) +
                 sorted(glob.glob(os.path.join(REPO, "BENCH_ZOO_r*.json")))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        row = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
            else doc
        rows = [row] + [m for m in (row.get("models") or [])
                        if isinstance(m, dict)]
        for r in rows:
            v = r.get("value")
            if r.get("metric") == metric and \
                    isinstance(v, (int, float)) and v:
                best = (os.path.basename(path), float(v))
    return best


def emit(out):
    """Prints the bench's one-JSON-line contract, self-baselining rows
    that have no reference measurement: a null vs_baseline (the
    placeholders PR 1 introduced for LM/word2vec/aggregate rows) is
    filled against the newest prior round's same-metric value among the
    BENCH_r*.json / BENCH_ZOO_r*.json files on disk. Rows with no
    prior same-metric round anywhere stay null — never a fabricated
    0.0."""
    if out.get("vs_baseline") is None and out.get("value"):
        prior = _prior_round_value(out.get("metric"))
        if prior:
            fname, value = prior
            out["vs_baseline"] = round(float(out["value"]) / value, 3)
            out["baseline"] = "%s; vs prior-round %s same-metric value %s" \
                % (out.get("baseline", ""), fname, value)
    print(json.dumps(out))


def trace_overhead_main(args):
    """bench.py --trace-overhead (docs/TRACING.md): is the always-on
    span recorder actually free enough to leave on?

    Interleaved A/B pairs (tracing ON first, then OFF, repeated — host
    drift cancels) on two workloads: (1) the autotune A/B step workload
    (48 x 128KB gradients/step at 4 ranks, tuner off) for the steps/s
    number the <3% acceptance bounds, (2) the bucket-mode negotiation
    microbench (16 tensors/step, HVD_TPU_CYCLE_TIME=0) — maximal span
    rate per unit work, the recorder's worst case — for the us/op
    number. The tracing-on negotiation run also proves drops == 0 at
    the DEFAULT ring size: an overhead number measured while silently
    shedding spans would be fiction."""
    import statistics as _stats

    def _steps(trace):
        return _run_autotune_ab(4, {"HVD_TPU_AUTOTUNE": "0",
                                    "HVD_TPU_TRACE": trace,
                                    "AB_ITERS": str(max(150,
                                                        args.num_iters * 4))})

    # One discarded warmup run (the first launcher run of a batch is a
    # consistent cold-start outlier), then pairs with ALTERNATING order
    # so host drift cancels inside the per-pair delta. The overhead the
    # <3% gate bounds is the median per-pair delta in JOB CPU-seconds
    # per step: on a saturated 1-core host steps/s is exactly
    # 1 / job-CPU-per-step, and wall-clock runs swing +/-15% with
    # hypervisor steal while the rusage window doesn't (the same reason
    # the negotiation microbench and SCALING.md measure CPU time). Wall
    # steps/s medians ride along for the record.
    _steps("1")
    on_steps, off_steps, on_cpu, off_cpu, pair_pcts = [], [], [], [], []
    for i in range(12):
        order = ("1", "0") if i % 2 == 0 else ("0", "1")
        pair = {}
        for trace in order:
            pair[trace] = _steps(trace)
        on_steps.append(pair["1"]["steps_per_s"])
        off_steps.append(pair["0"]["steps_per_s"])
        cpu_on = pair["1"]["cpu_ms_per_step_job"]
        cpu_off = pair["0"]["cpu_ms_per_step_job"]
        on_cpu.append(cpu_on)
        off_cpu.append(cpu_off)
        pair_pcts.append((cpu_on - cpu_off) / cpu_off * 100)
        print("trace overhead pair %d (%s first): cpu/step on %.2f / "
              "off %.2f ms (%.2f%%); wall on %.2f / off %.2f steps/s"
              % (i + 1, "on" if order[0] == "1" else "off", cpu_on,
                 cpu_off, pair_pcts[-1], pair["1"]["steps_per_s"],
                 pair["0"]["steps_per_s"]), file=sys.stderr)
    step_on = _stats.median(on_steps)
    step_off = _stats.median(off_steps)
    step_overhead_pct = round(_stats.median(pair_pcts), 2)
    print("trace overhead (step workload): %.2f%% job-CPU-per-step cost "
          "(wall medians %.2f -> %.2f steps/s)"
          % (step_overhead_pct, step_off, step_on), file=sys.stderr)

    neg_iters = max(100, args.num_iters * 10)
    neg_env = {"HVD_TPU_CYCLE_TIME": "0", "HVD_TPU_BENCH_TENSORS": "16"}
    on_us, off_us, neg_pair_pcts = [], [], []
    trace_ctr = None
    for i in range(5):
        order = ("1", "0") if i % 2 == 0 else ("0", "1")
        pair_cpu = {}
        for trace in order:
            us, ctr = _run_negotiation_bench(
                4, neg_iters, dict(neg_env, HVD_TPU_TRACE=trace))
            (on_us if trace == "1" else off_us).append(us)
            c0 = ctr.get(0) or {}
            # Coordinator CPU-us per op — steal-immune, like the step
            # workload's job-CPU metric (wall us/op rides along).
            pair_cpu[trace] = (c0["cpu_us"] /
                               (c0["iters"] * c0["tensors_per_step"]))
            if trace == "1":
                trace_ctr = c0.get("trace") or trace_ctr
        neg_pair_pcts.append(
            (pair_cpu["1"] - pair_cpu["0"]) / pair_cpu["0"] * 100)
    neg_on = _stats.median(on_us)
    neg_off = _stats.median(off_us)
    neg_overhead_pct = round(_stats.median(neg_pair_pcts), 2)
    spans = int((trace_ctr or {}).get("trace_spans_total", 0))
    dropped = int((trace_ctr or {}).get("trace_spans_dropped_total", -1))
    print("trace overhead (negotiation worst case): %.2f%% coordinator-"
          "CPU-per-op cost (wall medians %.1f -> %.1f us/op); rank-0 "
          "spans %d, dropped %d"
          % (neg_overhead_pct, neg_off, neg_on, spans, dropped),
          file=sys.stderr)

    ok = (step_overhead_pct < 3.0 and spans > 0 and dropped == 0)
    emit({
        "round": 13,
        "command": "JAX_PLATFORMS=cpu python bench.py --trace-overhead",
        "note": "always-on trace recorder A/B (docs/TRACING.md): one "
                "discarded warmup run, then 12 on/off pairs in "
                "ALTERNATING order (drift cancels inside each pair); "
                "value = median per-pair delta in JOB CPU-seconds per "
                "step, the determinant of steps/s on a saturated "
                "1-core host (wall runs swing +/-15% with hypervisor "
                "steal; CPU time measures the framework — the "
                "SCALING.md methodology). Step workload = autotune A/B "
                "shape (48 x 128KB gradients/step, 4 ranks, tuner "
                "off); negotiation workload = bucket-mode control-"
                "plane microbench (16 tensors/step, cycle pacing off) "
                "as the recorder's worst case, its overhead likewise "
                "the median per-pair delta in coordinator CPU-us per "
                "op over 5 alternating pairs. "
                "Acceptance: steps/s cost < 3% with ZERO ring drops "
                "at the default HVD_TPU_TRACE_RING.",
        "metric": "trace_overhead_steps_pct",
        "value": step_overhead_pct,
        "unit": "percent_steps_per_s_cost",
        "steps_per_s_tracing_off": step_off,
        "steps_per_s_tracing_on": step_on,
        "cpu_ms_per_step_job_off": _stats.median(off_cpu),
        "cpu_ms_per_step_job_on": _stats.median(on_cpu),
        "negotiation_us_per_op_off": neg_off,
        "negotiation_us_per_op_on": neg_on,
        "negotiation_overhead_pct": neg_overhead_pct,
        "rank0_spans_total": spans,
        "rank0_spans_dropped": dropped,
        "vs_baseline": None,
        "baseline": "no prior tracing round (BENCH_r13 introduces the "
                    "recorder); acceptance: <3% steps/s cost, 0 drops",
    })
    return 0 if ok else 1


def _cpu_per_cycle(ctr):
    """Rank-0 CPU-us per work cycle from a negotiation-bench counter
    dict (None when the worker predates the cpu_us field)."""
    d = ctr.get(0) or {}
    cycles = (d.get("cycles_fast") or 0) + (d.get("cycles_full") or 0)
    if not d.get("cpu_us") or not cycles:
        return None
    return round(d["cpu_us"] / cycles, 1)


def scaling_main(args):
    """bench.py --scaling: regenerates the SCALING.md evidence — (a)
    weak-scaling efficiency of the full jitted DP train step on the
    virtual CPU mesh, (b) control-plane negotiation latency curves at
    32..max-ranks local ranks (cached fast path and full uncached
    negotiation)."""
    weak = _run_weak_scaling(args.scaling_batch, args.num_iters)

    # 512/1024 are extension sizes (real rank processes, several
    # minutes each on a 1-core host) — opt in via --scaling-max-ranks.
    rank_counts = [n for n in (32, 64, 128, 256, 512, 1024)
                   if n <= args.scaling_max_ranks]
    negotiation = []
    metrics_ab = None
    for n in rank_counts:
        iters = max(25, 3200 // n)
        try:
            cached, c_ctr = _run_negotiation_bench(n, iters)
            uncached, u_ctr = _run_negotiation_bench(
                n, max(10, iters // 4), {"HVD_TPU_CACHE_CAPACITY": "0"})
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            # One failing size shouldn't lose the whole evidence run.
            negotiation.append({"ranks": n, "error": str(e)[:500]})
            print("negotiation n=%d FAILED: %s" % (n, str(e)[:200]),
                  file=sys.stderr)
            continue

        def per_step(ctr, rank):
            d = ctr.get(rank)
            if not d or not d.get("iters"):
                return None
            return round((d["ctrl_bytes_sent"] + d["ctrl_bytes_recv"])
                         / d["iters"], 1)

        entry = {
            "ranks": n, "cached_us_per_op": cached,
            "uncached_us_per_op": uncached,
            # Protocol-level fast-path evidence, wall-clock-independent:
            # control bytes (sent+recv, headers included) per op.
            "cached_bytes_per_op_coord": per_step(c_ctr, 0),
            "uncached_bytes_per_op_coord": per_step(u_ctr, 0),
            "cached_bytes_per_op_worker": per_step(c_ctr, 1),
            "uncached_bytes_per_op_worker": per_step(u_ctr, 1),
            "cached_cycle_kinds": {
                "fast": c_ctr.get(0, {}).get("cycles_fast"),
                "full": c_ctr.get(0, {}).get("cycles_full")},
            "uncached_cycle_kinds": {
                "fast": u_ctr.get(0, {}).get("cycles_fast"),
                "full": u_ctr.get(0, {}).get("cycles_full")},
            # Coordinator CPU time per work cycle (user+sys of the
            # rank-0 process / its work-cycle count) — wall clock on a
            # shared core measures the scheduler, CPU time measures
            # the protocol (SCALING.md §2.3).
            "cached_coord_cpu_us_per_cycle": _cpu_per_cycle(c_ctr),
            "uncached_coord_cpu_us_per_cycle": _cpu_per_cycle(u_ctr),
            # Coordinator live-metrics snapshot (docs/METRICS.md):
            # cycle-time histogram, fused bytes, cache hit rate.
            "metrics_snapshot": c_ctr.get(0, {}).get("metrics"),
        }

        # Metrics-plane on/off A/B at the smallest size: the acceptance
        # bar is that metrics-DISABLED runs (the default above) pay
        # nothing, and enabling the plane costs only the ~1/s summary
        # piggyback + forced sync cycle.
        if metrics_ab is None:
            try:
                on_us, _ = _run_negotiation_bench(
                    n, iters, {"HVD_TPU_METRICS": "1"})
                metrics_ab = {
                    "ranks": n,
                    "metrics_off_us_per_op": cached,
                    "metrics_on_us_per_op": on_us,
                    "on_over_off": round(on_us / cached, 3),
                }
                print("metrics A/B n=%d: off %.0f us/op, on %.0f us/op"
                      % (n, cached, on_us), file=sys.stderr)
            except (RuntimeError, subprocess.TimeoutExpired) as e:
                metrics_ab = {"error": str(e)[:300]}

        # Gradient-bucket shape: one training step = 32 long-named
        # async ops negotiated together. Uncached request lists scale
        # with tensors x name length; the cached bit vector doesn't.
        bucket_env = {"HVD_TPU_BENCH_TENSORS": "32"}
        biters = max(10, iters // 4)
        try:
            _, cb_ctr = _run_negotiation_bench(n, biters, bucket_env)
            _, ub_ctr = _run_negotiation_bench(
                n, max(5, biters // 2),
                dict(bucket_env, HVD_TPU_CACHE_CAPACITY="0"))
            entry["bucket32_cached_bytes_per_step_coord"] = \
                per_step(cb_ctr, 0)
            entry["bucket32_uncached_bytes_per_step_coord"] = \
                per_step(ub_ctr, 0)
            entry["bucket32_cached_bytes_per_step_worker"] = \
                per_step(cb_ctr, 1)
            entry["bucket32_uncached_bytes_per_step_worker"] = \
                per_step(ub_ctr, 1)
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            entry["bucket32_error"] = str(e)[:300]

        negotiation.append(entry)
        print("negotiation n=%d: cached %.0f us/op (%s B/op coord), "
              "uncached %.0f us/op (%s B/op coord); bucket32 %s vs %s "
              "B/step coord"
              % (n, cached, entry["cached_bytes_per_op_coord"],
                 uncached, entry["uncached_bytes_per_op_coord"],
                 entry.get("bucket32_cached_bytes_per_step_coord"),
                 entry.get("bucket32_uncached_bytes_per_step_coord")),
              file=sys.stderr)

    out = {
        "metric": "scaling_evidence",
        "value": weak[-1]["efficiency"],
        "unit": "weak_scaling_efficiency_n8_virtual_mesh",
        "vs_baseline": round(weak[-1]["efficiency"] / 0.90, 3),
        "baseline": "reference claims 90% scaling efficiency at 512 GPUs "
                    "(README.rst:75); projection model in SCALING.md",
        "weak_scaling": weak,
        "negotiation_latency": negotiation,
        "metrics_overhead": metrics_ab,
        "host_cores": os.cpu_count(),
    }
    emit(out)


def w2v_make_step(mesh, n, sparse, lr=0.5, num_iters=100, donate=True):
    """Skip-gram NCE multi-step train fn over a dp mesh, sparse or
    dense gradient plane. The IndexedSlices rationale (reference
    horovod/tensorflow/__init__.py:65-76) as a measurable A/B:

    * sparse: grads w.r.t. the GATHERED rows only (O(B*D)), shipped
      through the PRODUCT sparse plane — `horovod_tpu.jax.sparse.
      allreduce_sparse` (allgather (indices, values) over the axis,
      average) + `apply_sparse` (scatter-add; duplicates accumulate,
      exactly IndexedSlices application).
    * dense: differentiate through the gathers (XLA materializes the
      full [V, D] scatter-add gradient), psum it, dense SGD update —
      O(V*D) per step, the `sparse_as_dense` escape hatch.

    Top-level (not nested in word2vec_main) so tests can pin the two
    paths against each other on a CPU mesh."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax.sparse import allreduce_sparse, apply_sparse

    def nce(er, pw, pb, nw, nb):
        pos = jnp.sum(er * pw, axis=-1) + pb
        negl = er @ nw.T + nb[None, :]
        return jnp.mean(-jax.nn.log_sigmoid(pos) -
                        jnp.sum(jax.nn.log_sigmoid(-negl), axis=-1))

    def run(emb, nce_w, nce_b, center, context, neg):
        def one(tables, _):
            emb, nce_w, nce_b = tables
            if sparse:
                er = jnp.take(emb, center, axis=0)
                pw = jnp.take(nce_w, context, axis=0)
                pb = jnp.take(nce_b, context, axis=0)
                nw = jnp.take(nce_w, neg, axis=0)
                nb = jnp.take(nce_b, neg, axis=0)
                loss, g = jax.value_and_grad(
                    nce, argnums=(0, 1, 2, 3, 4))(er, pw, pb, nw, nb)

                def sparse_apply(table, ix, vals):
                    ai, av = allreduce_sparse(ix, vals, average=True,
                                              axis_name="dp")
                    return apply_sparse(table, ai, av, scale=-lr)

                emb = sparse_apply(emb, center, g[0])
                nce_w = sparse_apply(nce_w, context, g[1])
                nce_b = sparse_apply(nce_b, context, g[2])
                nce_w = sparse_apply(nce_w, neg, g[3])
                nce_b = sparse_apply(nce_b, neg, g[4])
            else:
                def full_loss(emb, nce_w, nce_b):
                    return nce(jnp.take(emb, center, axis=0),
                               jnp.take(nce_w, context, axis=0),
                               jnp.take(nce_b, context, axis=0),
                               jnp.take(nce_w, neg, axis=0),
                               jnp.take(nce_b, neg, axis=0))
                loss, g = jax.value_and_grad(
                    full_loss, argnums=(0, 1, 2))(emb, nce_w, nce_b)
                emb = emb - lr * (lax.psum(g[0], "dp") / n)
                nce_w = nce_w - lr * (lax.psum(g[1], "dp") / n)
                nce_b = nce_b - lr * (lax.psum(g[2], "dp") / n)
            return (emb, nce_w, nce_b), lax.pmean(loss, "dp")

        tables, losses = lax.scan(one, (emb, nce_w, nce_b), None,
                                  length=num_iters)
        return tables + (losses[-1],)

    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(), P("dp"), P("dp"), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False)
    # donate=False exists for the CPU-mesh equivalence test, which feeds
    # the same tables to both variants; the benchmark itself keeps
    # donation for the in-place table-update memory footprint.
    return jax.jit(sharded,
                   donate_argnums=(0, 1, 2) if donate else ())


def word2vec_main(args, devices):
    """bench.py --model word2vec: the sparse (indices, values)
    embedding-gradient plane vs the dense full-table path, on chip.
    Reference counterpart: examples/tensorflow_word2vec.py
    (BASELINE.json config #4, "exercises allgather + broadcast") whose
    embedding grads are IndexedSlices. One JSON row: the sparse path
    is the metric, the dense A/B rides along as fields."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    V, D, B, K = args.vocab_size, 256, 4096, 512
    iters = args.num_iters
    rng = np.random.RandomState(0)
    # Zipf-ish ids like natural text; heavy duplication at low ids
    # exercises the scatter-add accumulate path.
    p = 1.0 / np.arange(1, V + 1)
    p /= p.sum()
    center = jnp.asarray(rng.choice(V, size=B, p=p).astype(np.int32))
    context = jnp.asarray(rng.choice(V, size=B, p=p).astype(np.int32))
    neg = jnp.asarray(rng.choice(V, size=K, p=p).astype(np.int32))

    n = len(devices)
    mesh = Mesh(np.array(devices), ("dp",))
    print("bench: %d device(s), platform=%s" %
          (n, devices[0].platform), file=sys.stderr)

    def tables():
        r = np.random.RandomState(1)
        return (jnp.asarray(r.randn(V, D).astype(np.float32) * 0.1),
                jnp.asarray(r.randn(V, D).astype(np.float32) * 0.1),
                jnp.zeros((V,), jnp.float32))

    results = {}
    for name, sparse in (("sparse", True), ("dense", False)):
        step = w2v_make_step(mesh, n, sparse, num_iters=iters)
        emb, nce_w, nce_b = tables()
        for _ in range(max(1, args.num_warmup)):
            emb, nce_w, nce_b, loss = step(emb, nce_w, nce_b, center,
                                           context, neg)
        jax.block_until_ready(loss)
        times = []
        for _ in range(max(2, args.num_rounds)):
            t0 = time.perf_counter()
            emb, nce_w, nce_b, loss = step(emb, nce_w, nce_b, center,
                                           context, neg)
            jax.block_until_ready(loss)
            times.append((time.perf_counter() - t0) / iters)
        results[name] = sorted(times)[len(times) // 2]
        print("word2vec %s: %.3f ms/step" % (name, results[name] * 1e3),
              file=sys.stderr)

    sparse_sps = 1.0 / results["sparse"]
    dense_sps = 1.0 / results["dense"]
    out = {
        "metric": "word2vec_sparse_steps_per_sec_per_chip",
        "value": round(sparse_sps, 1),
        "unit": "steps/sec/chip",
        "vs_baseline": None,
        "baseline": "reference tensorflow_word2vec (BASELINE.json #4) "
                    "publishes no steps/s; the dense-equivalent A/B "
                    "of the same model rides in this row",
        "dense_steps_per_sec": round(dense_sps, 1),
        "sparse_speedup_vs_dense": round(sparse_sps / dense_sps, 2),
        "vocab": V, "embedding_dim": D, "batch_centers": B,
        "num_negatives": K,
        "sparse_rows_per_step": int(2 * B + 2 * K + B),
    }
    out.update(device_fields(devices))
    emit(out)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256,
                    help="per-chip batch size (the reference script's "
                         "tunable, default 64 on 2016 GPUs; 256 measured "
                         "fastest on v5e — see PERF.md)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-warmup", type=int, default=3)
    ap.add_argument("--num-rounds", type=int, default=5)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "resnet50gn", "resnet50nf",
                             "resnet50lean", "resnet50pbn", "resnet101",
                             "resnet101nf", "resnet152",
                             "vgg16", "inception3", "inception3pbn",
                             "transformer", "word2vec"],
                    help="vgg16/inception3 are the other models in the "
                         "reference's published scaling table "
                         "(docs/benchmarks.rst:13-14); use "
                         "--image-size 299 for inception3's canonical "
                         "input")
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="sequence length (transformer model)")
    ap.add_argument("--tokens-batch", type=int, default=8,
                    help="per-chip sequences per step (transformer model)")
    ap.add_argument("--num-heads", type=int, default=12,
                    help="transformer attention heads; embed_dim stays "
                         "768, so head_dim = 768/H. H=6 gives D=128 "
                         "heads — identical FLOPs to GPT-2's 12xD64 but "
                         "full MXU width (D=64 caps every attention "
                         "matmul at half the systolic array)")
    ap.add_argument("--vocab-size", type=int, default=100000,
                    help="word2vec model: embedding/NCE table rows "
                         "(the dense A/B's per-step cost scales with "
                         "this; the sparse path's does not)")
    ap.add_argument("--num-kv-heads", type=int, default=0,
                    help="transformer GQA/MQA: kv heads < query heads "
                         "(0 = plain MHA). Shrinks the k/v projections "
                         "and runs the flash kernels' grouped-rows "
                         "layout (one kv fetch per query-head group, "
                         "in-kernel dK/dV group reduction)")
    ap.add_argument("--fused-rope", action="store_true",
                    help="fuse rotary embedding into the flash kernels' "
                         "q/k load path (saves the HBM round trip of "
                         "writing rotated q/k outside the kernel)")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1 optimizer-state sharding in the train "
                         "step (parallel/train.py) - state memory/n, "
                         "same wire bytes")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="transformer only: >0 swaps every other "
                         "block's MLP for a Switch-MoE layer with this "
                         "many experts (parallel/expert.py)")
    ap.add_argument("--fused-xent", action="store_true",
                    help="use the streaming chunked LM cross entropy "
                         "(ops/losses.py) instead of the dense "
                         "log_softmax loss — required for very long "
                         "sequences (dense f32 logits at L=8192 "
                         "exceed a v5e's HBM)")
    ap.add_argument("--sync-bn", action="store_true",
                    help="cross-replica (sync) BN for the resnet "
                         "variants: batch statistics psum over the "
                         "data-parallel mesh axis inside the train "
                         "step (ops/batch_norm.py; the standard choice "
                         "at small per-chip batch)")
    ap.add_argument("--virtual-batch-size", type=int, default=0,
                    help="ghost BN for the resnet lean/pallas "
                         "variants: per-group virtual batch "
                         "(ops/batch_norm.py; the large-per-chip-batch "
                         "regularizer). 0 = off")
    ap.add_argument("--bn-traffic", action="store_true",
                    help="graph-level BN A/B, CPU-reproducible (PERF.md "
                         "round 10): per-step cost_analysis() bytes "
                         "accessed for the resnet50 train step under "
                         "stock flax BN vs the traffic-lean custom-VJP "
                         "BN, with the norm-free conv-only floor, the "
                         "BN-tax reduction as the headline, and the "
                         "AGC norm-free convergence gate; prints one "
                         "JSON line (works under JAX_PLATFORMS=cpu)")
    ap.add_argument("--bn-traffic-batch", type=int, default=32,
                    help="--bn-traffic batch size (CPU-compilable "
                         "stand-in for the chip's batch-256 shape; the "
                         "A/B ratio, not the absolute bytes, is the "
                         "metric)")
    ap.add_argument("--bn-traffic-image-size", type=int, default=64)
    ap.add_argument("--all-models", action="store_true",
                    help="run the whole model-zoo sweep (one subprocess "
                         "per model) and print a single combined JSON "
                         "line")
    ap.add_argument("--compression", choices=["none", "bf16", "int8"],
                    default=None,
                    help="A/B the wire-compression stage "
                         "(docs/COMPRESSION.md): data-ring bytes + "
                         "step time with compression off vs this mode "
                         "(2 local ranks, CPU-only), plus the int8 vs "
                         "fp32 convergence run; prints one JSON line")
    ap.add_argument("--shm", action="store_true",
                    help="A/B the shared-memory intra-host data plane "
                         "(docs/TRANSPORT.md): same-host allreduce wall "
                         "time shm vs TCP loopback at 2 and 4 ranks "
                         "across none/bf16/int8, plus a hierarchical-"
                         "composite A/B on the emulated cross-host "
                         "link; prints one JSON line (BENCH_r11)")
    ap.add_argument("--sharded-update", action="store_true",
                    help="A/B the ZeRO-style sharded weight update "
                         "(docs/ZERO.md): step time, optimizer-state "
                         "bytes (opt_state_bytes gauge) and data-ring "
                         "wire bytes for reduce-scatter+allgather vs "
                         "plain allreduce at 2 and 4 local ranks, plus "
                         "a 2-rank replicated-vs-sharded convergence "
                         "run; prints one JSON line")
    ap.add_argument("--zoo-headroom", action="store_true",
                    help="per-zoo-model training-state residency vs the "
                         "v5e 16 GiB HBM budget with the sharded update "
                         "applied (exact eval_shape byte accounting + "
                         "BENCH_r07's measured 1/N opt-state law; "
                         "HVD_TPU_HEADROOM_RANKS sets N, default 8); "
                         "prints one JSON line for PERF.md")
    ap.add_argument("--model-parallel", type=int, default=0,
                    metavar="K",
                    help="process-group / 2-D mesh A/B (docs/GROUPS.md, "
                         "BENCH_r09) at 2*K local ranks: model-group vs "
                         "full-world allreduce wire bytes (acceptance "
                         "<= K/world + 5%%), per-op latency for world/"
                         "model/batch rings, and the jax_tp_lm example's "
                         "loss trajectory vs its single-process "
                         "reference; prints one JSON line")
    ap.add_argument("--autotune", action="store_true",
                    help="closed-loop autotune on/off A/B (untuned "
                         "defaults vs the always-on tuner, zero "
                         "hand-set knobs, vs the AUTOTUNE_AB_r05 "
                         "tuned-env replay target) plus a "
                         "pipelined-ring chunk-size sweep on >=1MB "
                         "fused buffers at 2-4 ranks "
                         "(docs/AUTOTUNE.md); prints one JSON line")
    ap.add_argument("--durable-commit", action="store_true",
                    help="measure ElasticState.commit() latency with "
                         "the durable checkpoint writer off vs on "
                         "(docs/ELASTIC.md 'Durability'); CPU-only, "
                         "prints one JSON line")
    ap.add_argument("--serve", action="store_true",
                    help="serving-plane bench (docs/SERVE.md): open-"
                         "loop RPS/latency curve on a 2-replica pool "
                         "plus the autoscale-on-traffic-step row; "
                         "CPU-only, prints one JSON line (BENCH_r12)")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="A/B the always-on trace recorder "
                         "(docs/TRACING.md): tracing on vs off on the "
                         "step and negotiation workloads; CPU-only, "
                         "prints one JSON line (BENCH_r13)")
    ap.add_argument("--scaling", action="store_true",
                    help="regenerate the SCALING.md evidence (weak "
                         "scaling on the virtual CPU mesh + negotiation "
                         "latency curves) instead of the throughput bench")
    ap.add_argument("--scaling-max-ranks", type=int, default=256,
                    help="largest local rank count for the negotiation "
                         "latency curve")
    ap.add_argument("--scaling-batch", type=int, default=128,
                    help="per-shard batch for the weak-scaling step")
    ap.add_argument("--scaling-worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--scaling-single", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.model == "transformer":
        if 768 % args.num_heads or (768 // args.num_heads) % 64:
            ap.error("--num-heads must divide embed_dim=768 with a "
                     "64-multiple head_dim (the Pallas kernels need "
                     "lane-tileable D); got H=%d -> D=%d rem %d"
                     % (args.num_heads, 768 // args.num_heads,
                        768 % args.num_heads))
        if args.num_kv_heads and args.num_heads % args.num_kv_heads:
            ap.error("--num-kv-heads must divide --num-heads; got "
                     "G=%d, H=%d" % (args.num_kv_heads, args.num_heads))

    if args.scaling_worker is not None:
        return scaling_worker(args)
    if args.bn_traffic:
        return bn_traffic_main(args)
    if args.compression is not None:
        return compression_main(args)
    if args.shm:
        return shm_main(args)
    if args.sharded_update:
        return sharded_update_main(args)
    if args.model_parallel:
        return model_parallel_main(args)
    if args.zoo_headroom:
        return zoo_headroom_main(args)
    if args.autotune:
        return autotune_main(args)
    if args.durable_commit:
        return durable_commit_main(args)
    if args.serve:
        return serve_main(args)
    if args.trace_overhead:
        return trace_overhead_main(args)
    if args.scaling:
        return scaling_main(args)
    if args.all_models:
        return all_models_main(args)

    # The throughput path from here on: fail before building anything
    # when there is no chip to measure.
    devices = require_tpu()
    if args.model == "word2vec":
        return word2vec_main(args, devices)

    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    n = len(devices)
    print("bench: %d device(s), platform=%s" % (n, devices[0].platform),
          file=sys.stderr)
    rng = jax.random.PRNGKey(0)
    mesh = data_parallel_mesh(devices=devices)

    if args.model == "transformer":
        # GPT-2-small-shaped causal LM with the Pallas flash-attention
        # kernel — the long-context extension's on-chip evidence (the
        # unit per "image" below is one sequence).
        moe = {}
        if args.moe_experts:
            # Switch-MoE variant (single chip: all experts local, top-1
            # routing into capacity buffers gathered from the sorted
            # assignments; the ep all_to_all engages only on multi-chip
            # meshes). The dropless path is the benchmark's OLMoE cell.
            moe = dict(moe_experts=args.moe_experts, moe_every=2,
                       moe_capacity_factor=1.25)
        cfg = models.TransformerConfig(
            vocab_size=32000, num_layers=12, num_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads or None,
            rope_fused=args.fused_rope,
            embed_dim=768, mlp_dim=3072, attention="flash",
            dtype=jnp.bfloat16, max_seq_len=max(8192, args.seq_len),
            **moe)
        model = models.Transformer(cfg)
        L = args.seq_len
        global_batch = args.tokens_batch * n
        tokens = jax.random.randint(rng, (global_batch, L), 0,
                                    cfg.vocab_size)
        positions = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None], tokens.shape)
        params = model.init(rng, tokens[:1], positions[:1])["params"]

        if args.fused_xent:
            # Streaming LM loss: chunked vocab projection + logsumexp,
            # never materializing [B, L, V] f32 logits (identical
            # math; ops/losses.py).
            from horovod_tpu.ops.losses import \
                chunked_softmax_cross_entropy

            # Largest power-of-two chunk (<=512) dividing L, so any
            # --seq-len works; L itself as the degenerate fallback.
            # chunk=1024 measured slightly SLOWER at L=8192 h6 on v5e
            # (8.53 vs 8.66 seq/s) — 512 stays the cap.
            chunk = next((c for c in (512, 256, 128, 64)
                          if args.seq_len % c == 0), args.seq_len)

            def loss_fn(params, batch):
                hidden = model.apply({"params": params}, batch["x"],
                                     batch["pos"], return_hidden=True)
                tgt = jnp.roll(batch["x"], -1, axis=1)
                return chunked_softmax_cross_entropy(
                    hidden, params["lm_head"]["kernel"], tgt, chunk=chunk)
        else:
            def loss_fn(params, batch):
                logits = model.apply({"params": params}, batch["x"],
                                     batch["pos"])
                tgt = jnp.roll(batch["x"], -1, axis=1)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32))
                return -jnp.mean(jnp.take_along_axis(
                    logp, tgt[..., None], axis=-1))

        opt = optax.adam(1e-4)
        step = make_train_step(loss_fn, opt, mesh, donate=True,
                               zero1=args.zero1)
        params_p, opt_state, batch = step.place(
            params, opt.init(params),
            {"x": tokens, "pos": positions})
        unit = "sequences/sec/chip"
        per_item_tokens = L
    else:
        model_cls = {"resnet50": models.ResNet50,
                     "resnet50gn": models.ResNet50GN,
                     "resnet50nf": models.ResNet50NF,
                     "resnet50lean": models.ResNet50Lean,
                     "resnet50pbn": models.ResNet50PBN,
                     "resnet101": models.ResNet101,
                     "resnet101nf": models.ResNet101NF,
                     "resnet152": models.ResNet152,
                     "vgg16": models.VGG16,
                     "inception3": models.InceptionV3,
                     "inception3pbn": partial(models.InceptionV3,
                                              norm="pallas")}[args.model]
        extra = {}
        if args.sync_bn or args.virtual_batch_size:
            if not args.model.startswith("resnet") or \
                    args.model.endswith(("nf", "gn")):
                raise SystemExit(
                    "--sync-bn/--virtual-batch-size apply to the "
                    "BN-carrying resnet variants (GroupNorm has no "
                    "cross-sample statistics to sync)")
            if args.sync_bn:
                # The train step's mesh axis (parallel/train.py): the
                # stats psum rides the same shard_map the gradients do.
                extra["bn_axis_name"] = "hvd"
            if args.virtual_batch_size:
                if args.model not in ("resnet50lean", "resnet50pbn"):
                    raise SystemExit("--virtual-batch-size needs the "
                                     "lean or pallas BN variants")
                extra["bn_virtual_batch_size"] = args.virtual_batch_size
        model = model_cls(num_classes=1000, dtype=jnp.bfloat16, **extra)

        s = args.image_size
        variables = model.init(rng, jnp.zeros((1, s, s, 3)), train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        mutable = ["batch_stats"] if batch_stats else []
        drop_rng = jax.random.PRNGKey(1)

        def loss_fn(params, batch):
            state = {"params": params}
            if batch_stats:
                state["batch_stats"] = batch_stats
                logits, _ = model.apply(state, batch["x"], train=True,
                                        mutable=mutable,
                                        rngs={"dropout": drop_rng})
            else:
                logits = model.apply(state, batch["x"], train=True,
                                     rngs={"dropout": drop_rng})
            return cross_entropy_loss(logits, batch["y"])

        # Norm-free variants train with adaptive gradient clipping
        # (ops/agc.py): the knob that makes the measured-fastest route
        # an actual training config, not just a roofline probe. Cost
        # rides in the measured step like any real run. zero1 cannot
        # carry AGC (flat shards destroy the unit structure) — fail
        # loudly rather than silently measure an untrainable config.
        agc = None
        if args.model.endswith("nf"):
            if args.zero1:
                raise SystemExit(
                    "--zero1 with a norm-free model would drop AGC "
                    "(sharded updates see 1/N flat shards, not "
                    "per-filter units) — the measured step would not "
                    "be a trainable config; run nf rows replicated")
            agc = 0.01
        opt = optax.sgd(0.01, momentum=0.9)
        step = make_train_step(loss_fn, opt, mesh, donate=True,
                               zero1=args.zero1, agc=agc)

        global_batch = args.batch_size * n
        x = jax.random.normal(rng, (global_batch, s, s, 3), jnp.float32)
        y = jax.random.randint(rng, (global_batch,), 0, 1000)
        params_p, opt_state, batch = step.place(params, opt.init(params),
                                                {"x": x, "y": y})
        unit = "images/sec/chip"
        per_item_tokens = None

    # The final loss depends on every prior step's params, so waiting
    # for it is an end-of-chain barrier.
    for _ in range(args.num_warmup):
        params_p, opt_state, loss = step(params_p, opt_state, batch)
    jax.block_until_ready(loss)

    # Optional profiler hook, through the program's one control for the
    # profiler (hvd.profile): trace a separate burst of steps BEFORE the
    # timed rounds so trace collection overhead never contaminates the
    # reported numbers.
    profile_dir = os.environ.get("HVD_TPU_PROFILE_DIR")
    if profile_dir:
        from horovod_tpu import profile
        profile.start(profile_dir)
        for _ in range(args.num_iters):
            params_p, opt_state, loss = step(params_p, opt_state, batch)
        jax.block_until_ready(loss)
        print("trace: %s" % profile.stop(), file=sys.stderr)

    rates = []
    for r in range(args.num_rounds):
        t0 = time.perf_counter()
        for _ in range(args.num_iters):
            params_p, opt_state, loss = step(params_p, opt_state, batch)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rates.append(global_batch * args.num_iters / dt)
        print("round %d: %.1f img/sec total" % (r, rates[-1]),
              file=sys.stderr)

    total = float(np.mean(rates))
    per_chip = total / n
    step_time_ms = global_batch / total * 1000.0

    # MFU: XLA-reported per-device FLOPs / measured step time / peak.
    if not np.isfinite(float(loss)):
        raise SystemExit("bench: loss is not finite (%r)" % float(loss))
    flops = compiled_flops(step, params_p, opt_state, batch)
    peak = peak_flops(devices[0])
    tflops_per_chip = mfu = None
    if flops:
        tflops_per_chip = flops / (step_time_ms / 1000.0) / 1e12
        mfu = tflops_per_chip * 1e12 / peak

    if args.model == "transformer":
        label = "transformer"
        if args.moe_experts:
            label = "transformer_moe%d" % args.moe_experts
        if args.num_heads != 12:
            label += "_h%d" % args.num_heads
        if args.num_kv_heads:
            label += "_gqa%d" % args.num_kv_heads
        if args.fused_rope:
            label += "_frope"
        out = {
            "metric": "%s_flash_L%d_sequences_per_sec_per_chip"
                      % (label, args.seq_len),
            "value": round(per_chip, 2),
            "unit": unit,
            "vs_baseline": None,
            "baseline": "no reference LM baseline (the reference has no "
                        "long-context path); tokens/sec/chip = %.0f"
                        % (per_chip * per_item_tokens),
            "step_time_ms": round(step_time_ms, 2),
        }
        # XLA's cost analysis reports the Pallas attention kernels as
        # ZERO flops, so `mfu` above undercounts the transformer. Add
        # the analytic kernel FLOPs (documented, separately) for the
        # honest total.
        if flops:
            from horovod_tpu.ops.flash_attention import \
                analytic_attention_flops
            attn = cfg.num_layers * analytic_attention_flops(
                args.tokens_batch, cfg.num_heads, L,
                cfg.embed_dim // cfg.num_heads, causal=True, training=True)
            total_tflops = (flops + attn) / (step_time_ms / 1000.0) / 1e12
            out["attn_tflops_uncounted_by_xla"] = round(
                attn / (step_time_ms / 1000.0) / 1e12, 1)
            out["mfu_with_attn_kernels"] = round(
                total_tflops * 1e12 / peak, 3)
    else:
        baseline_per_gpu = 1656.82 / 16.0
        out = {
            "metric": "%s_synthetic_images_per_sec_per_chip" % args.model,
            "value": round(per_chip, 2),
            "unit": unit,
            "vs_baseline": round(per_chip / baseline_per_gpu, 3),
            "baseline": "reference ResNet-101 @ 16xP100, 103.55 img/s/GPU "
                        "(docs/benchmarks.rst:43)%s" % (
                            "" if args.model == "resnet101"
                            else "; cross-model vs %s" % args.model),
            "step_time_ms": round(step_time_ms, 2),
        }
    out.update(device_fields(devices))
    if tflops_per_chip is not None:
        out["tflops_per_chip"] = round(tflops_per_chip, 1)
        out["mfu"] = round(mfu, 3)
    emit(out)


if __name__ == "__main__":
    sys.exit(main())
