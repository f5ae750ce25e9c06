#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

Run from the root of a checkout. Finds the cell in `BENCHMARK.json`, its
configuration file, its traffic file (`benchmark/traffic/<traffic>.json`),
the configuration's builder (`benchmark/builders/<builder>.py`) and, in a
traced run (`--trace 1`: a run of its own; `--trace 2`: a `--trace 0` run
that, once its window has closed and its numbers are taken, traces a few
more steps in the same process and prints both kinds of metric), one reader
per per-layer metric (`benchmark/layer_metrics/<metric>.py`) — all by name,
so this file holds no cell, configuration, traffic or per-layer metric name.
It does hold the four end-to-end metrics (`measured`, in `main`): they are
what this loop measures, and only a `benchmark` PR, which may edit this
file, adds one.
The measured window accounts for itself (`window_account`: its median
step, the time it lost to anything but its steps' usual pace, its longest
gap), and the result line of a traced run carries that account; the window
is taken once, and `throughput` and `step_ms_p95` are over all of it.
The last line of stdout is the result; the lines before it that start with
`INFO ` are for people.

Without a TPU, or with fewer chips than the cell asks for, the command
fails and prints no result. `--rehearse` is the one exception, for the
control flow on the CPU: it applies the `rehearse` overrides of the
configuration and the traffic file (tiny sizes), and its result line
carries no metric at all.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from statistics import median  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = float(1 << 30)
WARMUP_STEPS = 3
TRACE_MIN_STEPS = 10
TRACE_SECONDS = 2.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def info(**kw):
    print("INFO " + json.dumps(kw, sort_keys=True), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_plugin(kind, name):
    """The module `benchmark/<kind>/<name>.py`."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest, name):
    """(cell, configuration entry) of the workload `name`."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit("benchmark: no workload %r in BENCHMARK.json (have %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    return cell, configs[cell["config"]]


def metrics_of(manifest, group, cell_name):
    """The metrics of `group` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def with_rehearsal(spec, rehearse):
    """`spec` with its `rehearse` overrides applied (tiny sizes for a CPU
    run of the control flow), or as it is."""
    spec = dict(spec)
    over = spec.pop("rehearse", {})
    if rehearse:
        spec.update(over)
    return spec


def peaks_for(kind):
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit("benchmark: device_kind %r is not in peaks.json; add "
                         "its published peaks and their source first" % kind)
    return table["devices"][kind]


def program_needles(config, chips, counts=None):
    """Strings the compiled step's text must hold: the configuration's own
    (the mechanisms it has to run), the flash kernels the program's own
    plan names for the cell's shapes (the builder's
    `counts["flash_kernels"]`: whichever backward the plan chose has to be
    there, so the check follows the plan and a backward that left Pallas
    fails it), and an all-reduce wherever the step spans chips."""
    needles = list(config.get("program_must_contain", []))
    needles += [k for k in (counts or {}).get("flash_kernels", [])
                if k not in needles]
    return needles + (["all-reduce"] if chips > 1 else [])


def percentile(values, q):
    """Nearest-rank percentile: a value that was measured."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def memory_gib(mem):
    """`compiled.memory_analysis()` per device in GiB; `step` is what the
    executable needs while it runs: argument + output + temporary - aliased."""
    parts = {"argument": mem.argument_size_in_bytes,
             "output": mem.output_size_in_bytes,
             "temp": mem.temp_size_in_bytes,
             "alias": mem.alias_size_in_bytes}
    parts["step"] = (parts["argument"] + parts["output"] + parts["temp"]
                     - parts["alias"])
    return {k: v / GIB for k, v in parts.items()}


def replica_checksums(tree, devices):
    """For each device the wrap-around sum of the bit patterns of its copy
    of every leaf of `tree`: equal numbers mean bit-identical replicas."""
    import jax
    import jax.numpy as jnp

    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    @jax.jit
    def checksum(leaves):
        return sum(jnp.sum(jax.lax.bitcast_convert_type(
            x, unsigned[x.dtype.itemsize]).astype(jnp.uint32),
            dtype=jnp.uint32) for x in leaves)

    leaves = jax.tree_util.tree_leaves(tree)
    sums = []
    for d in devices:
        mine = [next(s.data for s in leaf.addressable_shards if s.device == d)
                for leaf in leaves]
        sums.append(int(checksum(mine)))
    return sums


class Loop:
    """The closed loop every cell runs: one resident batch, one step in
    flight. Step k is dispatched, then step k-1's loss is waited for and the
    clock is stamped, so the host's dispatch overlaps the device as it does
    in a training loop. `losses` keeps every loss on the device."""

    def __init__(self, step, state):
        self.step = step
        self.params, self.opt_state, self.batch = state
        self.losses = []

    def run(self, seconds=None, steps=None, annotate=None):
        """Runs until `seconds` have passed or `steps` are dispatched.
        Returns dict(t0, t1, completed, stamps, dispatch_s, wait_s): the
        clock after every completion, the host seconds inside each
        `step(...)` call, and the host seconds of each wait for a loss
        (`wait_s[k]` ends at `stamps[k]`, just after `dispatch_s[k + 1]`)."""
        import contextlib

        span = annotate or (lambda name: contextlib.nullcontext())
        stamps, dispatch, wait = [], [], []
        prev = None
        t0 = time.perf_counter()
        while True:
            with span("bench_dispatch"):
                d0 = time.perf_counter()
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, self.batch)
                d1 = time.perf_counter()
            dispatch.append(d1 - d0)
            self.losses.append(loss)
            if prev is not None:
                with span("bench_wait_loss"):
                    prev.block_until_ready()
                stamps.append(time.perf_counter())
                wait.append(stamps[-1] - d1)
            prev = loss
            if (steps is not None and len(dispatch) >= steps) or (
                    seconds is not None and d1 - t0 >= seconds):
                break
        w0 = time.perf_counter()
        with span("bench_wait_loss"):
            prev.block_until_ready()
        stamps.append(time.perf_counter())
        wait.append(stamps[-1] - w0)
        return dict(t0=t0, t1=stamps[-1], completed=len(stamps),
                    stamps=stamps, dispatch_s=dispatch, wait_s=wait)


class Setup:
    """Seconds of set-up by what was done, from the start of the process."""

    def __init__(self):
        self.phases = {}
        self._mark = T_PROCESS

    def done(self, name):
        now = time.perf_counter()
        self.phases[name] = now - self._mark
        self._mark = now


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run of the control flow at the files' "
                    "`rehearse` sizes; prints no metric")
    return ap.parse_args()


def find_devices(chips, rehearse):
    """(all devices, the ones this cell uses), or no run at all."""
    import jax

    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise SystemExit("benchmark: JAX found no TPU (%d x %s); this "
                         "benchmark measures the chip and does not fall "
                         "back" % (len(devices), devices[0].platform))
    if len(devices) < chips:
        raise SystemExit("benchmark: the cell needs %d chips, JAX reports %d"
                         % (chips, len(devices)))
    return devices, devices[:chips]


def trace_steps(loop, n_steps, trace_dir):
    """Runs `n_steps` of the loop under the profiler (`hvd.profile`: the
    program's one control for it), with the loop's own spans on the host
    plane. Returns the reduced trace and what tracing cost: the seconds
    that `start`, the steps, `stop` and loading the trace took, and the
    median step inside the profiler window."""
    import horovod_tpu as hvd

    from benchmark import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.perf_counter()
    hvd.profile.start(trace_dir)
    t1 = time.perf_counter()
    try:
        ran = loop.run(steps=n_steps, annotate=hvd.profile.span)
    finally:
        t2 = time.perf_counter()
        path = hvd.profile.stop()
    t3 = time.perf_counter()
    trace = trace_reduce.load(path)
    info(steps_in_traced_loop=ran["completed"],
         programs_in_trace=trace.modules)
    stamps = ran["stamps"]
    took = {"start_s": t1 - t0, "steps_s": t2 - t1, "stop_s": t3 - t2,
            "load_s": time.perf_counter() - t3,
            "step_ms_median_traced": median(
                1e3 * (b - a) for a, b in zip(stamps, stamps[1:]))}
    return trace, took


def trace_after_window(loop, n_steps, trace_dir):
    """`--trace 2`, after the measured window has closed: starts and stops
    the profiler once and throws that trace away, so that what the first
    start costs falls into no number; then `trace_steps`. Returns the
    reduced trace and, for the INFO line, what tracing cost."""
    import horovod_tpu as hvd

    t0 = time.perf_counter()
    hvd.profile.start(trace_dir + ".first")
    hvd.profile.stop()
    shutil.rmtree(trace_dir + ".first", ignore_errors=True)
    first_s = time.perf_counter() - t0
    trace, took = trace_steps(loop, n_steps, trace_dir)
    return trace, dict(took, first_start_and_stop_s=first_s)


def allocator_peaks(devices):
    """`memory_stats()` of the fullest chip: the allocator's own peaks of
    what was allocated (arguments, results, the loop's arrays) and of what
    the runtime reserved for the executable's temporaries, which it counts
    apart."""
    return max(((d.memory_stats() or {}) for d in devices),
               key=lambda m: m.get("peak_bytes_in_use", 0)
               + m.get("peak_bytes_reserved", 0))


def window_account(gaps_ms, wait_s=None, dispatch_s=None):
    """What a window's step gaps say of it: the median gap; `lost_ms`, the
    window's length less its steps at the median gap, which is what its
    `throughput` lost to anything but its own usual pace (a short gap after
    a late one gives the time back); the gaps over 1.5 medians with their
    summed excess (a few long waits, or every step slower?); the longest
    gap, where it fell and, given the loop's `wait_s` and `dispatch_s`, how
    much of it was the wait for the loss and how much the `step(...)`
    calls."""
    mid = median(gaps_ms)
    over = [g for g in gaps_ms if g > 1.5 * mid]
    longest = gaps_ms.index(max(gaps_ms))
    window_ms = sum(gaps_ms)
    account = {"steps": len(gaps_ms), "window_ms": window_ms,
               "step_ms_median": mid,
               "lost_ms": window_ms - len(gaps_ms) * mid,
               "long_gaps": len(over),
               "long_gaps_excess_ms": sum(g - mid for g in over),
               "max_ms": gaps_ms[longest], "max_at": longest}
    if wait_s is not None:
        account["max_wait_ms"] = 1e3 * wait_s[longest]
        account["max_dispatch_ms"] = 1e3 * sum(
            dispatch_s[0 if longest == 0 else longest + 1:longest + 2])
    return account


def layer_metrics(manifest, cell, trace, context):
    """(metrics, device fields, breakdown) of a traced run."""
    from benchmark import trace_reduce as tr

    metrics = {}
    for m in metrics_of(manifest, "per_layer", cell["name"]):
        value = load_plugin("layer_metrics", m["name"]).read(trace, context)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "busy_s": tr.mean_over_devices(trace, tr.busy) / 1e9,
        "window_s": tr.mean_over_devices(
            trace, lambda ev: tr.window(ev)[1] - tr.window(ev)[0]) / 1e9}
    ops = sorted(tr.mean_self_times(trace).items(), key=lambda kv: -kv[1])
    gaps = tr.idle_gaps(trace.devices[min(trace.devices)], trace.host, top=5)
    breakdown = {"device_ops": [[k, v / 1e9] for k, v in ops[:10]],
                 "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
    return metrics, device, breakdown


def main():
    args = parse_args()
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config_entry = find_cell(manifest, args.workload)
    config = with_rehearsal(load_json(os.path.join(ROOT, config_entry["file"])),
                            args.rehearse)
    traffic = with_rehearsal(load_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic"] + ".json")), args.rehearse)
    chips = int(cell["chips"])
    setup = Setup()

    from horovod_tpu.run.util import use_compile_cache

    cache_dir = use_compile_cache()
    if args.rehearse and chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % chips)

    import jax

    devices, used = find_devices(chips, args.rehearse)
    on_chip = devices[0].platform == "tpu"
    peaks = None if args.rehearse else peaks_for(devices[0].device_kind)
    compiles, cache_events = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == COMPILE_EVENT else None)
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.append(name))
    setup.done("import_jax_and_find_devices")

    import horovod_tpu as hvd
    from horovod_tpu import parallel

    hvd.init()
    setup.done("hvd_init")
    mesh = parallel.data_parallel_mesh(devices=used)
    built = load_plugin("builders", config["builder"]).build(
        config, traffic, mesh, args.seed)
    step = built["step"]
    state = step.place(*built["state"])
    jax.block_until_ready(state)
    setup.done("build_state_on_device")
    # Compiled ahead of the first call only to be read (its bytes and its
    # text). The loop calls `step(...)` itself; jit keeps the executable
    # with the lowering, so that call runs this one and compiles nothing
    # (the cache counts below show one compile or load of the step).
    compiled = step.lower(*state).compile()
    mem_gib = memory_gib(compiled.memory_analysis())
    checks = []  # (what, ok, detail)
    text = compiled.as_text()
    for needle in program_needles(config, chips, built["counts"]):
        n = text.count(needle)
        checks.append(("program text holds %r" % needle, n > 0 or not on_chip,
                       "%d times%s" % (n, "" if on_chip else
                                       " (not a TPU program: not required)")))
    del compiled, text
    setup.done("compile_or_load_step")

    loop = Loop(step, state)
    del state
    warm = loop.run(steps=WARMUP_STEPS)
    step_s = (warm["t1"] - warm["stamps"][0]) / (WARMUP_STEPS - 1)
    misses = cache_events.count("/jax/compilation_cache/cache_misses")
    hits = cache_events.count("/jax/compilation_cache/cache_hits")
    setup.done("warm_up")
    setup_s = time.perf_counter() - T_PROCESS

    # The window. `--trace 1` measures a quarter of it untraced (for the
    # per-layer metrics that need no trace), then a profiler window.
    # `--trace 0` and `--trace 2` share every statement down to the
    # allocator's peaks: only after those does `--trace 2` touch the
    # profiler, so the two kinds of run read alike. The window is taken
    # once: `throughput` and `step_ms_p95` are over all its steps and all
    # its time, and `window_account` says what it lost to a late host.
    compiles_before = len(compiles)
    seconds = max(2.0, args.seconds / 4.0) if args.trace == 1 \
        else args.seconds
    win = loop.run(seconds=seconds)
    compiles_in_window = len(compiles) - compiles_before
    marks = [win["t0"]] + win["stamps"]
    gaps_ms = [1e3 * (b - a) for a, b in zip(marks, marks[1:])]
    account = window_account(gaps_ms, win["wait_s"], win["dispatch_s"])
    window_s = win["t1"] - win["t0"]
    throughput = (built["items_per_step"] * win["completed"]
                  / window_s / chips)
    step_ms_p95 = percentile(gaps_ms, 0.95)
    trace, counted = None, None  # `counted`: the losses the checks count
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    traced_steps = max(TRACE_MIN_STEPS, int(TRACE_SECONDS / step_s))
    if args.trace == 1:
        trace, _ = trace_steps(loop, traced_steps, trace_dir)
    stats = allocator_peaks(used)
    # What `hbm_stats_gib` reads: taken before `--trace 2` starts the
    # profiler.
    stats_peak_bytes = int(stats.get("peak_bytes_in_use", 0)
                           + stats.get("peak_bytes_reserved", 0))
    if args.trace == 2:
        # The traced steps' losses take no part in `attempted`, `failed`
        # and the checks on the losses: those are the closed window's.
        counted = len(loop.losses)
        trace, tracing = trace_after_window(loop, traced_steps, trace_dir)
        stats = allocator_peaks(used)  # the peaks of the whole run
        info(tracing=tracing, compiles_while_tracing=len(compiles)
             - compiles_before - compiles_in_window)

    # Correctness, outside the window.
    losses = [float(x) for x in jax.device_get(loop.losses[:counted])]
    failed = sum(1 for v in losses if not math.isfinite(v))
    checks.append(("every loss is finite", failed == 0,
                   "%d of %d are not" % (failed, len(losses))))
    first, last = median(losses[:WARMUP_STEPS]), median(losses[-WARMUP_STEPS:])
    checks.append(("loss falls on the resident batch", last < first,
                   "%.6f -> %.6f" % (first, last)))
    checks.append(("no compilation inside the window", compiles_in_window == 0,
                   "%d" % compiles_in_window))
    final_params = loop.params
    del loop
    if chips > 1:
        sums = replica_checksums(final_params, used)
        checks.append(("parameters are bit-identical on all %d chips after "
                       "the window" % chips, len(set(sums)) == 1,
                       "checksums %s" % sums))
    checks.extend(built["verify"](final_params, losses[0]))
    del final_params
    hvd.shutdown()
    for what, ok, detail in checks:
        info(check=what, ok=bool(ok), detail=detail)

    info(cell=cell["name"], seed=args.seed, steps_in_window=win["completed"],
         window_s=window_s, step_ms_median=account["step_ms_median"],
         window_lost_ms=account["lost_ms"],
         step_ms_long_gaps=account["long_gaps"],
         step_ms_long_gaps_excess_ms=account["long_gaps_excess_ms"],
         step_ms_samples=len(gaps_ms), step_ms_max=account["max_ms"],
         step_ms_max_at=account["max_at"],
         step_ms_max_wait_ms=account["max_wait_ms"],
         step_ms_max_dispatch_ms=account["max_dispatch_ms"],
         dispatch_ms_median=1e3 * median(win["dispatch_s"]),
         compiles_in_window=compiles_in_window, compile_cache=cache_dir,
         cache_hits=hits, cache_misses=misses, first_run_compiled=misses > 0,
         setup_s=setup_s, setup_phases_s=setup.phases,
         memory_stats=stats, memory_analysis_gib=mem_gib,
         loss_first=losses[0], loss_last=losses[-1])

    result = {"correct": all(ok for _, ok, _ in checks),
              "attempted": len(losses), "failed": failed, "metrics": {}}
    # `memory_peak_bytes` is the allocator's reading alone; its two parts
    # and the executable's own count stand beside it. In a `--trace 2` run
    # it is the peak of the whole run, the traced steps included.
    stats_in_use = int(stats.get("peak_bytes_in_use", 0))
    stats_reserved = int(stats.get("peak_bytes_reserved", 0))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats_in_use + stats_reserved,
              "memory_stats_peak_bytes_in_use": stats_in_use,
              "memory_stats_peak_bytes_reserved": stats_reserved,
              "memory_analysis_step_bytes": int(mem_gib["step"] * GIB)}
    end_to_end = {}
    if args.trace != 1:
        measured = {"throughput": throughput, "step_ms_p95": step_ms_p95,
                    "peak_hbm_gib": mem_gib["step"], "setup_s": setup_s}
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            if m["name"] not in measured:
                raise SystemExit("benchmark: the harness does not measure "
                                 "the end-to-end metric %r" % m["name"])
            end_to_end[m["name"]] = {"value": measured[m["name"]],
                                     "unit": m["unit"]}
    if args.rehearse:
        info(rehearsal="sizes are the files' `rehearse` overrides; no metric "
             "is printed", traced_devices=sorted(trace.devices) if trace
             else None)
    elif trace is not None:
        # `throughput`, `dispatch_s`, `gaps_ms` and `window` are the
        # untraced window's: a quarter of `--seconds` under
        # `--trace 1`, all of it under `--trace 2`.
        context = {"cell": cell, "config": config, "traffic": traffic,
                   "chips": chips, "peaks": peaks, "counts": built["counts"],
                   "throughput": throughput,
                   "steps_traced": trace.modules[min(trace.modules)],
                   "dispatch_s": win["dispatch_s"], "gaps_ms": gaps_ms,
                   "window": account,
                   "memory_stats_peak_bytes": stats_peak_bytes}
        t0 = time.perf_counter()
        per_layer, traced_device, result["breakdown"] = \
            layer_metrics(manifest, cell, trace, context)
        info(reducing_the_trace_s=time.perf_counter() - t0)
        result["metrics"] = dict(end_to_end, **per_layer)
        device.update(traced_device)
    else:
        result["metrics"] = end_to_end
    if args.trace == 2:
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["device"] = device
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
