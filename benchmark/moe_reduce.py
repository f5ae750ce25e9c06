"""Reduction of a profiler trace for the routed feed-forward
(`horovod_tpu/parallel/expert.py`): the device time under the scope
`hvd_moe`, and the part of it in the grouped-matmul kernels
(`hvd_moe_gmm`, `hvd_moe_gmm_dlhs`, `hvd_moe_gmm_drhs`). Every name comes
from the program's `horovod_tpu.profile`, through `scope_reduce.names`: a
program that lacks them reads as None, and a renamed kernel is followed.
Beside `scope_reduce.py`, whose reading of the trace (an instruction's
`op_name` from the event metadata, self times, `kernel_of`) it uses and
does not change.
"""

import json
import os
from statistics import median

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def moe_names():
    """(the scope around the routed feed-forward, its inner scopes, the
    grouped-matmul kernels' names) as the program's `horovod_tpu.profile`
    gives them, or None for a program that has no such names."""
    moe = getattr(sr.names, "MOE", None)
    if moe is None:
        return None
    inner = tuple(t for t in sr.names.MOE_SCOPES if t != moe)
    return moe, inner, sr.names.MOE_GMM_KERNELS


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"moe": under the scope
    `profile.MOE`, either direction; "gmm": the grouped-matmul kernels
    (`scope_reduce.kernel_of`: a Pallas kernel by the name the program gave
    it, never by `tpu_custom_call` alone, which the flash kernels are too);
    "kernels": {kernel: ns}; "scopes": {the inner scope: ns}}. An
    instruction with no `op_name` counts with the named one before it, as
    in `scope_reduce.self_ns`."""
    moe, inner_scopes, gmm = moe_names()
    out = {"moe": 0.0, "gmm": 0.0, "kernels": {}, "scopes": {}}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        ns = sum(e - s for s, e in intervals)
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        kernel = sr.kernel_of(ev, op_name)
        if kernel not in gmm:
            kernel = None
        toks = sr.scopes(op_name)
        if moe not in toks and kernel is None:
            continue
        out["moe"] += ns
        inner = next((t for t in toks if t in inner_scopes), moe)
        out["scopes"][inner] = out["scopes"].get(inner, 0.0) + ns
        if kernel:
            out["gmm"] += ns
            out["kernels"][kernel] = out["kernels"].get(kernel, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`: {"moe",
    "gmm", "kernels", "scopes"}, or None where the program names no routed
    feed-forward (a program without one, or older than its names)."""
    if moe_names() is None:
        return None
    moe, _, _ = moe_names()
    tables = sr.op_names(path)
    if not any(moe in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps * len(per_device)
    out = {k: sum(d[k] for d in per_device) / scale for k in ("moe", "gmm")}
    for k in ("kernels", "scopes"):
        out[k] = {name: ms / 1e6 / steps
                  for name, ms in sr._mean([d[k] for d in per_device]).items()}
    return out


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the split for people."""
    path = sr.trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            # The routing drifts as the router trains: the untraced
            # window's step time at its start and at its end, for people.
            gaps = context["gaps_ms"]
            tenth = max(1, len(gaps) // 10)
            print("INFO " + json.dumps(
                {"moe_ms_a_step": {"by_scope": out["scopes"],
                                   "by_kernel": out["kernels"]},
                 "step_ms_median_first_tenth_of_window": median(gaps[:tenth]),
                 "step_ms_median_last_tenth_of_window": median(gaps[-tenth:])}),
                flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`moe`, `gmm` or `shuffle` (= moe - gmm) in ms a step, or None."""
    out = reduce(trace, context)
    if out is None:
        return None
    return out["moe"] - out["gmm"] if what == "shuffle" else out[what]
