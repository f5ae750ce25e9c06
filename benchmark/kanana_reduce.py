"""Reduction of a profiler trace for latent attention in a stack that has
nothing else of its own to name (`builders/kanana.py`): the device time
under a block's `attn` half with the flash kernels (`mla`), and in the flash
kernels by the names the program gave them, the forward and the backward
apart (the backward: `hvd_flash_bwd`, or `hvd_flash_dq` + `hvd_flash_dkv`,
whichever the plan chose). Every name comes from the program's
`horovod_tpu.profile`, through `scope_reduce.names`: a program that lacks
them, or a trace with no flash kernel, reads as None. Beside
`scope_reduce.py`, whose reading of the trace (an instruction's `op_name`
from the event metadata, self times, `kernel_of`) it uses and does not
change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def flash_names():
    """(the forward kernel's name, the backward kernels' names) as the
    program's `horovod_tpu.profile` gives them, or None."""
    fwd = getattr(sr.names, "FLASH_FWD", None)
    if fwd is None:
        return None
    return fwd, tuple(getattr(sr.names, k) for k in (
        "FLASH_BWD", "FLASH_DQ", "FLASH_DKV") if hasattr(sr.names, k))


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"mla", "flash",
    "flash_fwd", "flash_bwd": ns; "flash_kernels": {kernel: ns}, adding up
    to "flash" = "flash_fwd" + "flash_bwd"; "mla_parts": {"fwd" | "bwd":
    ns}, adding up to "mla"}. An instruction with no `op_name` counts with
    the named one before it, as in `scope_reduce.self_ns`."""
    fwd, bwd = flash_names()
    out = {"mla": 0.0, "flash": 0.0, "flash_fwd": 0.0, "flash_bwd": 0.0,
           "flash_kernels": {}, "mla_parts": {}}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        ns = sum(e - s for s, e in intervals)
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        toks = sr.scopes(op_name)
        kernel = sr.kernel_of(ev, op_name)
        flash = kernel == fwd or kernel in bwd
        if flash:
            out["flash"] += ns
            out["flash_fwd" if kernel == fwd else "flash_bwd"] += ns
            out["flash_kernels"][kernel] = \
                out["flash_kernels"].get(kernel, 0.0) + ns
        if flash or (sr.names.BLOCK in toks and "attn" in toks):
            out["mla"] += ns
            label = "bwd" if sr._BACKWARD.search(op_name) else "fwd"
            out["mla_parts"][label] = out["mla_parts"].get(label, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no flash kernel or the trace holds none."""
    if sr.names is None or flash_names() is None:
        return None
    tables = sr.op_names(path)
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    if not any(d["flash"] for d in per_device):
        return None
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("mla", "flash", "flash_fwd", "flash_bwd")}
    for k in ("flash_kernels", "mla_parts"):
        out[k] = {name: ns / scale for name, ns in sr._mean(
            [d[k] for d in per_device]).items()}
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
            print("INFO " + json.dumps(
                {"flash_ms_a_step_by_kernel": out["flash_kernels"],
                 "mla_ms_a_step": dict(sorted(out["mla_parts"].items())),
                 "flash_plan": context["counts"].get("flash_plan")}),
                flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`mla`, `flash`, `flash_fwd` or `flash_bwd` in ms a step, or None
    (nothing to read, or nothing ran)."""
    out = reduce(trace, context)
    return None if out is None else out[what] or None
