"""Reduction of a profiler trace for what block-diffusion training adds to a
step (`horovod_tpu/models/block_diffusion.py`, the mask-ruled flash
kernels): the device time under the scope `hvd_bd` (the noise, the doubled
batch, the slice of the noisy half; both directions), under a block's `attn`
half with the flash kernels, and in the flash kernels by the names the
program gave them. Every name comes from the program's
`horovod_tpu.profile`, through `scope_reduce.names`: a program that lacks
`BD` (the parent of the PR that brought it) reads as None. Beside
`scope_reduce.py`, whose reading of the trace (an instruction's `op_name`
from the event metadata, self times, `kernel_of`) it uses and does not
change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def sdar_names():
    """(the block-diffusion scope, the flash kernels' names) as the
    program's `horovod_tpu.profile` gives them, or None."""
    bd = getattr(sr.names, "BD", None)
    if bd is None:
        return None
    return bd, tuple(getattr(sr.names, k) for k in (
        "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
        if hasattr(sr.names, k))


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"bd", "attn", "flash": ns;
    "bd_parts": {"fwd" | "bwd": ns}, adding up to "bd"; "flash_kernels":
    {kernel: ns}, adding up to "flash"}. An instruction with no `op_name`
    counts with the named one before it, as in `scope_reduce.self_ns`."""
    bd, flash = sdar_names()
    out = {"bd": 0.0, "attn": 0.0, "flash": 0.0, "bd_parts": {},
           "flash_kernels": {}}
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
        if kernel in flash:
            out["flash"] += ns
            out["flash_kernels"][kernel] = \
                out["flash_kernels"].get(kernel, 0.0) + ns
        if kernel in flash or (sr.names.BLOCK in toks and "attn" in toks):
            out["attn"] += ns
        if bd in toks:
            out["bd"] += ns
            label = "bwd" if sr._BACKWARD.search(op_name) else "fwd"
            out["bd_parts"][label] = out["bd_parts"].get(label, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no block-diffusion scope (a program without one, or
    older than the name)."""
    if sr.names is None or sdar_names() is None:
        return None
    bd = sdar_names()[0]
    tables = sr.op_names(path)
    if not any(bd in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("bd", "attn", "flash")}
    for k in ("bd_parts", "flash_kernels"):
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
                {"bd_ms_a_step": dict(sorted(out["bd_parts"].items())),
                 "flash_ms_a_step_by_kernel": out["flash_kernels"],
                 "attn_ms_a_step": out["attn"],
                 "flash_tiles_visited_masked_skipped":
                 context["counts"].get("flash_tiles")}), flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`bd`, `attn` or `flash` in ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else out[what]
