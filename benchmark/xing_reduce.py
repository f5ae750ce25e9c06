"""Reduction of a profiler trace for the parts that latent attention,
hyper-connections and the multi-token prediction module add to a step
(`horovod_tpu/models/transformer.py`): the device time under the scope
`hvd_hc` (with `hvd_hc_map` and `hvd_hc_mix` and the direction apart), under
a block's `attn` half (the flash kernels included), in the flash kernels by
the names the program gave them, and under `hvd_mtp`. The views overlap on
purpose: the module's block has its own hyper-connections and attention,
which count under `hc` and `mla` and under `mtp`. Every name comes from the
program's `horovod_tpu.profile`, through `scope_reduce.names`: a program
that lacks them reads as None. Beside `scope_reduce.py`, whose reading of
the trace (an instruction's `op_name` from the event metadata, self times,
`kernel_of`) it uses and does not change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def xing_names():
    """(the hyper-connection's scope, its inner scopes, the module's scope,
    the flash kernels' names) as the program's `horovod_tpu.profile` gives
    them, or None for a program that has no such names."""
    hc = getattr(sr.names, "HC", None)
    mtp = getattr(sr.names, "MTP", None)
    if hc is None or mtp is None:
        return None
    inner = tuple(t for t in sr.names.HC_SCOPES if t != hc)
    flash = tuple(getattr(sr.names, k) for k in (
        "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
        if hasattr(sr.names, k))
    return hc, inner, mtp, flash


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"hc", "mla", "flash",
    "mtp": ns; "hc_parts": {"<inner scope> fwd|bwd": ns}, adding up to
    "hc"; "flash_kernels": {kernel: ns}, adding up to "flash"}. An
    instruction with no `op_name` counts with the named one before it, as
    in `scope_reduce.self_ns`."""
    hc, inner_scopes, mtp, flash = xing_names()
    out = {"hc": 0.0, "mla": 0.0, "flash": 0.0, "mtp": 0.0,
           "hc_parts": {}, "flash_kernels": {}}
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
        if hc in toks:
            out["hc"] += ns
            label = next((t for t in toks if t in inner_scopes), hc) + (
                " bwd" if sr._BACKWARD.search(op_name) else " fwd")
            out["hc_parts"][label] = out["hc_parts"].get(label, 0.0) + ns
        elif kernel in flash or (sr.names.BLOCK in toks and "attn" in toks):
            out["mla"] += ns
        if mtp in toks:
            out["mtp"] += ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no hyper-connection (a program without one, or older
    than its names)."""
    if sr.names is None or xing_names() is None:
        return None
    hc = xing_names()[0]
    tables = sr.op_names(path)
    if not any(hc in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("hc", "mla", "flash", "mtp")}
    for k in ("hc_parts", "flash_kernels"):
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
                {"hc_ms_a_step": dict(sorted(out["hc_parts"].items())),
                 "flash_ms_a_step_by_kernel": out["flash_kernels"],
                 "mla_ms_a_step": out["mla"], "mtp_ms_a_step": out["mtp"]}),
                flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`hc`, `mla`, `flash` or `mtp` in ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else out[what]
