"""Reduction of a profiler trace for what a Mamba-2 layer adds to a step
(`horovod_tpu/models/transformer.py::Mamba2`, `horovod_tpu/ops/ssd.py`): the
device time under the scope `hvd_ssm` (the mixer whole: projections,
convolution, scan, gate and grouped norm; both directions, a recomputed
forward too), the parts of it under `hvd_ssm_conv` and `hvd_ssd`, and the
flash kernels by the names the program gave them. Every name comes from the
program's `horovod_tpu.profile`, through `scope_reduce.names`: a program
that lacks `SSM` (the parent of the PR that brought it) reads as None.
Beside `scope_reduce.py`, whose reading of the trace (an instruction's
`op_name` from the event metadata, self times, `kernel_of`) it uses and
does not change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def nemo3_names():
    """(the mixer's scope, the scopes inside it, the flash kernels' names)
    as the program's `horovod_tpu.profile` gives them, or None."""
    ssm = getattr(sr.names, "SSM", None)
    if ssm is None:
        return None
    inner = tuple(t for t in sr.names.SSM_SCOPES if t != ssm)
    return ssm, inner, tuple(getattr(sr.names, k) for k in (
        "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
        if hasattr(sr.names, k))


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"ssm", "ssd", "flash": ns;
    "ssm_parts": {"<inner scope or the mixer's> fwd|bwd": ns}, adding up to
    "ssm"; "flash_kernels": {kernel: ns}, adding up to "flash"}. "ssd" is
    the part of "ssm" under `profile.SSD`. An instruction with no `op_name`
    counts with the named one before it, as in `scope_reduce.self_ns`."""
    ssm, inner_scopes, flash = nemo3_names()
    out = {"ssm": 0.0, "ssd": 0.0, "flash": 0.0, "ssm_parts": {},
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
        if ssm in toks:
            out["ssm"] += ns
            inner = next((t for t in toks if t in inner_scopes), ssm)
            if inner == sr.names.SSD:
                out["ssd"] += ns
            label = inner + (" bwd" if sr._BACKWARD.search(op_name)
                             else " fwd")
            out["ssm_parts"][label] = out["ssm_parts"].get(label, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no Mamba-2 mixer (a program without one, or older
    than the name)."""
    if sr.names is None or nemo3_names() is None:
        return None
    ssm = nemo3_names()[0]
    tables = sr.op_names(path)
    if not any(ssm in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("ssm", "ssd", "flash")}
    for k in ("ssm_parts", "flash_kernels"):
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
                {"ssm_ms_a_step": dict(sorted(out["ssm_parts"].items())),
                 "flash_ms_a_step_by_kernel": out["flash_kernels"]}),
                flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`ssm`, `ssd` or `flash` in ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else out[what]
