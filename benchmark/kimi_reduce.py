"""Reduction of a profiler trace for what a Kimi-Linear stack adds to a step
(`horovod_tpu/models/transformer.py::KimiDeltaAttention`,
`horovod_tpu/ops/kda.py`; `builders/kimi.py`): the device time under the
scope `hvd_kda` (a KDA layer's attention half whole: the norm before the
mixer, its projections, convolutions, decay and gates, the chunked
recurrence, the output projection, the residual add; both directions, a
recomputed forward too), its parts by the scope inside it (`hvd_kda_proj`,
`hvd_kda_conv`, `hvd_kda_gate`, `hvd_kda_chunk`, `hvd_kda_carry`; what is
under `hvd_kda` alone stays the mixer's own), the two kernels of a
sub-block's own decayed scores by name (inside `hvd_kda_chunk`), the latent
layers' attention half under `hvd_attn_full` with its flash kernels (`mla`),
and the flash kernels by the names the program gave them. Every name comes from the
program's `horovod_tpu.profile`, through `scope_reduce.names`: a program
that lacks `KDA` (the parent of the PR that brought it) reads as None.
Beside `scope_reduce.py`, whose reading of the trace (an instruction's
`op_name` from the event metadata, self times, `kernel_of`) it uses and
does not change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def kimi_names():
    """(the mixer's scope, the scopes inside it, its kernels' names, the
    latent layers' scope, the flash kernels' names) as the program's
    `horovod_tpu.profile` gives them, or None."""
    kda = getattr(sr.names, "KDA", None)
    if kda is None:
        return None
    inner = tuple(t for t in sr.names.KDA_SCOPES if t != kda)
    return kda, inner, tuple(getattr(sr.names, "KDA_KERNELS", ())), \
        sr.names.ATTN_FULL, tuple(
            getattr(sr.names, k) for k in (
                "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
            if hasattr(sr.names, k))


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"kda", "mla", "flash",
    "kda_kernel": ns; "kda_by_scope": {inner scope or the mixer's: ns} and
    "kda_parts": {"<scope> fwd|bwd": ns}, each adding up to "kda";
    "kda_kernels": {kernel: ns}, adding up to "kda_kernel" (a part of the
    chunk's scope); "flash_kernels": {kernel: ns}, adding up to "flash"}. "mla" is everything under the
    latent layers' scope and every flash kernel. An instruction with no
    `op_name` counts with the named one before it, as in
    `scope_reduce.self_ns`."""
    kda, inner_scopes, kernels, full, flash = kimi_names()
    out = {"kda": 0.0, "mla": 0.0, "flash": 0.0, "kda_kernel": 0.0,
           "kda_by_scope": {}, "kda_parts": {}, "kda_kernels": {},
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
        if kernel in flash or full in toks:
            out["mla"] += ns
        if kernel in kernels:
            out["kda_kernel"] += ns
            out["kda_kernels"][kernel] = \
                out["kda_kernels"].get(kernel, 0.0) + ns
        if kda in toks:
            out["kda"] += ns
            inner = next((t for t in toks if t in inner_scopes), kda)
            by = out["kda_by_scope"]
            by[inner] = by.get(inner, 0.0) + ns
            label = inner + (" bwd" if sr._BACKWARD.search(op_name)
                             else " fwd")
            out["kda_parts"][label] = out["kda_parts"].get(label, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no KDA mixer (a program without one, or older than
    the name)."""
    if sr.names is None or kimi_names() is None:
        return None
    kda = kimi_names()[0]
    tables = sr.op_names(path)
    if not any(kda in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("kda", "mla", "flash", "kda_kernel")}
    for k in ("kda_by_scope", "kda_parts", "kda_kernels", "flash_kernels"):
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
                {"kda_ms_a_step": dict(sorted(out["kda_parts"].items())),
                 "kda_ms_a_step_by_kernel": out["kda_kernels"],
                 "mla_ms_a_step": out["mla"],
                 "flash_ms_a_step_by_kernel": out["flash_kernels"]}),
                flush=True)
    return _reduced[key]


def ms(trace, context, what, scope=None):
    """`kda`, `mla`, `flash` or `kda_kernel` in ms a step, or with `scope` (a name of
    `horovod_tpu.profile`, e.g. "KDA_CHUNK") the part of `kda` under it; None
    where there is nothing to read or nothing ran."""
    out = reduce(trace, context)
    if out is None:
        return None
    if scope is None:
        return out[what] or None
    return out["kda_by_scope"].get(getattr(sr.names, scope, None)) or None
