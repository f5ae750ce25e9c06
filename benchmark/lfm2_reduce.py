"""Reduction of a profiler trace for what an LFM2 stack adds to a step
(`horovod_tpu/models/transformer.py::GatedShortConv`,
`horovod_tpu/ops/sconv.py`; `builders/lfm2.py`): the device time under the
scope `hvd_sconv` (a conv layer's first branch whole: the norm before the
mixer, its two projections, the gated pass between them, the residual add;
both directions, a recomputed forward too) and its parts by the scope inside
it (`hvd_sconv_proj`, `hvd_sconv_gate`; what is under `hvd_sconv` alone stays
the mixer's own: the norm and the add). The attention layers' scope and their
flash kernels are `mellum_reduce.py`'s reading (kind "full"), the routed
layers' `moe_reduce.py`'s. Every name comes from the program's
`horovod_tpu.profile`, through `scope_reduce.names`: a program that lacks
`SCONV` (the parent of the PR that brought it) reads as None. Beside
`scope_reduce.py`, whose reading of the trace (an instruction's `op_name` from
the event metadata, self times) it uses and does not change.
"""

import json
import os

from benchmark import mellum_reduce
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

attn_ms = mellum_reduce.attn_ms
flash_ms = mellum_reduce.flash_ms
flash_roofline = mellum_reduce.flash_roofline


def sconv_names():
    """(the mixer's scope, the scopes inside it) as the program's
    `horovod_tpu.profile` gives them, or None."""
    scopes = getattr(sr.names, "SCONV_SCOPES", None)
    return None if scopes is None else (scopes[0], tuple(scopes[1:]))


def self_ns(events, table):
    """Self nanoseconds of one device's events under the mixer's scope:
    {"sconv": ns; "by_scope": {inner scope or the mixer's: ns} and "parts":
    {"<scope> fwd|bwd": ns}, each adding up to "sconv"}. An instruction with
    no `op_name` counts with the named one before it, as in
    `scope_reduce.self_ns`."""
    sconv, inner_scopes = sconv_names()
    out = {"sconv": 0.0, "by_scope": {}, "parts": {}}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        toks = sr.scopes(op_name)
        if sconv not in toks:
            continue
        ns = sum(e - s for s, e in intervals)
        out["sconv"] += ns
        inner = next((t for t in toks if t in inner_scopes), sconv)
        out["by_scope"][inner] = out["by_scope"].get(inner, 0.0) + ns
        label = inner + (" bwd" if sr._BACKWARD.search(op_name) else " fwd")
        out["parts"][label] = out["parts"].get(label, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names no conv mixer (a program without one, or older than
    the name)."""
    if sr.names is None or sconv_names() is None:
        return None
    sconv = sconv_names()[0]
    tables = sr.op_names(path)
    if not any(sconv in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {"sconv": sum(d["sconv"] for d in per_device) / len(per_device)
           / scale}
    for k in ("by_scope", "parts"):
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
                {"sconv_ms_a_step": dict(sorted(out["parts"].items()))}),
                flush=True)
    return _reduced[key]


def ms(trace, context, scope=None):
    """Everything under the mixer's scope in ms a step, or with `scope` (a
    name of `horovod_tpu.profile`, e.g. "SCONV_GATE") the part under it;
    None where there is nothing to read or nothing ran."""
    out = reduce(trace, context)
    if out is None:
        return None
    if scope is None:
        return out["sconv"] or None
    return out["by_scope"].get(getattr(sr.names, scope, None)) or None


def gate_roofline(trace, context):
    """The gated pass's share of its roofline, in percent: the least bytes
    of a one-pass form over the step (`flops_lfm2.gate_step_min_bytes`, the
    builder's `counts`) at the peak bandwidth, over the device time under
    the pass's scope: the same work whatever implements it. None where
    there is nothing to read."""
    counts, peaks = context["counts"], context["peaks"]
    took = ms(trace, context, "SCONV_GATE")
    if "sconv_gate_min_bytes" not in counts or not took:
        return None
    least_s = counts["sconv_gate_min_bytes"] / peaks["hbm_bytes_per_s"]
    print("INFO " + json.dumps({"sconv_gate_roofline_binds": "bytes",
                                "least_ms": 1e3 * least_s}), flush=True)
    return 100.0 * least_s / (took / 1e3)
