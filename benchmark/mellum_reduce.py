"""Reduction of a profiler trace for what an attention KIND a layer adds to a
step (`horovod_tpu/models/transformer.py`, `attention_types`): the device
time under each kind's scope (`hvd_attn_window`, `hvd_attn_full`: the norm
before the attention, the projections, the per-head norms, the kind's
rotation, its flash kernels, the output projection, the residual add; both
directions, and the forward again where a block is recomputed), and inside
it in the flash kernels by the names the program gave them: the two kinds
run kernels of one name and one shape, and the scope is what tells them
apart. Every name comes from the program's `horovod_tpu.profile`, through
`scope_reduce.names`: a program that lacks `ATTN_KINDS` (the parent of the PR
that brought it) reads as None. Beside `scope_reduce.py`, whose reading of
the trace (an instruction's `op_name` from the event metadata, self times,
`kernel_of`) it uses and does not change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def kind_scopes():
    """{scope: kind} as the program's `horovod_tpu.profile` gives them, or
    None."""
    kinds = getattr(sr.names, "ATTN_KINDS", None)
    return None if kinds is None else {v: k for k, v in kinds.items()}


def flash_names():
    return tuple(getattr(sr.names, k) for k in (
        "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
        if hasattr(sr.names, k))


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"attn": {kind: ns},
    "flash": {kind: {kernel: ns}}, "flash_unscoped": ns (a flash kernel
    under neither scope: there should be none)}. An instruction with no
    `op_name` counts with the named one before it, as in
    `scope_reduce.self_ns`."""
    scopes, flash = kind_scopes(), flash_names()
    out = {"attn": dict.fromkeys(scopes.values(), 0.0),
           "flash": {k: {} for k in scopes.values()}, "flash_unscoped": 0.0}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        ns = sum(e - s for s, e in intervals)
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        kind = next((scopes[t] for t in sr.scopes(op_name) if t in scopes),
                    None)
        kernel = sr.kernel_of(ev, op_name)
        if kind is not None:
            out["attn"][kind] += ns
        if kernel in flash:
            if kind is None:
                out["flash_unscoped"] += ns
            else:
                by = out["flash"][kind]
                by[kernel] = by.get(kernel, 0.0) + ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`: {"attn":
    {kind: ms}, "flash": {kind: {kernel: ms}}, "flash_unscoped": ms}, or
    None where the program names no attention kind (a program without one,
    or older than the names)."""
    if sr.names is None or kind_scopes() is None:
        return None
    scopes = kind_scopes()
    tables = sr.op_names(path)
    if not any(t in scopes for table in tables.values()
               for op in table.values() for t in sr.scopes(op)):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    n = len(per_device)
    return {
        "attn": {k: ms / scale for k, ms in sr._mean(
            [d["attn"] for d in per_device]).items()},
        "flash": {kind: {k: ms / scale for k, ms in sr._mean(
            [d["flash"][kind] for d in per_device]).items()}
            for kind in scopes.values()},
        "flash_unscoped": sum(d["flash_unscoped"] for d in per_device)
        / n / scale}


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the split for people, with the plan's
    tile counts and each kind's kernel calls a step."""
    path = sr.trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            counts = context["counts"]
            print("INFO " + json.dumps(
                {"attn_ms_a_step_by_kind": out["attn"],
                 "flash_ms_a_step_by_kind_and_kernel": out["flash"],
                 "flash_ms_a_step_under_no_kind": out["flash_unscoped"],
                 "flash_layers_and_forwards_again_by_kind": {
                     kind: [c["layers"], c["forward_again"]]
                     for kind, c in counts.get("flash_by_kind", {}).items()},
                 "flash_tiles_visited_masked_skipped_by_kind":
                 counts.get("flash_tiles")}), flush=True)
    return _reduced[key]


def attn_ms(trace, context, kind):
    """Everything under the kind's scope, ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else out["attn"].get(kind) or None


def flash_ms(trace, context, kind):
    """The flash kernels under the kind's scope, ms a step, or None."""
    out = reduce(trace, context)
    if out is None:
        return None
    return sum(out["flash"].get(kind, {}).values()) or None


def flash_roofline(trace, context, kind):
    """The kind's flash kernels' share of their roofline, in percent
    (`flash_roofline.share`, on the kind's own counts), or None."""
    from benchmark.layer_metrics.flash_roofline import share

    by_kind = context["counts"].get("flash_by_kind", {})
    if kind not in by_kind:
        return None
    counts = dict(context["counts"],
                  flash_executed_flops=by_kind[kind]["executed_flops"],
                  flash_min_bytes=by_kind[kind]["min_bytes"])
    return share(trace, dict(context, counts=counts),
                 flash_ms(trace, context, kind),
                 "flash_%s_roofline" % kind)
