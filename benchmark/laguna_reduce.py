"""Reduction of a profiler trace for what Laguna's attention adds to a step
(`horovod_tpu/models/transformer.py`, `attention_shapes` and
`attention_gate`): the two kinds' scopes and the flash kernels under each are
`mellum_reduce.py`'s reading (the kinds' kernels share a name, and here not a
shape: 48 query heads on a full layer, 64 on a window one; the builder's
`counts["flash_by_kind"]` are at each kind's own head count), and the gate's
scope (`hvd_attn_gate`: the gate's projection of the branch's normed input,
its sigmoid and the product with the heads' outputs, both directions and the
forward again where a block is recomputed) is read here. Every name comes
from the program's `horovod_tpu.profile`, through `scope_reduce.names`: a
program that lacks `ATTN_GATE` (the parent of the PR that brought it) reads
as None. Beside `scope_reduce.py`, whose reading of the trace it uses and
does not change.
"""

import json
import os

from benchmark import mellum_reduce
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

attn_ms = mellum_reduce.attn_ms
flash_ms = mellum_reduce.flash_ms
flash_roofline = mellum_reduce.flash_roofline


def gate_scope():
    """The gate's scope as the program names it, or None."""
    return None if sr.names is None else getattr(sr.names, "ATTN_GATE", None)


def gate_ns(events, table, kinds):
    """Self nanoseconds of one device's events under the gate's scope, by
    the attention kind's scope around it ("" under none): {kind: ns}. An
    instruction with no `op_name` counts with the named one before it, as
    in `scope_reduce.self_ns`."""
    gate, out, last_named = gate_scope(), {}, ""
    for ev, intervals in tr.self_intervals(events):
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        toks = sr.scopes(op_name)
        if gate in toks:
            kind = next((kinds[t] for t in toks if t in kinds), "")
            out[kind] = out.get(kind, 0.0) + sum(e - s for s, e in intervals)
    return out


def reduce_file(path, trace, steps):
    """{kind: ms a step under the gate's scope}, mean over the devices of
    `trace`, or None where the program names no gate (a program without one,
    or older than the name)."""
    gate = gate_scope()
    if gate is None:
        return None
    tables = sr.op_names(path)
    if not any(gate in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    kinds = mellum_reduce.kind_scopes() or {}
    per_device = [gate_ns(events, tables.get(n, {}), kinds)
                  for n, events in trace.devices.items()]
    return {k: ns / (1e6 * steps) for k, ns in sr._mean(per_device).items()}


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the split by kind for people."""
    path = sr.trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            print("INFO " + json.dumps({"attn_gate_ms_a_step_by_kind": out}),
                  flush=True)
    return _reduced[key]


def gate_ms(trace, context):
    """Everything under the gate's scope, ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else sum(out.values()) or None
