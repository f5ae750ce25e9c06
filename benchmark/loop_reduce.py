"""Reduction of a profiler trace for the looped stack
(`horovod_tpu/models/transformer.py`, `num_passes` > 1): the device time
under the scope `hvd_loop` (all the passes over the stack, both directions)
with the time of each pass and direction, and the time under `hvd_exit` (the
exit gate, the exit distribution, its entropy and the weights of the loss's
rows). Every name comes from the program's `horovod_tpu.profile`, through
`scope_reduce.names`: a program that lacks them reads as None. Beside
`scope_reduce.py`, whose reading of the trace (an instruction's `op_name`
from the event metadata, self times) it uses and does not change.
"""

import json
import os
import re

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr


def loop_names():
    """(the scope around the passes, a pattern for one pass's scope, the
    scope of the exits) as the program's `horovod_tpu.profile` gives them,
    or None for a program that has no such names."""
    loop = getattr(sr.names, "LOOP", None)
    if loop is None:
        return None
    a_pass = re.compile("^" + re.escape(sr.names.LOOP_PASS).replace(
        "%d", r"(\d+)") + "$")
    return loop, a_pass, sr.names.EXIT


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"loop": under the scope
    `profile.LOOP`, either direction, kernels included; "exit": under
    `profile.EXIT`; "passes": {"pass_<t> fwd" | "pass_<t> bwd": ns}, which
    add up to "loop"}. An instruction with no `op_name` counts with the
    named one before it, as in `scope_reduce.self_ns`."""
    loop, a_pass, exits = loop_names()
    out = {"loop": 0.0, "exit": 0.0, "passes": {}}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        ns = sum(e - s for s, e in intervals)
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        toks = sr.scopes(op_name)
        if loop in toks:
            out["loop"] += ns
            rest = toks[toks.index(loop) + 1:]
            which = next((t for t in rest if a_pass.match(t)), loop)
            label = which + (" bwd" if sr._BACKWARD.search(op_name)
                             else " fwd")
            out["passes"][label] = out["passes"].get(label, 0.0) + ns
        elif exits in toks:
            out["exit"] += ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`: {"loop",
    "exit", "passes"}, or None where the program names no looped stack (a
    program without one, or older than its names)."""
    if sr.names is None or loop_names() is None:
        return None
    loop = loop_names()[0]
    tables = sr.op_names(path)
    if not any(loop in sr.scopes(op)
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: sum(d[k] for d in per_device) / len(per_device) / scale
           for k in ("loop", "exit")}
    out["passes"] = {name: ns / scale for name, ns in sr._mean(
        [d["passes"] for d in per_device]).items()}
    return out


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the time a pass and direction for
    people."""
    path = sr.trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            print("INFO " + json.dumps(
                {"loop_ms_a_step": dict(sorted(out["passes"].items())),
                 "exit_ms_a_step": out["exit"]}), flush=True)
    return _reduced[key]


def ms(trace, context, what):
    """`loop` or `exit` in ms a step, or None."""
    out = reduce(trace, context)
    return None if out is None else out[what]
