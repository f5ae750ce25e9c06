"""Reduction of a profiler trace for what an attention module does OUTSIDE
its kernels and a Mamba-2 mixer outside its scan
(`horovod_tpu/models/transformer.py`: `Attention`, `LatentAttention`,
`Mamba2`), one reducer for every cell. Two areas, each split by the scopes
the program opens where the work happens:

- `attn`: everything under a block's `attn` half, under an attention kind's
  scope, and the flash kernels (the total `mla_ms`, `attn_ms.sdar` and
  `attn_window_ms` + `attn_full_ms` take, and like `mla_ms` not what lies
  under a hyper-connection), by `hvd_attn_proj`, `hvd_attn_norm`,
  `hvd_attn_rope`, each flash kernel's own name, and `rest` (what the
  program names no further: reshapes, transposes and copies around the
  kernels; under a kind the norm before the attention and the residual add);
- `ssm`: everything under `hvd_ssm` (the total `ssm_ms` takes), by
  `hvd_ssm_proj`, `hvd_ssm_gate`, `hvd_ssm_conv`, `hvd_ssd` and `rest` (the
  slices of the in-projection's output, the reshapes).

The parts of an area add up to it. A part's time is by FUSION: a fusion has
one `op_name` and counts wholly for the part that names; which fusions hold
more than one is `hvd.profile.fused_scopes`' to say, from the compiled text.
Directions: `fwd`, `bwd`, and `again` for a recomputed forward (inside the
backward, under jax's `rematted_computation`; the other reducers count it as
backward). Every name comes from the program's `horovod_tpu.profile`,
through `scope_reduce.names`: a program that lacks `ATTN_PARTS` (the parent
of the PR that brought them) reads as None. Beside `scope_reduce.py`, whose
reading of the trace (an instruction's `op_name` from the event metadata,
self times, `kernel_of`) it uses and does not change.
"""

import json
import os

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

REST = "rest"
AGAIN = "rematted_computation"  # jax's name for a recomputed forward
LONGEST = 12


def inner_names():
    """What the program's `horovod_tpu.profile` says of the two areas, or
    None for a program older than `ATTN_PARTS`: {"attn_parts", "ssm",
    "ssm_parts" (`SSM_SCOPES` but the mixer's own), "kinds" (the attention
    kinds' scopes), "hc", "flash" (the flash kernels' names)}."""
    parts = getattr(sr.names, "ATTN_PARTS", None)
    if parts is None:
        return None
    ssm = getattr(sr.names, "SSM", None)
    return {
        "attn_parts": tuple(parts), "ssm": ssm,
        "ssm_parts": tuple(t for t in getattr(sr.names, "SSM_SCOPES", ())
                           if t != ssm),
        "kinds": tuple(getattr(sr.names, "ATTN_KINDS", {}).values()),
        "hc": getattr(sr.names, "HC", None),
        "flash": tuple(getattr(sr.names, k) for k in (
            "FLASH_FWD", "FLASH_BWD", "FLASH_DQ", "FLASH_DKV")
            if hasattr(sr.names, k))}


def where(op_name, kernel, names):
    """(area, part, direction) of an instruction, or None where it lies in
    neither area."""
    toks = sr.scopes(op_name)
    if names["ssm"] in toks:
        area, part = "ssm", next(
            (t for t in toks if t in names["ssm_parts"]), REST)
    elif names["hc"] in toks:
        return None  # the connection around a branch is `hc_ms`'s
    elif kernel in names["flash"]:
        area, part = "attn", kernel
    elif (sr.names.BLOCK in toks and "attn" in toks) or any(
            t in names["kinds"] for t in toks):
        area, part = "attn", next(
            (t for t in toks if t in names["attn_parts"]), REST)
    else:
        return None
    if not sr._BACKWARD.search(op_name):
        return area, part, "fwd"
    return area, part, "again" if AGAIN in toks else "bwd"


def self_ns(events, table):
    """Self nanoseconds of one device's events: {"attn", "ssm": {"<part>
    <direction>": ns}; "instructions": {area: {instruction: [part, ns]}}}.
    An instruction with no `op_name` counts with the named one before it,
    as in `scope_reduce.self_ns`."""
    names = inner_names()
    out = {"attn": {}, "ssm": {}, "instructions": {"attn": {}, "ssm": {}}}
    read = {}  # {(op_name, kernel): `where`'s answer}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        op_name = table.get(ev.name, "")
        if op_name:
            last_named = op_name
        else:
            op_name = last_named
        kernel = sr.kernel_of(ev, op_name)
        if (op_name, kernel) not in read:
            read[op_name, kernel] = where(op_name, kernel, names)
        if read[op_name, kernel] is None:
            continue
        area, part, direction = read[op_name, kernel]
        ns = sum(e - s for s, e in intervals)
        label = part + " " + direction
        out[area][label] = out[area].get(label, 0.0) + ns
        row = out["instructions"][area].setdefault(ev.name, [part, 0.0])
        row[1] += ns
    return out


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace`, or None where
    the program names none of the parts (a program older than the names, or
    one with no attention and no mixer): per area (`attn`, `ssm`) {"parts":
    {part: ms}, adding up to the area; "by_direction": {"<part>
    <direction>": ms}; "instructions": {instruction: [part, ms]}, every
    device instruction of the area; "longest": the `LONGEST` longest of them
    outside the kernels, [[instruction, part, ms]]}."""
    names = None if sr.names is None else inner_names()
    if names is None:
        return None
    named = set(names["attn_parts"]) | set(names["ssm_parts"])
    tables = sr.op_names(path)
    if not any(t in named for table in tables.values()
               for op in table.values() for t in sr.scopes(op)):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {}
    for area in ("attn", "ssm"):
        by_direction = {k: ns / scale for k, ns in sr._mean(
            [d[area] for d in per_device]).items()}
        parts = {}
        for label, ms in by_direction.items():
            part = label.rsplit(" ", 1)[0]
            parts[part] = parts.get(part, 0.0) + ms
        rows = [d["instructions"][area] for d in per_device]
        ms_of = sr._mean([{k: v[1] for k, v in r.items()} for r in rows])
        part_of = {k: v[0] for r in rows for k, v in r.items()}
        instructions = {k: [part_of[k], ns / scale]
                        for k, ns in ms_of.items()}
        longest = sorted(((k, p, ms) for k, (p, ms) in instructions.items()
                          if p not in names["flash"]),
                         key=lambda row: -row[2])[:LONGEST]
        out[area] = {"parts": parts, "by_direction": by_direction,
                     "instructions": instructions,
                     "longest": [list(row) for row in longest]}
    return out


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the split for people: by part and
    direction, and the longest instructions of each area outside the
    kernels with the part each was counted to."""
    path = sr.trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            print("INFO " + json.dumps({"inner_ms_a_step": {
                area: {"by_part_and_direction": dict(sorted(
                    out[area]["by_direction"].items())),
                    "longest_instructions_outside_the_kernels":
                    out[area]["longest"]}
                for area in ("attn", "ssm") if out[area]["parts"]}}),
                flush=True)
    return _reduced[key]


def ms(trace, context, area, part):
    """Milliseconds a step in the part of `area` (`attn`, `ssm`) under the
    scope the program's `horovod_tpu.profile` calls `part` (`ATTN_PROJ`,
    ...), or None: a program without the name, or a step with nothing under
    it."""
    out = reduce(trace, context)
    if out is None:
        return None
    return out[area]["parts"].get(getattr(sr.names, part, None)) or None
