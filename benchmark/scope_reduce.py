"""Reduction of a profiler trace by the names the program gives its own work
(`horovod_tpu/profile.py`): the scopes of the train step's phases and of the
models' parts, and the names of the Pallas kernels. Beside
`trace_reduce.py`, which reduces by instruction and interval; checked in
`tests/test_scope_reduce.py` on a synthetic trace with hand-worked answers.

Where the names are in a trace of a TPU run: an `XLA Ops` event carries its
times and no stat of its own. The instruction's scope path (its `op_name`,
`jit(shard_step)/shard_map/hvd_fwd_bwd/transpose(jvp(Transformer))/
hvd_block/block_3/attn/dot_general`) is the stat `tf_op` of the event's
METADATA in the plane's `event_metadata` table, which
`jax.profiler.ProfileData` does not hand out. So this module reads that one
table from the `.xplane.pb` itself (a few protocol-buffer fields walked by
hand, standard library only: no second profile reader is loaded into the
process that holds the chip) and joins it to the events `trace_reduce.load`
made, by the instruction's name, which is unique in a program.

A fusion carries the one `op_name` XLA gave the fusion instruction. Where
XLA fuses across a boundary of the program's scopes (a weight gradient with
the optimizer's update behind it), the whole fusion counts for the scope its
metadata names; nothing in the program is changed to sharpen that.

A phase's time is the SELF time of its events (`trace_reduce.
self_intervals`): a `while` and the scoped instructions of its body are each
counted once, so the phases and `unscoped` add up to the time the device was
busy.
"""

import json
import os
import re

from benchmark import trace_reduce as tr

try:
    from horovod_tpu import profile as names
except ImportError:  # a program older than its names: nothing to read
    names = None

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
UNSCOPED = "unscoped"
PALLAS_TARGET = "tpu_custom_call"
OP_NAME_STAT = "tf_op"  # the profiler's name for an instruction's op_name
_PALLAS_CALL = re.compile(r"([^/()]+)/pallas_call$")
_BACKWARD = re.compile(r"(^|/)transpose\(")


# --------------------------------------------------------------------------
# What a scope path says
# --------------------------------------------------------------------------

def scopes(op_name):
    """The elements of a scope path, transforms unwrapped:
    `a/transpose(jvp(b))/c` -> [a, transpose, jvp, b, c]."""
    return [t for t in re.split(r"[/()]", op_name) if t]


def phase_of(op_name):
    """The phase scope an instruction lies under (the outermost one on its
    path), or `unscoped`."""
    for t in scopes(op_name):
        if t in names.PHASE_SCOPES:
            return t
    return UNSCOPED


def in_loss(op_name):
    return names.LOSS in scopes(op_name)


def part_of(op_name):
    """How the by-scope table names an instruction: its phase, the model's
    part (a block's `attn` or `mlp` half told apart) and, inside the
    forward-and-backward phase, the direction. A backward operation carries
    its forward's scope inside `transpose(...)`."""
    toks = scopes(op_name)
    phase = phase_of(op_name)
    label = [phase]
    part = next((t for t in toks if t in names.MODEL_SCOPES), None)
    if part == names.BLOCK:
        half = next((t for t in toks if t in ("attn", "mlp")), None)
        part += "/" + half if half else ""
    if part:
        label.append(part)
    if phase == names.FWD_BWD:
        label.append("bwd" if _BACKWARD.search(op_name) else "fwd")
    return " ".join(label)


def kernel_of(event, op_name):
    """The name the program gave a Pallas kernel's event, or None: from its
    scope path (`.../hvd_flash_fwd/pallas_call`), else from its instruction
    (`hvd_flash_fwd.3`)."""
    if event.target != PALLAS_TARGET:
        return None
    m = _PALLAS_CALL.search(op_name)
    kernel = m.group(1) if m else tr.base_name(event.name)
    return kernel if kernel in names.KERNELS else None


# --------------------------------------------------------------------------
# The event metadata of an `.xplane.pb`, by hand
# --------------------------------------------------------------------------
# XSpace{1: planes}; XPlane{2: name, 4: event_metadata, 5: stat_metadata}
# (both map<int64, message>: entries {1: key, 2: value});
# XEventMetadata{2: name, 5: stats}; XStatMetadata{1: id, 2: name};
# XStat{1: metadata_id, 5: str_value, 7: ref_value (a stat_metadata id whose
# name is the string)}.

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of a message's fields: an integer for a varint,
    (start, end) in `buf` for a length-delimited field (no copy); fixed-width
    fields are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, (i, i + size)
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError("unexpected wire type %d in the trace" % wire)


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value message's span of a map<int64, message> entry."""
    for number, value in _fields(buf, *span):
        if number == 2:
            return value
    return (span[1], span[1])


def _plane_op_names(buf, span):
    """(chip number, {instruction name: op_name}) of one XPlane; (None, {})
    for a plane that is no chip's."""
    plane_name, event_meta, stat_names = "", [], {}
    for number, value in _fields(buf, *span):
        if number == 2:
            plane_name = _text(buf, value)
        elif number == 4:
            event_meta.append(_map_value(buf, value))
        elif number == 5:
            sid, sname = None, ""
            for n, v in _fields(buf, *_map_value(buf, value)):
                if n == 1:
                    sid = v
                elif n == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
    chip = tr.DEVICE_PLANE.match(plane_name)
    if not chip:
        return None, {}
    out = {}
    for meta in event_meta:
        text, op_name = "", ""
        for number, value in _fields(buf, *meta):
            if number == 2:
                text = _text(buf, value)
            elif number == 5:
                stat = dict(_fields(buf, *value))
                if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                    continue
                if 5 in stat:
                    op_name = _text(buf, stat[5])
                elif 7 in stat:
                    op_name = stat_names.get(stat[7], "")
        instruction = tr.parse_instruction(text)[0]
        op_name = op_name.rstrip(":")
        # Two programs of one trace may use one instruction name: a name
        # they scope differently is no name.
        if out.setdefault(instruction, op_name) != op_name:
            out[instruction] = ""
    return int(chip.group(1)), out


def op_names(path):
    """{chip number: {instruction name: op_name}} from the event metadata
    of every `/device:TPU:<n>` plane of the `.xplane.pb` at `path`; an
    instruction without the stat has ""."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for number, value in _fields(buf, 0, len(buf)):
        if number == 1:
            chip, table = _plane_op_names(buf, value)
            if chip is not None:
                out[chip] = table
    return out


# --------------------------------------------------------------------------
# Reduction
# --------------------------------------------------------------------------

def self_ns(events, table):
    """Self nanoseconds of one device's events by what their scope paths
    say: {"phases": {phase or `unscoped`: ns}, "loss": ns, "kernels":
    {kernel name: ns}, "parts": {`part_of` label: ns}}.

    An instruction the compiler inserted (a copy or a slice between memory
    spaces, with no `op_name` at all) cannot be named by the program: it
    counts for the scope of the last named instruction before it on the
    device, and `parts` lists it apart (`<phase> compiler-inserted`) so
    that the share attributed in this way stays visible. An `op_name`
    that holds none of the phase scopes is the program's to name, and is
    `unscoped`."""
    phases = dict.fromkeys(names.PHASE_SCOPES + (UNSCOPED,), 0.0)
    kernels, parts, loss = {}, {}, 0.0
    read = {}  # {op_name: (its phase, whether in the loss, its part)}
    last_named = ""
    for ev, intervals in tr.self_intervals(events):
        ns = sum(e - s for s, e in intervals)
        op_name = table.get(ev.name, "")
        inherited = not op_name
        if inherited:
            op_name = last_named
        else:
            last_named = op_name
        if op_name not in read:
            read[op_name] = (phase_of(op_name), in_loss(op_name),
                             part_of(op_name))
        phase, lossy, part = read[op_name]
        phases[phase] += ns
        if lossy:
            loss += ns
        kernel = kernel_of(ev, op_name)
        if kernel:
            kernels[kernel] = kernels.get(kernel, 0.0) + ns
        label = (phase + " compiler-inserted" if inherited
                 else part + (" " + kernel if kernel else ""))
        parts[label] = parts.get(label, 0.0) + ns
    return {"phases": phases, "loss": loss, "kernels": kernels,
            "parts": parts}


def _mean(dicts):
    """{key: mean of the dicts' values}; a key one lacks counts as zero."""
    keys = set().union(*dicts)
    return {k: sum(d.get(k, 0.0) for d in dicts) / len(dicts) for k in keys}


def reduce_file(path, trace, steps):
    """Milliseconds a step, mean over the devices of `trace` (loaded from
    the `.xplane.pb` at `path`): {"phases", "loss", "kernels", "parts"} as
    `self_ns` gives them, or None for a trace whose program carries none of
    the phase scopes (a program older than its names)."""
    if names is None:
        return None
    tables = op_names(path)
    if not any(phase_of(op) != UNSCOPED
               for table in tables.values() for op in table.values()):
        return None
    per_device = [self_ns(events, tables.get(n, {}))
                  for n, events in trace.devices.items()]
    scale = 1e6 * steps
    out = {k: {name: ns / scale for name, ns in _mean(
        [d[k] for d in per_device]).items()}
        for k in ("phases", "kernels", "parts")}
    out["loss"] = sum(d["loss"] for d in per_device) / len(per_device) / scale
    return out


def trace_path(context):
    """The trace the harness has just written for this cell."""
    return tr.find_xplane(os.path.join(os.path.dirname(BENCH_DIR),
                                       ".bench_trace",
                                       context["cell"]["name"]))


_reduced = {}  # {(path, its mtime): reduce_file's result}


def reduce(trace, context):
    """`reduce_file` of the cell's trace, made once for all the readers of
    one run; the first call prints the by-scope table for people."""
    path = trace_path(context)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = out = reduce_file(path, trace,
                                          context["steps_traced"])
        if out is not None:
            top = sorted(out["parts"].items(), key=lambda kv: -kv[1])[:24]
            print("INFO " + json.dumps({"device_ms_a_step_by_scope": top}),
                  flush=True)
    return _reduced[key]


def phase_ms(trace, context, phase, zero_is_none=False):
    """Milliseconds a step under `phase`; None where the trace has no names
    (or, if `zero_is_none`, nothing of the phase)."""
    out = reduce(trace, context)
    if out is None:
        return None
    value = out["phases"][phase]
    return None if zero_is_none and value == 0 else value


def loss_ms(trace, context):
    out = reduce(trace, context)
    return None if out is None else out["loss"]


def kernel_ms(trace, context, kernel):
    """Milliseconds a step in the Pallas kernel named `kernel`, or None."""
    out = reduce(trace, context)
    return None if out is None else out["kernels"].get(kernel)
