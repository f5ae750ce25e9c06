"""Reduction of a profiler trace (`.xplane.pb`) to what the per-layer
metrics read — the benchmark's own yardstick, checked in `tests/` on a
recorded trace with hand-worked answers.

Read with nothing but `jax.profiler.ProfileData`, in the process that held
the chip. All times are nanoseconds on the trace's clock until a function
says seconds.

What a trace of a TPU run holds (seen on the v5e, PR 22): one plane per
chip named `/device:TPU:<n>`. Its line `XLA Modules` has one event per
executed program (one per train step); its line `XLA Ops` has one event per
executed HLO instruction, and the event's name is the instruction's whole
text (`%fusion.30 = (f32[2048,50304]{...}, ...) fusion(...), kind=kLoop`;
a Pallas kernel is a `custom-call` named after the module that called it,
`%attn.47 = ... custom-call(...)`). Control-flow instructions (`while`,
`conditional`, `call`) enclose the events of their bodies on the same line,
so durations are attributed as SELF time: an event's duration minus what
its enclosed events cover. The line `Async XLA Ops` holds what runs beside
the ops line (DMA copies, asynchronous collectives) from start to done. The
host's spans (`jax.profiler.TraceAnnotation`) are on the plane `/host:CPU`,
on the same clock.
"""

import glob
import os
import re
from collections import namedtuple

# name: the instruction's name without `%` (`fusion.30`); opcode: what it
# executes (`fusion`, `custom-call`, `while`, `all-reduce`); shape: its first
# result (`f32[2048,50304]`); target: a custom call's `custom_call_target`
# (`tpu_custom_call` for a Pallas kernel), else ""; start/end in nanoseconds.
Event = namedtuple("Event", "name opcode shape target start end")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

_INSTRUCTION = re.compile(r"^%?(\S+) = (.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_SUFFIX = re.compile(r"(\.\d+)+$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?$")
# Instructions that only enclose others: their own time is the time of
# their bodies, so they are neither compute nor a kernel.
_CONTAINERS = ("while", "conditional", "call")


def parse_instruction(text):
    """(name, opcode, first result shape, custom-call target) of an event
    named by an HLO instruction's text; a name that is not such a text is
    its own name and opcode."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text, base_name(text), "", ""
    rest = " " + m.group(2)
    depth, i = 0, 1
    if rest[1] == "(":  # a tuple type: skip to its closing parenthesis
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0 and ch == ")":
                break
    op = _OPCODE.search(rest, i - 1 if i > 1 else 0)
    shape = _SHAPE.search(rest)
    target = _TARGET.search(rest)
    return (m.group(1), op.group(1) if op else base_name(m.group(1)),
            shape.group(0) if shape else "",
            target.group(1) if target else "")


def base_name(name):
    """`fusion.123` -> `fusion`, `all-reduce-start.4.1` -> `all-reduce-start`."""
    return _SUFFIX.sub("", name)


def collective_kind(event):
    """(kind, phase) of a collective instruction's event, phase one of
    '', '-start', '-done'; None for every other event."""
    m = _COLLECTIVE.match(event.opcode)
    return (m.group(1), m.group(2) or "") if m else None


def is_container(event):
    return event.opcode in _CONTAINERS


def label(event):
    """How the breakdown names an event: the instruction's name without its
    number, and its first result's shape (`fusion f32[8192,2048]`), which
    tells anonymous fusions apart."""
    return ("%s %s" % (base_name(event.name), event.shape)).strip()


# --------------------------------------------------------------------------
# Interval arithmetic on lists of (start, end).
# --------------------------------------------------------------------------

def merge(intervals):
    """Sorted, disjoint union of intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals):
    return sum(e - s for s, e in merge(intervals))


def subtract(a, b):
    """The part of the union of `a` that no interval of `b` covers."""
    out = []
    b = merge(b)
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


# --------------------------------------------------------------------------
# Loading
# --------------------------------------------------------------------------

def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    return paths[-1]


class Trace:
    """`devices`: {chip number: [Event] of the ops line, sorted by start};
    `async_ops`: the same for the line of asynchronous operations;
    `modules`: {chip number: programs executed in the trace};
    `host`: [Event] of the host plane whose names start with `host_prefix`."""

    def __init__(self, devices, async_ops, modules, host):
        self.devices = devices
        self.async_ops = async_ops
        self.modules = modules
        self.host = host


def _events(line):
    out = []
    for e in line.events:
        out.append(Event(*parse_instruction(e.name), e.start_ns,
                         e.start_ns + e.duration_ns))
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def load(path, host_prefix="bench_"):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, async_ops, modules, host = {}, {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            n = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[n] = _events(line)
                elif line.name == ASYNC_LINE:
                    async_ops[n] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[n] = sum(1 for _ in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append(Event(e.name, "host", "", "", e.start_ns,
                                          e.start_ns + e.duration_ns))
    host.sort(key=lambda e: e.start)
    return Trace(devices, async_ops, modules, host)


# --------------------------------------------------------------------------
# Reductions over one device's events
# --------------------------------------------------------------------------

def window(events):
    """(start, end) from the first event's start to the last event's end."""
    if not events:
        return (0.0, 0.0)
    return (min(e.start for e in events), max(e.end for e in events))


def busy(events):
    """Nanoseconds in which at least one operation ran."""
    return length((e.start, e.end) for e in events)


def self_intervals(events):
    """[(event, [intervals])]: for each event, the parts of its interval
    that no event enclosed in it covers. Events of one line nest or are
    disjoint; sorted by (start, -end) a parent comes before its children."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    children = [[] for _ in ordered]
    open_ = []  # indices of the events that enclose the current one
    for i, ev in enumerate(ordered):
        while open_ and ordered[open_[-1]].end <= ev.start:
            open_.pop()
        if open_:
            children[open_[-1]].append((ev.start, min(ev.end,
                                                      ordered[open_[-1]].end)))
        open_.append(i)
    return [(ev, subtract([(ev.start, ev.end)], kids))
            for ev, kids in zip(ordered, children)]


def self_times(events):
    """{label: self nanoseconds} summed over the events of that label."""
    totals = {}
    for ev, parts in self_intervals(events):
        t = sum(e - s for s, e in parts)
        key = label(ev)
        totals[key] = totals.get(key, 0.0) + t
    return totals


def collective_intervals(events, async_events=()):
    """Intervals during which a collective is in flight on this device: a
    synchronous collective's own event; for an asynchronous pair on the ops
    line, from the `-start` event's start to the matching `-done` event's
    end (matched in order within a kind, the order XLA schedules them in);
    and a collective's `-start` event on the asynchronous line, which spans
    start to done by itself."""
    out = [(ev.start, ev.end) for ev in async_events
           if collective_kind(ev) is not None]
    pending = {}
    for ev in sorted(events, key=lambda e: e.start):
        kp = collective_kind(ev)
        if kp is None:
            continue
        kind, phase = kp
        if phase == "":
            out.append((ev.start, ev.end))
        elif phase == "-start":
            pending.setdefault(kind, []).append(ev)
        elif pending.get(kind):
            out.append((pending[kind].pop(0).start, ev.end))
        else:
            out.append((ev.start, ev.end))
    for left in pending.values():
        out.extend((ev.start, ev.end) for ev in left)
    return out


def compute_intervals(events):
    """Self intervals of every event that is neither a collective nor a
    container: the time the device computes."""
    out = []
    for ev, parts in self_intervals(events):
        if collective_kind(ev) is None and not is_container(ev):
            out.extend(parts)
    return out


def collective_ns(events, async_events=()):
    """(in flight, exposed): nanoseconds with a collective in flight, and
    the part of them during which nothing else ran on the device."""
    coll = collective_intervals(events, async_events)
    return length(coll), length(subtract(coll, compute_intervals(events)))


def matching_ns(events, predicate):
    """Self nanoseconds of the events `predicate(event)` accepts."""
    return sum(sum(e - s for s, e in parts)
               for ev, parts in self_intervals(events) if predicate(ev))


def idle_gaps(events, host, top=5):
    """The `top` longest gaps between device operations as (label,
    nanoseconds), longest first. The label is the host span (see `load`'s
    `host_prefix`) that covers most of the gap, or `host:none` where no
    span of the benchmark's loop overlaps it."""
    busy_parts = merge((e.start, e.end) for e in events)
    gaps = [(busy_parts[i + 1][0] - busy_parts[i][1],
             busy_parts[i][1], busy_parts[i + 1][0])
            for i in range(len(busy_parts) - 1)]
    gaps.sort(reverse=True)
    out = []
    for dur, s, e in gaps[:top]:
        best, cover = "host:none", 0.0
        for h in host:
            c = min(e, h.end) - max(s, h.start)
            if c > cover:
                best, cover = "host:" + h.name, c
        out.append((best, dur))
    return out


# --------------------------------------------------------------------------
# Means over devices
# --------------------------------------------------------------------------

def mean_over_devices(trace, fn):
    """Mean of fn(ops events) over the devices of the trace."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    return sum(fn(ev) for ev in trace.devices.values()) / len(trace.devices)


def mean_collective_ns(trace):
    """(in flight, exposed) nanoseconds, each the mean over the devices, or
    None for a trace without a collective."""
    per_device = [collective_ns(ops, trace.async_ops.get(n, []))
                  for n, ops in trace.devices.items()]
    if not any(inflight for inflight, _ in per_device):
        return None
    return tuple(sum(col) / len(per_device) for col in zip(*per_device))


def mean_self_times(trace):
    """{label: self nanoseconds}, the mean over the devices."""
    totals = {}
    for events in trace.devices.values():
        for k, v in self_times(events).items():
            totals[k] = totals.get(k, 0.0) + v
    return {k: v / len(trace.devices) for k, v in totals.items()}
