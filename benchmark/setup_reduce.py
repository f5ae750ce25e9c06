"""Reduction of the program's own record of getting going
(`horovod_tpu.profile.phases()`; docs/TRACING.md, "Getting going") to the
layers of `setup_s`: the seconds inside `hvd.init()`, inside `step.place`,
in the trace and the lowering of the train step, and in its compile or its
load from the cache. The record is the running process's, kept in memory
from its start on the host's clock: nothing here reads the trace, and a
`--trace 2` run reads what happened before its window opened. Every name
comes from the program's `horovod_tpu.profile`; a program that keeps no
such record (the parent of PR 36) reads as None.
"""

import json


def names():
    """The program's `horovod_tpu.profile`, or None for a program that
    keeps no record of its phases."""
    import horovod_tpu as hvd

    return hvd.profile if hasattr(hvd.profile, "phases") else None


def seconds(record, name, fun_name=None):
    """Summed seconds of the record's closed spans called `name` (of the
    function `fun_name`, where one is given), or None where it has none."""
    found = [p["end_ns"] - p["start_ns"] for p in record
             if p["name"] == name and p["end_ns"] is not None
             and fun_name in (None, p["attrs"].get("fun_name"))]
    return sum(found) / 1e9 if found else None


def layers(prof, record):
    """{metric: seconds or None} of the four layers of `setup_s`."""
    step = prof.STEP_FUN_NAME
    lower = [s for s in (seconds(record, prof.SPAN_JAX_TRACE, step),
                         seconds(record, prof.SPAN_JAX_LOWER, step))
             if s is not None]
    return {"init_s": seconds(record, prof.SPAN_INIT),
            "place_s": seconds(record, prof.SPAN_PLACE),
            "step_lower_s": sum(lower) if lower else None,
            "step_executable_s": seconds(record, prof.SPAN_JAX_COMPILE,
                                         step)}


def value(metric):
    """The layer `metric` of the running process, or None."""
    prof = names()
    return None if prof is None else layers(prof, prof.phases())[metric]


def report():
    """Prints the line `INFO setup_s_by_program_span`: every span of the
    record (seconds from the first one's start, duration, parent,
    attributes), `hvd_init` split into its two native parts and the rest,
    `compiles()` and what the record dropped."""
    prof = names()
    if prof is None:
        return
    record = prof.phases()
    t0 = min((p["start_ns"] for p in record), default=0)
    spans = [dict(p["attrs"], name=p["name"], parent=p["parent"],
                  at_s=(p["start_ns"] - t0) / 1e9,
                  s=None if p["end_ns"] is None
                  else (p["end_ns"] - p["start_ns"]) / 1e9) for p in record]
    init = seconds(record, prof.SPAN_INIT)
    split = {name: seconds(record, name) or 0.0
             for name in (prof.SPAN_NATIVE_BUILD, prof.SPAN_NATIVE_INIT)}
    if init is not None:
        split["rest"] = init - sum(split.values())
    print("INFO " + json.dumps({"setup_s_by_program_span": {
        "layers": layers(prof, record), "hvd_init_split": split,
        "spans": spans, "compiles": prof.compiles(record),
        "dropped": prof.dropped()}}, sort_keys=True), flush=True)
