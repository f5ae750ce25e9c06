"""The four readers of `setup_s`'s layers on a made-up record of getting
going (`hvd.profile.phases()`): a warm process (the step's executable comes
from the cache), a cold one (XLA compiles it), an empty record, and a
program that keeps none. No reader raises; each reads a number wherever the
record holds its spans."""

import json
import types

import pytest

import horovod_tpu as hvd
from benchmark import setup_reduce
from benchmark.layer_metrics import (init_s, place_s, step_executable_s,
                                     step_lower_s)

READERS = {"init_s": init_s, "place_s": place_s,
           "step_lower_s": step_lower_s,
           "step_executable_s": step_executable_s}
SECOND = 10 ** 9


def made_up(cache, compile_s):
    """A record as `phases()` returns it: (name, parent, start s, seconds,
    attrs) per span, the step's trace met twice (the second from jit's own
    cache), another function's compile beside the step's, one span open."""
    prof = hvd.profile
    step = {"fun_name": prof.STEP_FUN_NAME, "nested": 7}
    cached = {"cache": cache,
              "retrieval_s": 0.5 if cache == "hit" else None}
    rows = [(prof.SPAN_INIT, None, 0.0, 2.0, {}),
            (prof.SPAN_NATIVE_BUILD, 0, 0.1, 0.25, {}),
            (prof.SPAN_NATIVE_INIT, 0, 0.5, 1.0, {}),
            (prof.SPAN_MAKE_STEP, None, 3.0, 0.125, {}),
            (prof.SPAN_PLACE, None, 4.0, 1.5, {}),
            (prof.SPAN_JAX_COMPILE, 4, 4.5, 0.75,
             dict(cached, fun_name="_multi_slice", nested=0)),
            (prof.SPAN_JAX_TRACE, None, 6.0, 3.0, step),
            (prof.SPAN_JAX_LOWER, None, 9.0, 1.0, step),
            (prof.SPAN_JAX_COMPILE, None, 10.0, compile_s,
             dict(step, **cached)),
            (prof.SPAN_JAX_TRACE, None, 90.0, 0.0, step),
            (prof.SPAN_PLACE, None, 95.0, None, {})]
    return [{"name": name, "parent": parent, "start_ns": int(at * SECOND),
             "end_ns": None if took is None else int((at + took) * SECOND),
             "attrs": attrs} for name, parent, at, took, attrs in rows]


@pytest.mark.parametrize("cache,compile_s", [("hit", 4.0), ("miss", 64.0)])
def test_readers_on_a_made_up_record(monkeypatch, capsys, cache, compile_s):
    record = made_up(cache, compile_s)
    monkeypatch.setattr(hvd.profile, "phases", lambda: record)
    values = {name: mod.read(None, {}) for name, mod in READERS.items()}
    assert values == {"init_s": 2.0, "place_s": 1.5, "step_lower_s": 4.0,
                      "step_executable_s": compile_s}
    (line,) = capsys.readouterr().out.splitlines()  # one reader reports
    assert line.startswith("INFO ")
    said = json.loads(line[5:])["setup_s_by_program_span"]
    assert said["layers"] == values
    assert said["hvd_init_split"] == {
        hvd.profile.SPAN_NATIVE_BUILD: 0.25,
        hvd.profile.SPAN_NATIVE_INIT: 1.0, "rest": 0.75}
    assert len(said["spans"]) == len(record)
    step = said["compiles"][hvd.profile.STEP_FUN_NAME]
    assert (step["requests"], step["recompiles"]) == (1, 0)
    assert (step["hits"], step["misses"]) == (
        (1, 0) if cache == "hit" else (0, 1))
    compiled = [s for s in said["spans"] if s.get("cache")
                and s["fun_name"] == hvd.profile.STEP_FUN_NAME]
    assert [(s["cache"], s["retrieval_s"]) for s in compiled] == [
        (cache, 0.5 if cache == "hit" else None)]


def test_empty_record_reads_none_everywhere(monkeypatch, capsys):
    monkeypatch.setattr(hvd.profile, "phases", lambda: [])
    assert {name: mod.read(None, {}) for name, mod in READERS.items()} == \
        dict.fromkeys(READERS)
    assert "setup_s_by_program_span" in capsys.readouterr().out


def test_program_without_a_record_reads_none_and_says_nothing(monkeypatch,
                                                              capsys):
    """The parent of PR 36: `horovod_tpu.profile` has `span` and no
    `phases`."""
    monkeypatch.setattr(hvd, "profile", types.SimpleNamespace(
        span=hvd.profile.span))
    assert {name: mod.read(None, {}) for name, mod in READERS.items()} == \
        dict.fromkeys(READERS)
    assert capsys.readouterr().out == ""
