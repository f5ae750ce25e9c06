"""The trace reduction against hand-worked answers.

`data/synthetic.xplane.pb` (written by `make_synthetic_trace.py`, every
interval chosen by hand; microseconds from the start of step 1):

device 0, ops line                         device 0, asynchronous line
  fusion.1            0-100                  copy-start.3        100-400
  attn.1 (Pallas)   100-300                  all-reduce-start.1  700-850
  while.1           300-700  encloses
    fusion.2        300-450                device 1, ops line
    fusion.3        460-700                  fusion.1          0-500
  all-reduce-start.1 700-710                 all-reduce.2    500-700
  fusion.4          710-800                  attn.1          700-1000
  all-reduce-done.1 800-850                  fusion.1       1000-1400
  all-reduce.2      850-950                  (idle          1400-1500)
  (idle             950-1000)                all-reduce.2   1500-1900
  fusion.1         1000-1100
  attn.1           1100-1300               host
  custom-call.9    1300-1300 ConcatBitcast   bench_dispatch    -20..-5
  fusion.5         1300-1800                 bench_wait_loss     5-990
  (idle            1800-1880)                bench_dispatch    990-996
  all-reduce.2     1880-1900                 bench_wait_loss   996-1795
                                             bench_dispatch   1795-1870
two programs (steps) on each device.         bench_wait_loss  1870-1905

`data/recorded_v5e_slice.xplane.pb` is cut from a trace recorded on four
v5e chips (my chip run, PR 22, cell lm1b4_dp4): it checks that the parser
reads the instruction texts the runtime really writes.
"""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import (collective_exposed_ms, collective_ms,
                                     device_idle_pct, flash_ms)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000.0  # nanoseconds


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(DATA, "synthetic.xplane.pb"))


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_instruction_text_is_parsed(trace):
    by_name = {e.name: e for e in trace.devices[0]}
    assert by_name["attn.1"].opcode == "custom-call"
    assert by_name["attn.1"].target == "tpu_custom_call"
    assert by_name["attn.1"].shape == "bf16[4,256,128]"
    assert by_name["custom-call.9"].target == "ConcatBitcast"
    assert by_name["while.1"].opcode == "while"
    assert by_name["fusion.4"].opcode == "fusion"
    assert by_name["all-reduce-start.1"].opcode == "all-reduce-start"
    assert by_name["all-reduce.2"].shape == "f32[2048,8192]"
    assert tr.label(by_name["fusion.4"]) == "fusion f32[8,128]"
    assert trace.modules == {0: 2, 1: 2}
    assert [e.name for e in trace.host].count("bench_dispatch") == 3
    assert all(e.name.startswith("bench_") for e in trace.host)


def test_busy_window_and_idle_share(trace):
    d0, d1 = trace.devices[0], trace.devices[1]
    assert tr.window(d0) == (10_000 * US, 11_900 * US)
    # device 0 is idle 950-1000 and 1800-1880; the 450-460 hole inside
    # while.1 is covered by the while itself.
    assert tr.busy(d0) == (1900 - 50 - 80) * US
    assert tr.busy(d1) == (1900 - 100) * US
    want = 100.0 * (130 / 1900 + 100 / 1900) / 2   # 6.0526 %
    assert device_idle_pct.read(trace, {}) == pytest.approx(want, rel=1e-12)


def test_self_time_excludes_enclosed_events(trace):
    st = tr.self_times(trace.devices[0])
    assert st["while s32[]"] == 10 * US          # 400 - 150 - 240
    # fusion.1 twice (100 each), fusion.2 150, fusion.3 240, fusion.4 90,
    # fusion.5 500
    assert st["fusion f32[8,128]"] == (200 + 150 + 240 + 90 + 500) * US
    assert sum(st.values()) == tr.busy(trace.devices[0])


def test_collective_time_and_the_exposed_part(trace):
    d0 = trace.devices[0]
    # in flight: 700-850 (start..done), 850-950, 1880-1900
    inflight, exposed = tr.collective_ns(d0, trace.async_ops[0])
    assert inflight == (150 + 100 + 20) * US
    # fusion.4 (710-800) computes while the first one is in flight
    assert exposed == (60 + 100 + 20) * US
    # the ops line alone pairs start with done and gives the same answer
    assert tr.collective_ns(d0) == (inflight, exposed)
    assert tr.collective_ns(trace.devices[1]) == (600 * US, 600 * US)
    ctx = {"steps_traced": 2}
    assert collective_ms.read(trace, ctx) == pytest.approx(
        (270 + 600) / 2 / 2 / 1000)               # 0.2175 ms a step
    assert collective_exposed_ms.read(trace, ctx) == pytest.approx(
        (180 + 600) / 2 / 2 / 1000)               # 0.195 ms a step


def test_kernel_time_counts_pallas_calls_only(trace):
    # device 0: 200 + 200 (ConcatBitcast is not a kernel); device 1: 300
    assert flash_ms.read(trace, {"steps_traced": 2}) == pytest.approx(
        (400 + 300) / 2 / 2 / 1000)               # 0.175 ms a step


def test_idle_gaps_are_labelled_by_the_host_span_that_covers_them(trace):
    gaps = tr.idle_gaps(trace.devices[0], trace.host, top=5)
    assert gaps == [("host:bench_dispatch", 80 * US),
                    ("host:bench_wait_loss", 50 * US)]
    assert tr.idle_gaps(trace.devices[1], [], top=1) == \
        [("host:none", 100 * US)]


def test_a_program_without_collectives_reports_no_collective_metric(trace):
    alone = tr.Trace({0: [e for e in trace.devices[0]
                          if tr.collective_kind(e) is None]}, {}, {0: 2}, [])
    assert collective_ms.read(alone, {"steps_traced": 2}) is None
    assert collective_exposed_ms.read(alone, {"steps_traced": 2}) is None


def test_recorded_v5e_slice_parses():
    path = os.path.join(DATA, "recorded_v5e_slice.xplane.pb")
    t = tr.load(path)
    assert sorted(t.devices) == [0, 1]
    for events in t.devices.values():
        assert events
        for e in events:
            assert e.opcode and " " not in e.opcode and "%" not in e.name
        assert sum(tr.self_times(events).values()) == pytest.approx(
            tr.busy(events))
    ops = {e.opcode for e in t.devices[0]}
    assert {"fusion", "custom-call", "all-reduce"} <= ops
    assert any(e.target == "tpu_custom_call" for e in t.devices[0])
    inflight, exposed = tr.collective_ns(t.devices[0], t.async_ops.get(0, []))
    # on the v5e these all-reduces run on the core's own line: nothing can
    # hide them
    assert inflight > 0 and exposed == pytest.approx(inflight)
