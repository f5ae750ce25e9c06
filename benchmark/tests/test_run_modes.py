"""`run.py`'s control flow on the CPU (`--rehearse`): `--trace 2` ends in
one result line that holds no metric (a time from a CPU is not a device
number), with the counts of the closed window; `--trace 0` prints what it
printed before there was a `--trace 2`. And the window's own account
(`window_account`) on hand-made gaps and on the real loop with a fake step
that stalls under a fake clock: the window is taken once, and its rate and
its tail are over all of it."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes",
               "memory_stats_peak_bytes_in_use",
               "memory_stats_peak_bytes_reserved",
               "memory_analysis_step_bytes"}
WARMUP_STEPS = 3


def rehearse(trace, home):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(home))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lm1b4_1chip",
         "--seed", "2147483659", "--seconds", "1", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    results = [ln for ln in lines if not ln.startswith("INFO ")]
    infos = [json.loads(ln[5:]) for ln in lines if ln.startswith("INFO ")]
    assert len(results) == 1 and lines[-1] == results[0]
    return json.loads(results[0]), infos


@pytest.mark.parametrize("trace", [0, 2])
def test_rehearsal_ends_in_one_result_line_without_metrics(trace, tmp_path):
    result, infos = rehearse(trace, tmp_path)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device"]
    assert result["metrics"] == {} and result["failed"] == 0
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    window = next(i for i in infos if "steps_in_window" in i)
    assert {"step_ms_median", "step_ms_long_gaps",
            "step_ms_long_gaps_excess_ms", "step_ms_max", "step_ms_max_wait_ms",
            "step_ms_max_dispatch_ms", "window_lost_ms"} <= set(window)
    assert window["step_ms_samples"] == window["steps_in_window"]
    tracing = [i for i in infos if "tracing" in i]
    # warm-up and the window's steps; the traced steps are not counted
    assert result["attempted"] == WARMUP_STEPS + window["steps_in_window"]
    if trace == 2:
        assert len(tracing) == 1 and tracing[0]["compiles_while_tracing"] == 0
        assert {"first_start_and_stop_s", "start_s", "steps_s", "stop_s",
                "load_s", "step_ms_median_traced"} <= set(
                    tracing[0]["tracing"])
        assert any(i.get("steps_in_traced_loop", 0) >= 10 for i in infos)
        assert not os.path.exists(os.path.join(ROOT, ".bench_trace",
                                               "lm1b4_1chip"))
    else:
        assert not tracing


def run_module():
    sys.path.insert(0, ROOT)
    from benchmark import run

    return run


def test_account_of_one_long_gap_among_steady_ones():
    account = run_module().window_account([106.0] * 330 + [1600.0])
    assert account["step_ms_median"] == 106.0 and account["steps"] == 331
    assert account["window_ms"] == pytest.approx(36580.0)
    assert account["lost_ms"] == pytest.approx(1494.0)  # 1600 - 106
    assert (account["long_gaps"], account["max_ms"], account["max_at"]) == \
        (1, 1600.0, 330)
    assert account["long_gaps_excess_ms"] == pytest.approx(1494.0)


def test_account_of_steps_that_are_all_slower():
    account = run_module().window_account([115.0] * 10)
    assert account["step_ms_median"] == 115.0 and account["lost_ms"] == 0
    assert account["long_gaps"] == 0 and account["max_ms"] == 115.0


def test_a_short_gap_after_a_late_one_gives_the_time_back():
    # The host woke 160 ms late for one loss; the next step had finished
    # meanwhile and its gap is the 40 ms that were left of it: the window
    # lost 100 ms, not the late gap's excess of 160.
    account = run_module().window_account(
        [100.0] * 5 + [260.0, 40.0] + [100.0] * 4,
        wait_s=[0.1] * 5 + [0.25, 0.03] + [0.1] * 4,
        dispatch_s=[0.002] * 11)
    assert account["step_ms_median"] == 100.0
    assert account["long_gaps"] == 1
    assert account["long_gaps_excess_ms"] == pytest.approx(160.0)
    assert account["lost_ms"] == pytest.approx(100.0)
    assert account["max_wait_ms"] == pytest.approx(250.0)
    assert account["max_dispatch_ms"] == pytest.approx(2.0)


class FakeClock:
    """`time.perf_counter` for `run.Loop`: it moves only when a fake step
    says so."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now


class FakeLoss:
    def __init__(self, clock, seconds):
        self.clock, self.seconds = clock, seconds

    def block_until_ready(self):
        self.clock.now += self.seconds


def fake_loop(monkeypatch, step_s, stalls):
    """`run.Loop` over a fake step of `step_s` seconds under a fake clock;
    `stalls` {call number, from 0: seconds} makes those steps wait longer,
    as a host that slept would."""
    run = run_module()
    clock = FakeClock()
    monkeypatch.setattr(run, "time", clock)
    calls = []

    def step(params, opt_state, batch):
        calls.append(len(calls))
        clock.now += 0.001  # the dispatch
        return params, opt_state, FakeLoss(
            clock, step_s + stalls.get(calls[-1], 0.0))

    return run, run.Loop(step, (None, None, None)), calls


def test_a_stalled_window_is_reported_whole_and_says_what_it_lost(
        monkeypatch):
    # The third step of a 10 s window stalls for 2 s: the window is taken
    # once, holds every gap (the long one too, which the rate and the tail
    # are over), and its account names the stall.
    run, loop, calls = fake_loop(monkeypatch, 0.1, {WARMUP_STEPS + 2: 2.0})
    loop.run(steps=WARMUP_STEPS)
    win = loop.run(seconds=10.0)
    marks = [win["t0"]] + win["stamps"]
    gaps_ms = [1e3 * (b - a) for a, b in zip(marks, marks[1:])]
    account = run.window_account(gaps_ms, win["wait_s"], win["dispatch_s"])
    assert len(calls) == WARMUP_STEPS + win["completed"] == \
        WARMUP_STEPS + account["steps"]
    assert account["step_ms_median"] == pytest.approx(101.0)
    assert account["lost_ms"] == pytest.approx(2000.0, rel=0.02)
    assert account["long_gaps"] == 1 and account["max_at"] in (1, 2)
    assert account["max_ms"] == pytest.approx(2101.0, rel=0.02)
    assert account["max_wait_ms"] > 2000.0
    assert run.percentile(gaps_ms, 1.0) == account["max_ms"]
    # all the window's time: 2 s of 10 are gone from the rate
    assert win["completed"] == pytest.approx(80, abs=2)
    undisturbed = fake_loop(monkeypatch, 0.1, {})[1].run(seconds=10.0)
    assert undisturbed["completed"] == pytest.approx(100, abs=2)
