"""`run.py`'s control flow on the CPU (`--rehearse`): `--trace 2` ends in
one result line that holds no metric (a time from a CPU is not a device
number), with the counts of the closed window; `--trace 0` prints what it
printed before there was a `--trace 2`."""

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
            "step_ms_long_gaps_excess_ms", "step_ms_max"} <= set(window)
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


def test_long_gaps_tell_one_late_wait_from_slower_steps():
    sys.path.insert(0, ROOT)
    from benchmark.run import long_gaps

    assert long_gaps([100.0] * 9 + [260.0]) == (100.0, 1, 160.0)
    assert long_gaps([115.0] * 10) == (115.0, 0, 0)
