"""Autotune coverage: the Bayesian-optimization math (unit) and a live
HVD_TPU_AUTOTUNE=1 job (e2e). Reference semantics: ParameterManager
warmup/sample/score flow (`/root/reference/horovod/common/parameter_manager.cc:27-30`)
+ BayesianOptimization (`common/optim/bayesian_optimization.cc`)."""

import ctypes
import json
import os
import re

import numpy as np
import pytest

from horovod_tpu.common import get_basics

FUSION_LO, FUSION_HI = 0.0, 64.0
CYCLE_LO, CYCLE_HI = 1.0, 100.0
# Pipelined-ring chunk bounds of the UNCOMPRESSED profile — the e2e's
# workload (parameter_manager.cc; compressed jobs search the tighter
# [16, 1024] instead).
CHUNK_LO_KB, CHUNK_HI_KB = 64.0, 4096.0

# Fast-convergence env for the closed-loop e2es: 2 cycles per sample,
# 6 samples, 1 warmup — the tuner converges in ~14 work cycles.
FAST_TUNE_ENV = {
    "HVD_TPU_AUTOTUNE": "1",
    "HVD_TPU_AUTOTUNE_CYCLES_PER_SAMPLE": "2",
    "HVD_TPU_AUTOTUNE_MAX_SAMPLES": "6",
    "HVD_TPU_AUTOTUNE_WARMUP": "1",
}


def _bo(lo0, hi0, lo1, hi1, seed):
    lib = get_basics().lib
    lib.horovod_tpu_bo_create.restype = ctypes.c_void_p
    lib.horovod_tpu_bo_create.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,
        ctypes.c_uint64]
    lib.horovod_tpu_bo_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double)]
    lib.horovod_tpu_bo_add.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_double]
    lib.horovod_tpu_bo_best.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double)]
    lib.horovod_tpu_bo_destroy.argtypes = [ctypes.c_void_p]
    return lib, lib.horovod_tpu_bo_create(lo0, hi0, lo1, hi1, seed)


def test_bayesian_optimizer_finds_optimum_2d():
    """EI over the GP surrogate must localize the optimum of a smooth
    2-D function within the sample budget the autotuner actually uses
    (kSamplesPerCombo=10 per categorical combo, up to kMaxSamples=40) —
    and never propose points outside the bounds."""
    lib, bo = _bo(FUSION_LO, FUSION_HI, CYCLE_LO, CYCLE_HI, seed=7)
    opt_x, opt_y = 20.0, 70.0

    def f(x, y):
        return -((x - opt_x) / (FUSION_HI - FUSION_LO)) ** 2 \
            - ((y - opt_y) / (CYCLE_HI - CYCLE_LO)) ** 2

    try:
        pt = (ctypes.c_double * 2)()
        for _ in range(25):
            lib.horovod_tpu_bo_next(bo, pt)
            x, y = pt[0], pt[1]
            assert FUSION_LO <= x <= FUSION_HI, x
            assert CYCLE_LO <= y <= CYCLE_HI, y
            lib.horovod_tpu_bo_add(bo, pt, f(x, y))
        best_y = ctypes.c_double()
        lib.horovod_tpu_bo_best(bo, pt, ctypes.byref(best_y))
        # Within ~15% of each axis of the true optimum, and a function
        # value close to the max of 0.
        assert abs(pt[0] - opt_x) < 0.15 * (FUSION_HI - FUSION_LO), pt[0]
        assert abs(pt[1] - opt_y) < 0.15 * (CYCLE_HI - CYCLE_LO), pt[1]
        assert best_y.value > -0.05, best_y.value
    finally:
        lib.horovod_tpu_bo_destroy(bo)


def test_bayesian_optimizer_survives_many_samples():
    """100 samples (beyond kMaxSamples) on a noisy constant function:
    the Cholesky must stay finite (no NaN proposals) even with
    near-duplicate inputs."""
    lib, bo = _bo(FUSION_LO, FUSION_HI, CYCLE_LO, CYCLE_HI, seed=3)
    rng = np.random.RandomState(0)
    try:
        pt = (ctypes.c_double * 2)()
        for i in range(100):
            lib.horovod_tpu_bo_next(bo, pt)
            assert np.isfinite(pt[0]) and np.isfinite(pt[1]), (i, pt[0],
                                                              pt[1])
            assert FUSION_LO <= pt[0] <= FUSION_HI
            assert CYCLE_LO <= pt[1] <= CYCLE_HI
            lib.horovod_tpu_bo_add(bo, pt, 1.0 + 1e-3 * rng.randn())
    finally:
        lib.horovod_tpu_bo_destroy(bo)


@pytest.mark.e2e
def test_autotune_e2e(run_launcher, tmp_path):
    """A 2-rank job with autotuning live: collectives must stay correct
    while the coordinator re-tunes fusion/cycle/cache knobs under the
    running job (cross-rank agreement is implicit — a desynchronized
    cache or fusion config deadlocks negotiation and the run times
    out), the CSV log must be well-formed with >= warmup + 2 samples,
    and every sampled/final knob must lie inside the search bounds."""
    log = tmp_path / "autotune.csv"
    proc = run_launcher(2, "autotune_worker.py",
                        extra_env=dict(FAST_TUNE_ENV,
                                       HVD_TPU_AUTOTUNE_LOG=str(log)),
                        timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MISMATCH" not in proc.stdout, proc.stdout

    # Every rank reports synchronized params inside the search bounds.
    params = [json.loads(m) for m in
              re.findall(r"AUTOTUNE_PARAMS (\{.*?\})", proc.stdout)]
    assert len(params) == 2, proc.stdout
    for p in params:
        assert FUSION_LO <= p["fusion_mb"] <= FUSION_HI, p
        assert CYCLE_LO <= p["cycle_time_ms"] <= CYCLE_HI, p

    # CSV: header + >= 2 post-warmup samples, all rows in bounds. Format
    # (docs/AUTOTUNE.md): the three continuous knobs, the five
    # categorical knobs (cache, the three hierarchicals, shm_transport),
    # the score, and the row's event (sample/converged/rearm reason).
    lines = log.read_text().strip().splitlines()
    assert lines[0].startswith(
        "fusion_mb,cycle_time_ms,pipeline_chunk_kb,cache_enabled"), lines[0]
    assert "shm_transport" in lines[0], lines[0]
    rows = [line.split(",") for line in lines[1:]]
    assert len(rows) >= 2, lines
    assert any(row[9] == "converged" for row in rows), lines
    for row in rows:
        assert len(row) == 10, row
        fusion, cycle, chunk = float(row[0]), float(row[1]), float(row[2])
        assert FUSION_LO <= fusion <= FUSION_HI, row
        assert CYCLE_LO <= cycle <= CYCLE_HI, row
        assert CHUNK_LO_KB <= chunk <= CHUNK_HI_KB, row
        for cat in row[3:8]:
            assert cat in ("0", "1"), row
        assert np.isfinite(float(row[8])), row
        assert row[9], row


@pytest.mark.e2e
def test_autotune_ab_worker_symmetric_exit(run_launcher):
    """The A/B worker's broadcast-gated tune loop (examples/autotune_ab.py):
    rank 0 alone decides exit (converged / step-capped / timed out)
    and broadcasts the verdict, so every rank leaves at the SAME step
    — per-rank polling of `active` exits ranks at different collective
    counts and desynchronizes shutdown (the race the A/B experiment
    hit live). Pins: clean exit at the step cap while tuning is still
    active, identical tune_steps on the reporting rank, and a
    well-formed AB_RESULT."""
    result = run_launcher(2, "autotune_ab_worker.py",
                          extra_env={"HVD_TPU_AUTOTUNE": "1",
                                     "AB_TUNE_MAX_STEPS": "25",
                                     "AB_ITERS": "10",
                                     "AB_TENSORS": "8",
                                     "AB_ELEMS": "4096"},
                          timeout=600)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "AUTOTUNE_TIMEOUT" not in result.stdout, result.stdout
    marker = result.stdout.find("AB_RESULT ")
    assert marker >= 0, result.stdout
    # raw_decode: another rank's output can interleave after the
    # JSON object on the same line.
    res = json.JSONDecoder().raw_decode(
        result.stdout[marker + len("AB_RESULT "):])[0]
    assert res["tune_steps"] == 25, res
    assert res["steps_per_s"] > 0, res


@pytest.mark.e2e
def test_autotune_drift_rearm(run_launcher):
    """Closed loop (docs/AUTOTUNE.md): after convergence on a small
    workload, an 8x payload shift must trip the drift watch — the tuner
    re-arms (rearms_total bumps, a new epoch rides the ResponseList
    bootstrap) on EVERY rank, with rank 0 naming workload-shift as the
    reason."""
    result = run_launcher(
        2, "autotune_drift_worker.py",
        extra_env=dict(FAST_TUNE_ENV,
                       HVD_TPU_AUTOTUNE_DRIFT_WINDOW="8",
                       HVD_TPU_AUTOTUNE_DRIFT="2.0"),
        timeout=600)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "DRIFT_TIMEOUT" not in result.stdout, result.stdout
    rearmed = [json.loads(m) for m in
               re.findall(r"DRIFT_REARMED (\{.*?\})", result.stdout)]
    assert len(rearmed) == 2, result.stdout  # both ranks re-entered tuning
    assert all(r["rearms"] >= 1 for r in rearmed), rearmed
    assert all(r["epoch"] >= 1 for r in rearmed), rearmed
    assert any(r["reason"] == "workload-shift" for r in rearmed), rearmed


@pytest.mark.e2e
def test_autotune_rearm_across_elastic_resize():
    """Acceptance e2e: the tuner converges in generation 0, RE-ARMS when
    worker 1 dies (shrink 3->2), converges again under the new world
    size with different knobs, survives the regrow to 3, and step time
    recovers to the converged-regime envelope instead of sticking at
    sampling-transient pacing."""
    import statistics
    import subprocess
    import sys
    import time as _time

    from tests.conftest import clean_worker_env

    env = clean_worker_env(dict(
        FAST_TUNE_ENV,
        HVD_TPU_ELASTIC_COOLDOWN="2",
        HVD_TPU_ELASTIC_DISCOVERY_INTERVAL="0.3",
        HVD_TPU_START_TIMEOUT="30",
    ))
    result = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run.run", "-np", "3",
         "--min-np", "1", "--",
         sys.executable, os.path.join(os.path.dirname(__file__),
                                      "autotune_elastic_worker.py")],
        env=env, timeout=420, capture_output=True, text=True)
    out = result.stdout
    assert result.returncode == 0, (out, result.stderr)
    assert "worker 1 crashing now" in out

    line = re.compile(
        r"TUNE worker (\S+) gen (\d+) step (\d+) size (\d+) active (\d) "
        r"epoch (\d+) rearms (\d+) fusion ([0-9.]+) cycle ([0-9.]+) "
        r"chunk ([0-9.]+) ms ([0-9.]+)")
    rows = [dict(worker=m[0], gen=int(m[1]), step=int(m[2]),
                 size=int(m[3]), active=int(m[4]), epoch=int(m[5]),
                 rearms=int(m[6]), fusion=float(m[7]), cycle=float(m[8]),
                 chunk=float(m[9]), ms=float(m[10]))
            for m in line.findall(out)]
    gen0 = [r for r in rows if r["gen"] == 0]
    shrunk = [r for r in rows if r["gen"] >= 1 and r["size"] == 2]
    assert gen0 and shrunk, out

    # Generation 0 converged before the crash...
    gen0_converged = [r for r in gen0 if r["active"] == 0]
    assert gen0_converged, "tuner never converged in gen 0:\n" + out
    # ...and the resize RE-ARMED it: the shrunk generation starts with
    # the tuner actively sampling again.
    assert any(r["active"] == 1 for r in shrunk), \
        "tuner did not re-arm after the shrink:\n" + out
    # Post-resize the tuner converges AGAIN (the shrunk generation may
    # regrow before its pass finishes — the regrown generation re-arms
    # once more and finishes there) on knobs that differ from the
    # pre-shrink ones: each pass explores generation-salted sample
    # points, so an identical point would mean the re-tune never ran.
    shrunk_converged = [r for r in rows
                        if r["gen"] >= 1 and r["active"] == 0]
    assert shrunk_converged, "tuner never re-converged post-resize:\n" + out
    pre, post = gen0_converged[-1], shrunk_converged[-1]
    assert (abs(pre["fusion"] - post["fusion"]) > 1e-9 or
            abs(pre["cycle"] - post["cycle"]) > 1e-9 or
            abs(pre["chunk"] - post["chunk"]) > 1e-9), (pre, post)

    # The job regrew to 3 and finished on every worker.
    assert any(r["size"] == 3 and r["gen"] >= 1 for r in rows), out
    assert len(re.findall(r"tune train done", out)) == 3, out

    # Throughput recovers: converged step time after the resize stays in
    # the same envelope as generation 0's converged regime (generous 4x
    # bound — the point is it does NOT stick at sampling-transient
    # pacing, e.g. a 100ms-cycle probe).
    pre_ms = statistics.median(r["ms"] for r in gen0_converged[-5:])
    post_ms = statistics.median(r["ms"] for r in shrunk_converged[-5:])
    assert post_ms <= 4 * pre_ms + 50, (pre_ms, post_ms)


# --- hvd-top `tun` column tolerance -----------------------------------------


def _job(per_rank):
    return {"size": len(per_rank), "generation": 1,
            "per_rank": per_rank,
            "age_seconds": {r: 0.0 for r in per_rank},
            "rank_lag_seconds": [0.0] * len(per_rank)}


def test_hvd_top_tun_column_and_mixed_version_tolerance():
    """The `tun` column renders tuning posture + re-arm count, and a
    mixed-version job (rank 1's summary predates the autotune fields)
    shows '-' in the same column span without shifting anything."""
    from horovod_tpu.run import top

    new_worker = {"cycles_total": 100.0, "cycle_seconds_sum": 1.0,
                  "cache_hit_total": 5, "cache_miss_total": 5,
                  "autotune_active": 1.0, "autotune_rearms_total": 2.0}
    old_worker = {"cycles_total": 90.0, "cycle_seconds_sum": 1.0,
                  "cache_hit_total": 5, "cache_miss_total": 5}
    frame = top.render(_job({"0": new_worker, "1": old_worker}), None, 0.0,
                       "test:0")
    lines = frame.splitlines()
    rows = [ln for ln in lines if ln.strip().startswith(("0", "1"))]
    assert len(rows) == 2, frame
    header = next(ln for ln in lines if " tun" in ln)
    tun_col = header.index(" tun")
    assert "tun/2" in rows[0], frame
    assert rows[1][tun_col:tun_col + 5].strip() == "-", frame
    assert all(len(r) == len(rows[0]) for r in rows), frame
    # Converged posture with no re-arms renders plain 'cvg'.
    cvg = dict(new_worker, autotune_active=0.0, autotune_rearms_total=0.0)
    assert "cvg" in top.render(_job({"0": cvg}), None, 0.0, "t"), "cvg"
