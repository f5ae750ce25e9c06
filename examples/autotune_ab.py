"""Defaults-vs-autotuned A/B of the host plane's runtime knobs.

Three 4-rank localhost runs of the same gradient-bucket workload
(`tests/autotune_ab_worker.py`):

  1. defaults   — fusion 64 MB / cycle 5 ms / cache on, no tuning
  2. autotune   — HVD_TPU_AUTOTUNE=1 (+ CSV log): warmup, Bayesian
                  sampling over (fusion, cycle) x categorical combos,
                  convergence; measurement happens AFTER the tuner
                  fixes the best knobs (reference flow:
                  horovod/common/parameter_manager.cc:27-30,136-160)
  3. tuned-env  — converged knobs re-applied via HVD_TPU_FUSION_
                  THRESHOLD / HVD_TPU_CYCLE_TIME on a fresh run
                  (tuning value clean of any in-process residue)

Writes autotune_ab.json and autotune_ab_samples.csv into the working
directory (runs, converged knobs, CSV sample log) and prints a summary
table. CPU-plane only — safe to run without TPU access, but it IS
load-sensitive: run it alone. Its timings are the host plane's on the
CPU, never device metrics.

Usage: python examples/autotune_ab.py [--np 4] [--iters 80]
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_once(np_, extra_env, timeout=600):
    from horovod_tpu.run.util import cpu_worker_env
    env = cpu_worker_env(extra_env=extra_env, repo_root=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run.run", "-np", str(np_),
         "--", sys.executable,
         os.path.join(REPO, "tests", "autotune_ab_worker.py")],
        env=env, timeout=timeout, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError("run failed:\n%s\n%s" %
                           (proc.stdout[-3000:], proc.stderr[-2000:]))
    # The launcher multiplexes rank stdout; the marker can land
    # mid-line after another rank's unflushed tail.
    marker = proc.stdout.find("AB_RESULT ")
    if marker < 0:
        raise RuntimeError("no AB_RESULT in output:\n%s"
                           % proc.stdout[-3000:])
    return json.JSONDecoder().raw_decode(
        proc.stdout[marker + len("AB_RESULT "):])[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, default=4)
    ap.add_argument("--iters", type=int, default=80)
    ap.add_argument("--tensors", type=int, default=48)
    ap.add_argument("--elems", type=int, default=32768)
    ap.add_argument("--out", default="autotune_ab.json")
    args = ap.parse_args()

    base = {"AB_ITERS": str(args.iters), "AB_TENSORS": str(args.tensors),
            "AB_ELEMS": str(args.elems)}
    log_path = os.path.abspath("autotune_ab_samples.csv")

    print("== defaults ==", file=sys.stderr)
    defaults = run_once(args.np, dict(base))

    print("== autotune ==", file=sys.stderr)
    tuned = run_once(args.np, dict(
        base, HVD_TPU_AUTOTUNE="1", HVD_TPU_AUTOTUNE_LOG=log_path),
        timeout=900)
    p = tuned["params"]

    print("== tuned knobs re-applied via env ==", file=sys.stderr)
    tuned_env = run_once(args.np, dict(
        base,
        HVD_TPU_FUSION_THRESHOLD=str(int(p["fusion_mb"] * 1024 * 1024)),
        HVD_TPU_CYCLE_TIME=str(p["cycle_time_ms"]),
        HVD_TPU_CACHE_CAPACITY=("1024" if p["cache_enabled"] else "0")))

    samples = []
    if os.path.exists(log_path):
        lines = open(log_path).read().strip().splitlines()
        samples = lines[1:]  # header first

    out = {
        "workload": {"np": args.np, "tensors_per_step": args.tensors,
                     "bytes_per_tensor": args.elems * 4,
                     "mb_per_step": args.tensors * args.elems * 4 / 1e6,
                     "measure_iters": args.iters},
        "defaults": defaults,
        "autotuned": tuned,
        "tuned_env_replay": tuned_env,
        "converged": p,
        "speedup_tuned_vs_defaults": round(
            tuned["steps_per_s"] / defaults["steps_per_s"], 3),
        "speedup_tuned_env_vs_defaults": round(
            tuned_env["steps_per_s"] / defaults["steps_per_s"], 3),
        "csv_samples": samples,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in
                      ("defaults", "autotuned", "tuned_env_replay",
                       "converged", "speedup_tuned_vs_defaults",
                       "speedup_tuned_env_vs_defaults")}, indent=1))
    print("wrote %s (%d CSV samples)" % (args.out, len(samples)),
          file=sys.stderr)


if __name__ == "__main__":
    main()
