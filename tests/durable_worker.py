"""Durable-checkpoint e2e worker: deterministic quadratic training with
durable commits (docs/ELASTIC.md "Durability").

Run under the launcher with ``HVD_TPU_CKPT_DIR`` (``--ckpt-dir``) set;
``@elastic.run`` auto-enables durable commits and auto-resumes from the
newest valid manifest. Every durable commit prints a CRC32C fingerprint
of the full state, and the first line inside ``train()`` prints the
state the run STARTED from — so the kill-everything tests can assert a
relaunch resumes bitwise-identically to what was committed.

Knobs (env):
  DURABLE_TEST_TOTAL_STEPS  total optimization steps        (default 24)
  DURABLE_TEST_COMMIT_EVERY commit cadence in steps         (default 2)
  DURABLE_TEST_STEP_SLEEP   per-step sleep seconds          (default 0.1)
  DURABLE_TEST_CRASH_STEP   step at which crashers exit(31) (-1 = never)
  DURABLE_TEST_CRASH_WIDS   csv of worker ids that crash (generation 0
                            only, so restarted/resumed runs never
                            re-crash)
  DURABLE_TEST_PID_DIR      write pid.<wid> files here so a test can
                            SIGKILL the worker processes directly
"""

import os
import sys
import time

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.elastic import durable

TOTAL_STEPS = int(os.environ.get("DURABLE_TEST_TOTAL_STEPS", "24"))
COMMIT_EVERY = int(os.environ.get("DURABLE_TEST_COMMIT_EVERY", "2"))
STEP_SLEEP = float(os.environ.get("DURABLE_TEST_STEP_SLEEP", "0.1"))
CRASH_STEP = int(os.environ.get("DURABLE_TEST_CRASH_STEP", "-1"))
CRASH_WIDS = set(
    w for w in os.environ.get("DURABLE_TEST_CRASH_WIDS", "").split(",")
    if w)
LR = 0.05
TARGET = 3.0

WID = os.environ.get("HVD_TPU_WORKER_ID", "?")


def state_crc(state):
    """CRC32C over the full state bytes — bitwise identity check."""
    crc = durable.crc32c(np.ascontiguousarray(state.w).tobytes())
    return durable.crc32c(("step=%d" % state.step).encode(), crc)


@elastic.run
def train(state):
    print("worker %s start step %d crc %08x size %d"
          % (WID, state.step, state_crc(state), hvd.size()), flush=True)
    while state.step < TOTAL_STEPS:
        gen = int(os.environ.get("HVD_TPU_GENERATION", "0") or 0)
        grad_local = 2.0 * (state.w - TARGET)
        grad = np.asarray(hvd.allreduce(grad_local, "grad", average=True))
        state.w = state.w - LR * grad
        state.step += 1
        if WID in CRASH_WIDS and gen == 0 and state.step == CRASH_STEP:
            # Drain the async writer first so the LAST durable commit is
            # deterministic for the driver-restart test's exact-step
            # assertion (crash-mid-write atomicity is covered separately
            # by the SIGKILL-everything test, where the kill is external
            # and the restore may legitimately land on an older valid
            # manifest).
            if state.durable is not None:
                state.durable.flush(timeout=60)
            print("worker %s crashing now" % WID, flush=True)
            os._exit(31)
        if state.step % COMMIT_EVERY == 0:
            # Printed BEFORE the commit (the values are the same): the
            # durable manifest of a step must never be on disk before
            # its line is in the pipe, or a test that kills the job the
            # moment it sees the manifest loses the line.
            print("worker %s commit step %d crc %08x"
                  % (WID, state.step, state_crc(state)), flush=True)
            state.commit()
        time.sleep(STEP_SLEEP)
    return float(np.sum((state.w - TARGET) ** 2))


def main():
    pid_dir = os.environ.get("DURABLE_TEST_PID_DIR")
    if pid_dir:
        with open(os.path.join(pid_dir, "pid.%s" % WID), "w") as f:
            f.write(str(os.getpid()))
    state = elastic.ElasticState(w=np.zeros(4, np.float64), step=0)
    final_loss = train(state)
    if final_loss is None:  # job finished before this worker could join
        print("worker %s superseded (job already complete)" % WID,
              flush=True)
        return 0
    print("worker %s done step %d crc %08x loss %.6f"
          % (WID, state.step, state_crc(state), final_loss), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
