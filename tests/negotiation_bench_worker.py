"""Control-plane negotiation microbenchmark worker: times synchronous tiny
allreduces, whose cost is dominated by the per-cycle coordinator negotiation
(gather/bcast or the cached bit-sync), not data movement. Run with
HVD_TPU_CYCLE_TIME=0 so the cycle pacing sleep doesn't mask the control
plane. Prints `NEGOTIATION_US_PER_OP <us>` on rank 0."""

import sys
import time

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.common import ops

ITERS = 200
WARMUP = 20  # populates the response cache


def main():
    hvd.init()
    r = hvd.rank()
    # Zero-element tensor: the negotiation/cycle machinery runs in full but
    # the ring data phase is skipped, isolating control-plane latency (a
    # payload allreduce would add the ring's inherent Theta(n) hop latency).
    x = np.zeros(0, dtype=np.float32)

    def step():
        ops.synchronize(ops.allreduce_async(x, "nb"))

    for _ in range(WARMUP):
        step()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        step()
    dt = time.perf_counter() - t0
    if r == 0:
        print("NEGOTIATION_US_PER_OP %.1f" % (dt / ITERS * 1e6))
    print("rank %d done" % r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
