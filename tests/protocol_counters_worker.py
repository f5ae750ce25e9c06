"""Protocol-counter worker: runs repeated same-name collectives and
prints this rank's control-plane accounting as one JSON line, so the
test can compare the response-cache fast path
against full negotiation at the PROTOCOL level — bytes and cycle
kinds, independent of wall clock (the fast path's design goal;
reference: response_cache.cc:308-409).

Env: HVD_TPU_CACHE_CAPACITY=0 disables the cache (full round trip per
cycle); default leaves it on.
"""

import json
import sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.common import ops
from horovod_tpu.common.basics import get_basics


def main():
    hvd.init()
    basics = get_basics()
    r = hvd.rank()

    # A deliberately long tensor name: the uncached path serializes one
    # Request (name + shape + dtype + op) per op per worker per cycle,
    # so name length is visible in bytes/op; the cached path sends a
    # fixed-width bit vector regardless.
    name = "protocol_counters.the_quick_brown_fox_gradient_block_%04d"

    # Warmup: populates the response cache (first sight of a name is
    # always a full negotiation) and lets autotune warmup cycles pass.
    for i in range(8):
        ops.allreduce(np.ones(16, np.float32), name % 0)  # hvd-lint: disable=loop-auto-name

    basics.protocol_counters_reset()
    n_ops = 64
    for i in range(n_ops):
        ops.allreduce(np.ones(16, np.float32), name % 0)  # hvd-lint: disable=loop-auto-name
    counters = basics.protocol_counters()
    counters["ops"] = n_ops
    counters["rank"] = r
    print("COUNTERS %s" % json.dumps(counters))
    return 0


if __name__ == "__main__":
    sys.exit(main())
