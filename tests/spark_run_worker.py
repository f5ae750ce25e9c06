"""Orchestrator for the spark.run e2e test: runs in a CLEAN interpreter
(no prior hvd.init in this process — forked barrier children must init
from scratch), puts the fake pyspark on sys.path, and drives the REAL
`horovod_tpu.spark.run` plumbing: SparkSession.builder.getOrCreate ->
parallelize -> barrier -> mapPartitions -> collect, with each barrier
task doing a genuine multi-process rendezvous + collective.
"""

import os
import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE / "fake_pyspark"))
sys.path.insert(0, str(_HERE.parent))


def train(scale):
    """Runs inside each barrier task AFTER hvd.init(): a real allreduce
    proves the rendezvous the topology env described actually formed."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import ops

    out = ops.allreduce(np.ones(4) * (hvd.rank() + 1), "spark_e2e_ar")
    return (float(out[0]) * scale, hvd.rank(), hvd.size())


def main():
    import horovod_tpu.spark as hvd_spark

    results = hvd_spark.run(train, args=(10,), num_proc=2, verbose=1)
    # results are ordered by rank (run() sorts on the task's rank).
    assert len(results) == 2, results
    expected_sum = (1 + 2) * 10.0
    for r, (val, rank_, size_) in enumerate(results):
        assert val == expected_sum, results
        assert rank_ == r and size_ == 2, results
    print("spark run ok: %s" % (results,))
    return 0


if __name__ == "__main__":
    sys.exit(main())
