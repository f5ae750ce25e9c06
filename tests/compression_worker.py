"""Self-verifying host-plane compression worker (docs/COMPRESSION.md),
run under the launcher with N >= 2 ranks.

Checks, on every rank:
  * allreduce correctness under none/bf16/int8 within each codec's
    error bound, with results bitwise-identical across ranks (the
    allgather leg forwards encoded chunks verbatim);
  * compressed modes actually shrink the data-ring wire bytes (socket-
    layer net_ring_bytes counters, headers included);
  * fusion still engages under compression (several small same-mode
    tensors share one ring pass);
  * a mode change on a cached name invalidates the response-cache entry
    and renegotiates (cache-key semantics);
  * with compression off the negotiation/result path is bitwise
    identical to an uncompressed build (none == plain allreduce).

Run: python -m horovod_tpu.run.run -np 2 -- python tests/compression_worker.py
"""

import sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.common import ops


def counters():
    return hvd.metrics()["counters"]


def ring_bytes_for(mode, elems, r, n):
    """Measures data-ring bytes one `elems`-element f32 allreduce moves
    under `mode` (fresh tensor name each call; cycle includes both ring
    legs)."""
    x = (np.arange(elems, dtype=np.float32) / 7.0) + r
    before = counters()["net_ring_bytes_sent_total"]
    out = ops.allreduce(x, "wire.%s.%d" % (mode, elems), compression=mode)
    after = counters()["net_ring_bytes_sent_total"]
    want = (np.arange(elems, dtype=np.float32) / 7.0) * n + sum(range(n))
    tol = {"none": 1e-5, "bf16": 2e-2, "int8": 4e-2}[mode]
    err = np.max(np.abs(out - want)) / max(np.max(np.abs(want)), 1e-9)
    assert err < tol, (mode, err)
    return after - before


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n >= 2
    rng = np.random.RandomState(1234)
    base = rng.randn(8192).astype(np.float32) * 3.0

    # Correctness + cross-rank bitwise identity per mode. The reduced
    # value is allgathered (uncompressed) and every rank checks every
    # rank's copy is byte-identical to its own.
    for mode, tol in (("none", 1e-5), ("bf16", 2e-2), ("int8", 4e-2)):
        x = base + r
        out = ops.allreduce(x, "corr.%s" % mode, compression=mode)
        want = base * n + sum(range(n))
        err = np.max(np.abs(out - want)) / np.max(np.abs(want))
        assert err < tol, (mode, err)
        gathered = ops.allgather(out[None, :], "corr.g.%s" % mode)
        for rr in range(n):
            assert np.array_equal(gathered[rr], out), \
                "mode %s: rank %d result differs from rank %d" % (mode, rr, r)

    # Wire-byte A/B at the socket layer: bf16 >= 1.9x, int8 >= 3x off
    # the ring for a payload large enough that headers don't dominate.
    elems = 256 * 1024
    none_b = ring_bytes_for("none", elems, r, n)
    bf16_b = ring_bytes_for("bf16", elems, r, n)
    int8_b = ring_bytes_for("int8", elems, r, n)
    assert none_b / bf16_b >= 1.9, (none_b, bf16_b)
    assert none_b / int8_b >= 3.0, (none_b, int8_b)
    print("rank %d wire bytes none=%d bf16=%d (%.2fx) int8=%d (%.2fx)"
          % (r, none_b, bf16_b, none_b / bf16_b, int8_b, none_b / int8_b),
          flush=True)

    # Fusion under compression: enqueue several small same-mode tensors
    # in one burst; the fused-tensor counter must grow (they shared a
    # response and one compressed ring pass).
    fused_before = counters()["fused_tensors_total"]
    handles = [ops.allreduce_async(np.full(64, float(r + 1), np.float32),
                                   "fuse.%d" % i, compression="int8")
               for i in range(6)]
    for h in handles:
        out = ops.synchronize(h)
        assert np.allclose(out, sum(range(1, n + 1)), atol=0.1), out
    fused_after = counters()["fused_tensors_total"]
    assert fused_after > fused_before, (fused_before, fused_after)

    # Cache-key semantics: warm a name into the cache, then change only
    # the mode — must invalidate (miss) and renegotiate, not reuse.
    x = np.ones(100, np.float32)
    for _ in range(3):
        ops.allreduce(x, "ck", compression="none")  # hvd-lint: disable=verify-mixed-modes
    # The ranks whose call reaches the cycle first read the stale entry
    # (INVALID); the invalid bit is OR-synced and every rank erases the
    # entry in that same cycle, so a rank that enqueues a cycle later
    # finds no entry (a MISS). No rank may HIT, and one must invalidate.
    before = counters()
    out = ops.allreduce(x, "ck", compression="bf16")  # hvd-lint: disable=duplicate-collective-name
    assert np.allclose(out, n), out
    after = counters()
    hit, invalid, miss = (after[k] - before[k] for k in (
        "cache_hit_total", "cache_invalid_total", "cache_miss_total"))
    assert hit == 0 and invalid + miss >= 1, (hit, invalid, miss)
    seen = ops.allgather(np.array([invalid], np.int64), "ck.invalidated")
    assert seen.sum() >= 1, seen

    # Mode accounting: per-mode allreduce counters moved.
    c = counters()
    assert c["allreduce_bf16_total"] >= 2, c["allreduce_bf16_total"]
    assert c["allreduce_int8_total"] >= 2, c["allreduce_int8_total"]
    assert c["compression_bytes_in_total"] > \
        c["compression_bytes_out_total"] > 0

    print("rank %d: compression worker passed" % r, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
