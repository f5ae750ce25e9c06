"""Worker for the process-group e2e suite (test_groups.py).

Modes (GROUP_MODE env):
  ops — 4 ranks: disjoint groups {0,2}/{1,3} run every collective kind
      with rank remapping, the SAME tensor name active in both groups
      concurrently (the 2-D mesh's per-column shape), plus a nontrivial
      whole-world group; asserts exact values and group metrics.
  cache — repeated steps in a 2-group job must HIT the response cache in
      both groups (fast-path cycles), and re-scoping a cached name to a
      DIFFERENT group must read INVALID -> renegotiate (membership
      change semantics, like a compression-mode change).
  wire — measures per-collective socket bytes: a model-group allreduce
      must move <= (group/world + 5%%) of the same tensor's full-world
      allreduce (summed across ranks; tests/test_groups.py asserts the ratio).
  reject — non-member submission fails immediately at enqueue; ranks
      that created the same group id with DIFFERENT member lists are
      rejected at negotiation naming the mixed membership.
"""

import os
import signal
import sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.common import ops
from horovod_tpu.common.ops import HorovodInternalError


def alarm(signum, frame):
    sys.stderr.write("watchdog fired: job deadlocked\n")
    sys.exit(3)


signal.signal(signal.SIGALRM, alarm)
signal.alarm(150)

mode = os.environ.get("GROUP_MODE", "ops")
hvd.init()
r, n = hvd.rank(), hvd.size()


def ring_bytes():
    c = hvd.metrics()["counters"]
    return c["net_ring_bytes_sent_total"]


if mode == "ops":
    assert n == 4
    g_even = hvd.new_group([0, 2])
    g_odd = hvd.new_group([1, 3])
    g_all = hvd.new_group(range(n))
    mine = g_even if r % 2 == 0 else g_odd
    members = list(mine.ranks)
    assert mine.rank() == members.index(r)
    assert mine.size() == 2

    # Same tensor NAME in two disjoint groups concurrently.
    out = ops.allreduce(np.full(7, float(r + 1), np.float32), "grad.0",
                        group=mine)
    assert np.allclose(out, sum(m + 1 for m in members)), (r, out)

    # Broadcast: root is a WORLD rank, remapped to the group ring.
    root = members[1]
    out = ops.broadcast(np.full(3, float(r), np.float32), root, "bc.0",
                        group=mine)
    assert np.allclose(out, float(root)), (r, out)

    # Allgather: blocks in group order, uneven first dims.
    out = ops.allgather(np.full((r + 1, 2), float(r), np.float32), "ag.0",
                        group=mine)
    exp = np.concatenate([np.full((m + 1, 2), float(m), np.float32)
                          for m in members])
    assert out.shape == exp.shape and np.allclose(out, exp), (r, out.shape)

    # Reduce-scatter: shard i to group member i.
    t = np.arange(10, dtype=np.float32) + r
    out = ops.reduce_scatter(t, "rs.0", group=mine)
    counts, offsets = ops.shard_partition(10, 2)
    gr = mine.rank()
    full = sum(np.arange(10, dtype=np.float32) + m for m in members)
    exp = full[offsets[gr]:offsets[gr] + counts[gr]]
    assert np.allclose(out, exp), (r, out, exp)

    # Average divides by the GROUP size.
    out = ops.allreduce(np.full(4, float(r), np.float32), "avg.0",
                        average=True, group=mine)
    assert np.allclose(out, sum(members) / 2.0), (r, out)

    # A whole-world group with a NONTRIVIAL id behaves like the world.
    out = ops.allreduce(np.ones(5, np.float32), "world.0", group=g_all)
    assert np.allclose(out, n), (r, out)

    m = hvd.metrics()
    assert m["gauges"]["groups"] == 3, m["gauges"]
    assert m["counters"]["group_tensors_total"] >= 6, m["counters"]
    if r == 0:
        # Coordinator-side group-labeled negotiation counters.
        per_group = m.get("per_group", {})
        assert per_group and all(int(v["negotiated_total"]) > 0
                                 for v in per_group.values()), per_group
    print("rank %d group ops ok" % r, flush=True)

elif mode == "cache":
    assert n == 4
    g_even = hvd.new_group([0, 2])
    g_odd = hvd.new_group([1, 3])
    mine = g_even if r % 2 == 0 else g_odd
    steps = 8
    for step in range(steps):
        out = ops.allreduce(np.full(64, float(r), np.float32), "c.t",
                            group=mine)
        assert np.allclose(out, sum(mine.ranks)), (r, step, out)
    c = hvd.metrics()["counters"]
    # Steps 2.. must ride the cached fast path in BOTH groups.
    assert c["cache_hit_total"] >= steps - 2, c
    assert c["cycles_fast_total"] >= 1, c
    hits_before = c["cache_hit_total"]

    # Membership change: the same tensor name re-scoped to a NEW group
    # id must read INVALID (erase + renegotiate), not silently reuse the
    # old group's cached response.
    # (The ranks that reach the cycle first count the invalidation; every
    # rank erases the entry in that cycle, so a later rank counts a miss.)
    g_new = hvd.new_group([0, 1, 2, 3])
    out = ops.allreduce(np.full(64, float(r), np.float32), "c.t",  # hvd-lint: disable=duplicate-collective-name
                        group=g_new)
    assert np.allclose(out, sum(range(n))), (r, out)
    c = hvd.metrics()["counters"]
    assert c["cache_hit_total"] == hits_before, c
    seen = ops.allgather(np.array([c["cache_invalid_total"]], np.int64),
                         "c.invalidated")
    assert seen.sum() >= 1, seen
    # And the new scope caches again.
    for step in range(3):
        out = ops.allreduce(np.full(64, float(r), np.float32), "c.t",  # hvd-lint: disable=duplicate-collective-name
                            group=g_new)
        assert np.allclose(out, sum(range(n))), (r, step, out)
    c = hvd.metrics()["counters"]
    assert c["cache_hit_total"] > hits_before, c
    print("rank %d group cache ok (hits=%d invalid=%d)"
          % (r, c["cache_hit_total"], c["cache_invalid_total"]), flush=True)

elif mode == "wire":
    assert n == 4
    group = hvd.new_group([0, 1])  # the "model group" of the A/B
    elems = 1 << 18  # 1 MiB f32 payload: frame headers are noise
    x = np.full(elems, float(r + 1), np.float32)

    # Warm-up builds rings and settles negotiation so the measured
    # deltas are pure collective traffic.
    ops.allreduce(x, "warm.world")
    if r in group.ranks:
        ops.allreduce(x, "warm.grp", group=group)

    b0 = ring_bytes()
    ops.allreduce(x, "wire.world")
    b1 = ring_bytes()
    if r in group.ranks:
        ops.allreduce(x, "wire.grp", group=group)
    b2 = ring_bytes()
    print("rank %d wire world=%d group=%d" % (r, b1 - b0, b2 - b1),
          flush=True)

elif mode == "reject":
    assert n == 2
    g0 = hvd.new_group([0])
    # Non-member submission fails at enqueue, naming rank and group.
    if r == 1:
        try:
            ops.allreduce(np.ones(3, np.float32), "nm.0", group=g0)  # hvd-lint: disable=verify-non-member-group-call
            raise AssertionError("non-member allreduce did not fail")
        except HorovodInternalError as e:
            assert "not a member" in str(e), e
    # Unknown group id.
    try:
        ops.allreduce(np.ones(3, np.float32), "ug.0", group=999)
        raise AssertionError("unknown-group allreduce did not fail")
    except HorovodInternalError as e:
        assert "unknown process group" in str(e), e
    # Mixed membership: both ranks create group id 2, with DIFFERENT
    # member lists (a new_group discipline violation). Rank 1's
    # announcement carries a digest that disagrees with the
    # coordinator's registry and is rejected by name.
    # id 2 everywhere; members differ!
    # hvd-lint: disable=verify-divergent-schedule
    g2 = hvd.new_group([r])
    if r == 0:
        # The coordinator's registry says {0}. Depending on announcement
        # order, rank 0's own submission either completes alone (its
        # announcement formed a fresh pending entry) or is failed
        # together with rank 1's colliding one — either way the error
        # NAMES the mixed membership; a hang is the only wrong outcome.
        try:
            out = ops.allreduce(np.ones(3, np.float32), "mm.0", group=g2)
            assert np.allclose(out, 1.0), out
        except HorovodInternalError as e:
            assert "Mixed membership" in str(e), e
    else:
        try:
            ops.allreduce(np.ones(3, np.float32), "mm.0", group=g2)  # hvd-lint: disable=duplicate-collective-name
            raise AssertionError("mixed-membership allreduce did not fail")
        except HorovodInternalError as e:
            assert "Mixed membership" in str(e) or "not a member" in \
                str(e), e
    print("rank %d group reject ok" % r, flush=True)

elif mode == "unknown":
    # Registration-order divergence: rank 1 creates (and uses) a group
    # the COORDINATOR never registered. The late-registration sweep can
    # never resolve it, so past the grace window the divergence detector
    # must error naming the unregistered group — not hang.
    assert n == 2
    import time
    if r == 1:
        g = hvd.new_group([1])  # rank 0 skips this call — the bug
        try:
            ops.allreduce(np.ones(4, np.float32), "ur.0", group=g)
            raise AssertionError("unregistered-group allreduce did not "
                                 "fail")
        except HorovodInternalError as e:
            assert "never registered that group" in str(e), e
            print("rank %d unregistered group reported" % r, flush=True)
    else:
        time.sleep(8)  # outlive rank 1's grace window
        print("rank %d coordinator survived" % r, flush=True)

else:
    raise SystemExit("unknown GROUP_MODE %r" % mode)
