"""Durable elastic checkpoints (ISSUE 5 tentpole; docs/ELASTIC.md
"Durability").

Unit layer: manifest/shard round trip, CRC validation, torn-write and
bit-flip fallback to the newest VALID manifest, ENOSPC retry/degrade
(training never crashes on a storage fault), retention, stale-tmp
pruning, fault-spec grammar + determinism, and the pure-Python CRC32C
fallback's bit-parity with the native export.

E2E layer (``e2e`` marker, launcher-driven): SIGKILL every worker AND
the driver mid-training, relaunch, and training resumes from the last
durable commit with bitwise-identical state (CRC32C over the full state
bytes) — plus a shrink-resume variant at a smaller world size, a chaos
run with injected storage faults, and the driver's
``--restart-from-ckpt`` full-job restart when the world falls below
``--min-np``.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu.elastic import durable
from horovod_tpu.elastic.durable import (CkptFaultInjector,
                                         DurableCheckpointer,
                                         MANIFEST_NAME, apply_retention,
                                         last_durable_step,
                                         latest_valid_manifest,
                                         list_checkpoints,
                                         prune_stale_tmp,
                                         prune_unrestorable,
                                         validate_manifest)
from horovod_tpu.elastic.state import ElasticState

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_state(value=0.0, step=0):
    return ElasticState(w=np.full(8, value, np.float64), step=step,
                        nested={"a": np.arange(3.0), "b": [1, 2.5]})


def write_ckpt(directory, step, value=1.0, world_size=1):
    """Synchronously writes one complete checkpoint at `step` (all
    shards from this process) and returns the state that was saved."""
    state = make_state(value, step)
    ckpts = [DurableCheckpointer(directory, rank=r,
                                 world_size=world_size)
             for r in range(world_size)]
    state.save()
    # Enqueue ALL ranks before flushing any: rank 0's publisher blocks
    # until every sibling shard exists (exactly like a real job, where
    # the rank writers run concurrently).
    for ck in ckpts:
        ck.maybe_enqueue(state._committed, step)
    for ck in ckpts:
        assert ck.flush(timeout=60)
    return state


# ---------------------------------------------------------------------------
# CRC32C parity

def test_py_crc32c_known_answer_and_native_parity():
    # The iSCSI/RFC 3720 check value.
    assert durable._py_crc32c(b"123456789") == 0xE3069283
    # Incremental chaining must compose to the one-shot value.
    assert durable._py_crc32c(
        b"6789", durable._py_crc32c(b"12345")) == 0xE3069283
    from horovod_tpu.common.basics import get_basics
    native = get_basics().crc32c
    for blob in (b"", b"\x00" * 33, os.urandom(257), b"horovod_tpu"):
        assert native(blob) == durable._py_crc32c(blob), blob


# ---------------------------------------------------------------------------
# Manifest round trip + sharding

def test_roundtrip_single_rank(tmp_path):
    d = str(tmp_path)
    saved = write_ckpt(d, step=7, value=4.25)
    manifest, path = latest_valid_manifest(d)
    assert manifest is not None
    assert manifest["step"] == 7
    assert manifest["world_size"] == 1
    assert len(manifest["shards"]) == 1

    fresh = make_state()
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    assert ck.restore_into(fresh) == 7
    assert np.array_equal(fresh.w, saved.w)
    assert fresh.step == 7
    assert fresh.nested["b"] == [1, 2.5]


def test_sharded_write_and_resharded_restore(tmp_path):
    """Two ranks each write only their shard; a single restoring rank
    (different world size) reads them all — the re-sharding path."""
    d = str(tmp_path)
    saved = write_ckpt(d, step=10, value=-2.5, world_size=2)
    manifest, path = latest_valid_manifest(d)
    assert manifest is not None and manifest["world_size"] == 2
    assert len(manifest["shards"]) == 2
    # Each shard holds a strict subset of the leaves.
    leaves = durable.load_leaves(manifest, path)
    import pickle
    for shard in manifest["shards"]:
        with open(os.path.join(path, shard["file"]), "rb") as f:
            part = pickle.loads(f.read())
        assert 0 < len(part) < len(leaves)

    fresh = make_state()
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    assert ck.restore_into(fresh) == 10
    assert np.array_equal(fresh.w, saved.w)
    assert np.array_equal(fresh.nested["a"], np.arange(3.0))


def test_structural_mismatch_is_rejected(tmp_path):
    d = str(tmp_path)
    write_ckpt(d, step=3)
    other = ElasticState(q=np.zeros(2), step=0)  # different attributes
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    assert ck.restore_into(other) is None  # warned, not raised
    assert np.array_equal(other.q, np.zeros(2))


def test_structural_mismatch_falls_back_to_matching_older(tmp_path):
    """A foreign-structure checkpoint as the NEWEST entry (another job
    sharing the dir, or a briefly-changed state registration) must not
    shadow an older checkpoint that matches this state exactly."""
    d = str(tmp_path)
    saved = write_ckpt(d, step=3, value=7.0)  # matches make_state
    foreign = ElasticState(qq=np.ones(4), step=9)
    ck_f = DurableCheckpointer(d, rank=0, world_size=1)
    foreign.save()
    ck_f.maybe_enqueue(foreign._committed, 9)
    assert ck_f.flush(timeout=60)
    assert latest_valid_manifest(d)[0]["step"] == 9  # newest is foreign

    fresh = make_state()
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    assert ck.restore_into(fresh) == 3  # fell back past the mismatch
    assert np.array_equal(fresh.w, saved.w)


def test_sticky_snapshots_guarantee_durable_progress(tmp_path,
                                                     monkeypatch):
    """The deterministic 1-in-K sticky slot: under storage far slower
    than the commit cadence, sticky steps are never displaced by newer
    non-sticky snapshots (every rank writes them — the cross-rank
    convergence anchor), while the newest snapshot still lands via the
    second slot."""
    # Which snapshots land is the subject here, not which ones retention
    # keeps: on a fast host four of the nine land (0, 3, 6, 8) and the
    # default keep=3 would delete the first sticky one again.
    monkeypatch.setenv("HVD_TPU_CKPT_KEEP", "16")
    d = str(tmp_path)
    state = make_state()
    ck = DurableCheckpointer(
        d, rank=0, world_size=1,
        fault_spec="op=shard,prob=1.0,action=slowfsync,"
                   "delay_ms=250,count=-1")
    ck._sticky_every = 3  # due commits 0, 3, 6 are sticky
    state._durable = ck
    for step in range(9):
        state.step = step
        state.commit()  # never blocks
    assert ck.flush(timeout=60)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert 0 in steps                  # first commit (sticky) landed
    assert steps[-1] == 8              # newest snapshot still wins
    assert 3 in steps or 6 in steps    # a mid-run sticky anchor landed


def test_every_n_commits_cadence(tmp_path):
    d = str(tmp_path)
    state = make_state()
    ck = DurableCheckpointer(d, every_n_commits=3, rank=0, world_size=1)
    state._durable = ck
    for step in range(7):
        state.step = step
        state.commit()
        # Flush each commit so the latest-wins pending slot (which may
        # otherwise skip an intermediate due snapshot when commits
        # outpace storage — by design) doesn't blur the cadence.
        assert ck.flush(timeout=60)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert steps == [0, 3, 6]  # commits 0, 3, 6 of 0..6


def test_off_stride_commit_cadence_still_durable(tmp_path):
    """A commit cadence whose step values never hit a stride multiple
    (steps 3, 8, 13, ... with every_n_commits=10) must still produce
    durable checkpoints: the due rule fires on the first commit in each
    stride-sized step window, not on `step % stride == 0`."""
    d = str(tmp_path)
    state = make_state()
    ck = DurableCheckpointer(d, every_n_commits=10, rank=0,
                             world_size=1)
    state._durable = ck
    for step in (3, 8, 13, 18, 23):
        state.step = step
        state.commit()
        assert ck.flush(timeout=60)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert steps == [3, 13, 23]


def test_storage_slower_than_commits_skips_to_newest(tmp_path):
    """When storage can't keep up, intermediate due snapshots are
    REPLACED by newer ones (never queued unboundedly) and the newest
    commit always lands."""
    d = str(tmp_path)
    state = make_state()
    ck = DurableCheckpointer(
        d, rank=0, world_size=1,
        fault_spec="op=shard,prob=1.0,action=slowfsync,"
                   "delay_ms=300,count=-1")
    state._durable = ck
    for step in range(5):
        state.step = step
        state.commit()  # never blocks, even at 300ms/write
    assert ck.flush(timeout=60)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert steps[-1] == 4            # the newest commit is durable
    assert len(steps) < 5            # and some intermediates skipped


# ---------------------------------------------------------------------------
# Torn-write / bit-flip fallback (the acceptance property)

def test_fallback_skips_torn_shard(tmp_path):
    d = str(tmp_path)
    good = write_ckpt(d, step=5, value=1.0)
    write_ckpt(d, step=9, value=9.0)
    # Tear the NEWEST checkpoint's shard after the fact (as a crash
    # mid-write on a non-atomic store would): truncate to half.
    step9 = [p for s, g, p in list_checkpoints(d) if s == 9][0]
    shard = [n for n in os.listdir(step9) if n.startswith("shard-")][0]
    spath = os.path.join(step9, shard)
    data = open(spath, "rb").read()
    with open(spath, "wb") as f:
        f.write(data[:len(data) // 2])
    assert validate_manifest(step9) is None
    manifest, _ = latest_valid_manifest(d)
    assert manifest["step"] == 5  # silently fell back
    fresh = make_state()
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    assert ck.restore_into(fresh) == 5
    assert np.array_equal(fresh.w, good.w)


def test_fallback_skips_bitflipped_shard(tmp_path):
    d = str(tmp_path)
    write_ckpt(d, step=2, value=1.0)
    write_ckpt(d, step=4, value=4.0)
    step4 = [p for s, g, p in list_checkpoints(d) if s == 4][0]
    shard = [n for n in os.listdir(step4) if n.startswith("shard-")][0]
    spath = os.path.join(step4, shard)
    data = bytearray(open(spath, "rb").read())
    data[len(data) // 3] ^= 0x01  # a single flipped bit
    with open(spath, "wb") as f:
        f.write(bytes(data))
    manifest, _ = latest_valid_manifest(d)
    assert manifest["step"] == 2


def test_fallback_skips_torn_manifest(tmp_path):
    d = str(tmp_path)
    write_ckpt(d, step=1, value=1.0)
    write_ckpt(d, step=6, value=6.0)
    step6 = [p for s, g, p in list_checkpoints(d) if s == 6][0]
    mpath = os.path.join(step6, MANIFEST_NAME)
    raw = open(mpath, "rb").read()
    with open(mpath, "wb") as f:
        f.write(raw[:len(raw) // 2])  # torn json
    manifest, _ = latest_valid_manifest(d)
    assert manifest["step"] == 1
    # A checkpoint dir with no manifest at all is also just skipped.
    os.remove(mpath)
    manifest, _ = latest_valid_manifest(d)
    assert manifest["step"] == 1


def test_injected_faults_produce_invalid_checkpoints(tmp_path):
    """The injector's torn/bitflip writes must be exactly the failures
    the validator rejects — proving detector and fault model agree."""
    d = str(tmp_path)
    state = make_state(1.0, 0)
    state.save()
    for step, spec in ((1, "op=shard,write=0,action=bitflip"),
                       (2, "op=shard,write=0,action=torn"),
                       (3, "op=manifest,write=0,action=torn")):
        ck = DurableCheckpointer(d, rank=0, world_size=1,
                                 fault_spec=spec)
        state.step = step
        ck.maybe_enqueue(state._committed, step)
        assert ck.flush(timeout=60)
        assert ck._injector.fires == 1
    # Every one of the three is invalid; nothing valid exists at all.
    assert all(validate_manifest(p) is None
               for _, _, p in list_checkpoints(d))
    assert latest_valid_manifest(d) == (None, None)
    # A clean write after the carnage is found immediately.
    write_ckpt(d, step=4, value=4.0)
    manifest, _ = latest_valid_manifest(d)
    assert manifest["step"] == 4


def test_enospc_degrades_to_warning_never_raises(tmp_path, capsys):
    """A persistently failing store exhausts the capped-backoff retries
    and degrades: the commit path never sees an exception, and the next
    healthy write succeeds."""
    d = str(tmp_path)
    state = make_state(1.0, 0)
    # Every attempt (first + 3 retries) hits ENOSPC.
    ck = DurableCheckpointer(d, rank=0, world_size=1,
                             fault_spec="op=shard,prob=1.0,"
                                        "action=enospc,count=-1")
    ck._retries = 2
    state.save()
    ck.maybe_enqueue(state._committed, 1)  # must not raise
    assert ck.flush(timeout=60)
    assert latest_valid_manifest(d) == (None, None)
    assert ck.last_durable_step == -1
    err = capsys.readouterr().err
    assert "FAILED after 3 attempts" in err
    # Storage recovers: the next durable commit lands.
    ck2 = DurableCheckpointer(d, rank=0, world_size=1)
    state.step = 2
    state.save()
    ck2.maybe_enqueue(state._committed, 2)
    assert ck2.flush(timeout=60)
    assert latest_valid_manifest(d)[0]["step"] == 2


class _Unpicklable:
    """deep-copyable (so commit() succeeds) but unpicklable (so the
    durable writer's serialization fails deterministically)."""

    def __deepcopy__(self, memo):
        return self

    def __reduce__(self):
        raise TypeError("deliberately unpicklable")


def test_unpicklable_state_degrades_and_writer_survives(tmp_path,
                                                        capsys):
    """A non-storage writer failure (unpicklable leaf) must degrade
    like a storage one — warning + failure metric — and must NOT kill
    the writer thread: later healthy snapshots still land."""
    d = str(tmp_path)
    bad = ElasticState(w=np.zeros(2), step=0, extra=_Unpicklable())
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    bad._durable = ck
    bad.commit()  # must not raise
    assert ck.flush(timeout=60)
    assert latest_valid_manifest(d) == (None, None)
    assert "FAILED" in capsys.readouterr().err
    # Same checkpointer, now-picklable state: the thread is still alive.
    good = make_state(3.0, 4)
    good.save()
    ck.maybe_enqueue(good._committed, 4)
    assert ck.flush(timeout=60)
    assert latest_valid_manifest(d)[0]["step"] == 4


def test_auto_resume_in_run_wrapper(tmp_path, monkeypatch):
    """@elastic.run auto-enables durability from HVD_TPU_CKPT_DIR and
    restores the newest valid manifest before entering the function."""
    import horovod_tpu as hvd
    from horovod_tpu import elastic

    d = str(tmp_path)
    saved = write_ckpt(d, step=5, value=2.5)
    monkeypatch.setenv("HVD_TPU_CKPT_DIR", d)
    hvd.init()
    state = make_state()

    @elastic.run
    def train(st):
        return st.step

    assert train(state) == 5
    assert np.array_equal(state.w, saved.w)
    assert state._durable is not None  # auto-enabled


def test_prune_unrestorable_removes_crashed_leftovers(tmp_path):
    d = str(tmp_path)
    write_ckpt(d, step=3)
    # A crashed run renamed a shard but never published the manifest.
    orphan = os.path.join(d, "ckpt-%012d-g0" % 7)
    os.makedirs(orphan)
    payload = b"stale trajectory"
    name = "shard-00000-of-00001.%08x.%d.bin" % (durable.crc32c(payload),
                                                 len(payload))
    with open(os.path.join(orphan, name), "wb") as f:
        f.write(payload)
    assert prune_unrestorable(d) == ["ckpt-000000000007-g0"]
    # The valid checkpoint survives.
    assert latest_valid_manifest(d)[0]["step"] == 3


def test_publisher_refuses_ambiguous_duplicate_shards(tmp_path, capsys):
    """Two same-rank shards with different content in one checkpoint
    dir (a stale leftover colliding with a fresh write) must abandon
    the manifest — publishing would mix trajectories with every CRC
    valid."""
    import pickle

    d = str(tmp_path)
    ckdir = os.path.join(d, durable._ckpt_dirname(5, 0))
    os.makedirs(ckdir)
    stale = pickle.dumps({"stale": True})
    name = durable._shard_name(0, 1, durable.crc32c(stale), len(stale))
    with open(os.path.join(ckdir, name), "wb") as f:
        f.write(stale)

    state = make_state(1.0, 5)
    ck = DurableCheckpointer(d, rank=0, world_size=1)
    state.save()
    ck.maybe_enqueue(state._committed, 5)
    assert ck.flush(timeout=60)
    assert "ambiguous duplicate shard" in capsys.readouterr().err
    assert validate_manifest(ckdir) is None  # no manifest published


# ---------------------------------------------------------------------------
# Hygiene: tmp pruning + retention

def test_prune_stale_tmp(tmp_path):
    d = str(tmp_path)
    write_ckpt(d, step=1)
    ckpt_dir = list_checkpoints(d)[0][2]
    for name in ("shard-00001-of-00002.deadbeef.12.bin.tmp",
                 MANIFEST_NAME + ".tmp"):
        with open(os.path.join(ckpt_dir, name), "w") as f:
            f.write("partial")
    assert prune_stale_tmp(d) == 2
    assert not any(n.endswith(".tmp") for n in os.listdir(ckpt_dir))
    assert validate_manifest(ckpt_dir) is not None  # untouched


def test_retention_keeps_last_k_valid(tmp_path, monkeypatch):
    # High keep while writing (the publisher applies retention itself),
    # then tighten and apply.
    monkeypatch.setenv("HVD_TPU_CKPT_KEEP", "50")
    d = str(tmp_path)
    for step in range(6):
        write_ckpt(d, step=step, value=float(step))
    monkeypatch.setenv("HVD_TPU_CKPT_KEEP", "2")
    removed = apply_retention(d)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert steps == [4, 5]
    assert len(removed) == 4
    # An abandoned invalid dir OLDER than the kept set is swept too.
    os.makedirs(os.path.join(d, "ckpt-%012d-g0" % 1))
    apply_retention(d)
    assert sorted(s for s, g, p in list_checkpoints(d)) == [4, 5]


def test_retention_runs_automatically_after_publish(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("HVD_TPU_CKPT_KEEP", "3")
    d = str(tmp_path)
    for step in range(5):
        write_ckpt(d, step=step)
    steps = sorted(s for s, g, p in list_checkpoints(d))
    assert steps == [2, 3, 4]  # publisher applied retention itself


def test_abandoned_publish_does_not_claim_durability(tmp_path, capsys):
    """Rank 0 whose manifest wait times out (a sibling shard never
    appeared) must NOT advance last_durable_step or the write counter —
    the step is unrestorable and the operator report must not name it
    as a recovery point."""
    d = str(tmp_path)
    state = make_state(1.0, 5)
    state.save()
    ck = DurableCheckpointer(d, rank=0, world_size=2,
                             publish_timeout=0.3)
    ck.maybe_enqueue(state._committed, 5)
    assert ck.flush(timeout=60)
    assert "abandoning manifest" in capsys.readouterr().err
    assert ck.last_durable_step == -1
    assert last_durable_step(d) == (None, None)


def test_last_durable_step_helper(tmp_path):
    d = str(tmp_path)
    assert last_durable_step(d) == (None, None)
    write_ckpt(d, step=11)
    step, path = last_durable_step(d)
    assert step == 11 and path is not None


# ---------------------------------------------------------------------------
# Fault-spec grammar

def test_fault_spec_parse_and_determinism():
    spec = ("seed=7;op=shard,prob=0.5,action=bitflip,count=-1;"
            "op=manifest,write=1,action=torn")
    a = CkptFaultInjector(spec, rank=1)
    b = CkptFaultInjector(spec, rank=1)
    seq_a = [a.on_write("shard")[0] for _ in range(32)]
    seq_b = [b.on_write("shard")[0] for _ in range(32)]
    assert seq_a == seq_b  # seeded: identical replay
    assert any(s == "bitflip" for s in seq_a)
    assert any(s is None for s in seq_a)
    # Different seed -> different sequence (32 coin flips: ~certain).
    c = CkptFaultInjector(spec.replace("seed=7", "seed=8"), rank=1)
    assert [c.on_write("shard")[0] for _ in range(32)] != seq_a
    # write= rules fire exactly at the Nth matching write, once.
    d = CkptFaultInjector(spec, rank=1)
    assert d.on_write("manifest") == (None, 0)
    assert d.on_write("manifest")[0] == "torn"
    assert d.on_write("manifest") == (None, 0)
    # rank filter: rules for rank 0 never fire on rank 1.
    e = CkptFaultInjector("rank=0,op=shard,write=0,action=torn", rank=1)
    assert e.on_write("shard") == (None, 0)


def test_fault_spec_rejects_garbage():
    for bad in ("op=shard,action=explode", "op=nope,action=torn",
                "op=shard", "op=shard,wat=1,action=torn"):
        with pytest.raises(ValueError):
            CkptFaultInjector(bad, rank=0)


# ---------------------------------------------------------------------------
# E2E: kill EVERYTHING, relaunch, resume bitwise-identically

COMMIT_LINE = re.compile(r"worker (\S+) commit step (\d+) crc ([0-9a-f]{8})")
START_LINE = re.compile(r"worker (\S+) start step (\d+) crc ([0-9a-f]{8}) "
                        r"size (\d+)")
DONE_LINE = re.compile(r"worker (\S+) done step (\d+) crc ([0-9a-f]{8})")


def _launch(ckpt_dir, np_, extra_env=None, extra_args=(), pid_dir=None,
            total=24, script="durable_worker.py"):
    from tests.conftest import clean_worker_env

    env = clean_worker_env(dict({
        "HVD_TPU_ELASTIC_COOLDOWN": "2",
        "HVD_TPU_ELASTIC_DISCOVERY_INTERVAL": "0.3",
        "HVD_TPU_START_TIMEOUT": "30",
        "DURABLE_TEST_TOTAL_STEPS": str(total),
        "DURABLE_TEST_STEP_SLEEP": "0.15",
    }, **(extra_env or {})))
    if pid_dir:
        env["DURABLE_TEST_PID_DIR"] = pid_dir
    cmd = [sys.executable, "-m", "horovod_tpu.run.run", "-np", str(np_),
           "--min-np", "1", "--ckpt-dir", ckpt_dir] + list(extra_args) + \
          ["--", sys.executable,
           os.path.join(REPO_ROOT, "tests", script)]
    return cmd, env


def _popen_to_files(cmd, env, tmp_path):
    """Starts a job that the test will poll and then kill, with its
    output in files: nobody drains a pipe while the test polls, and a
    chatty run (one line per step, runtime warnings on stderr) fills a
    64 KiB pipe and then blocks in print() before it ever checkpoints."""
    out = open(str(tmp_path / "killed_run.out"), "w+")
    err = open(str(tmp_path / "killed_run.err"), "w+")
    proc = subprocess.Popen(cmd, env=env, stdout=out, stderr=err,
                            start_new_session=True)

    def output():
        out.seek(0)
        err.seek(0)
        return out.read(), err.read()

    return proc, output


def _commit_crcs(out):
    """{step: crc} from a run's commit lines (identical across ranks —
    asserted)."""
    crcs = {}
    for wid, step, crc in COMMIT_LINE.findall(out):
        prev = crcs.setdefault(int(step), crc)
        assert prev == crc, ("ranks disagree at step %s: %s vs %s"
                             % (step, prev, crc))
    return crcs


@pytest.mark.e2e
def test_kill_everything_then_relaunch_resumes_bitwise(tmp_path):
    """SIGKILL every worker AND the driver mid-training; a relaunch
    must resume from the last durable commit with bitwise-identical
    state. Then the shrink variant: a second kill + relaunch at HALF
    the world size re-shards through rank-0-read + broadcast."""
    ckpt_dir = str(tmp_path / "ckpt")
    pid_dir = str(tmp_path / "pids")
    os.makedirs(pid_dir)

    # Run 1 gets a step budget it can never finish before the kill; the
    # relaunches run the normal 24 steps (the trajectory is identical
    # either way — total only bounds the loop).
    cmd, env = _launch(ckpt_dir, np_=2, pid_dir=pid_dir, total=200)
    proc, output = _popen_to_files(cmd, env, tmp_path)
    # Wait for a durable manifest covering a mid-training step.
    deadline = time.monotonic() + 120
    while True:
        manifest, _ = latest_valid_manifest(ckpt_dir)
        if manifest is not None and manifest["step"] >= 8:
            break
        assert proc.poll() is None, output()
        assert time.monotonic() < deadline, "no durable manifest in 120s"
        time.sleep(0.1)

    # SIGKILL the driver (the launcher process group) and every worker
    # (their own sessions, via the pid files) — total job loss.
    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    for name in os.listdir(pid_dir):
        pid = int(open(os.path.join(pid_dir, name)).read())
        try:
            os.killpg(os.getpgid(pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    proc.wait(timeout=30)
    out1, _ = output()
    crcs1 = _commit_crcs(out1)
    assert crcs1, out1

    def relaunch_and_check(np_, prior_crcs):
        cmd, env = _launch(ckpt_dir, np_=np_)
        result = subprocess.run(cmd, env=env, timeout=240,
                                capture_output=True, text=True)
        out = result.stdout
        assert result.returncode == 0, (out, result.stderr)
        starts = [(int(s), crc, int(n))
                  for _, s, crc, n in START_LINE.findall(out)]
        resumed = [x for x in starts if x[0] > 0]
        assert resumed, ("relaunch did not resume from the durable "
                         "checkpoint", out)
        step0, crc0, size0 = resumed[0]
        assert size0 == np_
        # Bitwise-identical: the resumed state's CRC equals the CRC the
        # killed run printed when it committed that exact step.
        assert step0 in prior_crcs, (step0, sorted(prior_crcs))
        assert crc0 == prior_crcs[step0], "state corrupted across restart"
        done = DONE_LINE.findall(out)
        assert len(done) == np_ and all(int(s) == 24 for _, s, _ in done)
        return _commit_crcs(out)

    # Same-size relaunch resumes bitwise-identically...
    crcs2 = relaunch_and_check(2, crcs1)
    # ...then kill nothing further; third run at HALF the world size
    # must restore the checkpoints run 2 finished with (step 24) — the
    # saved world size (2) differs from the restoring one (1).
    crcs2.update(crcs1)
    relaunch_and_check(1, crcs2)


@pytest.mark.e2e
def test_sharded_update_kill_restore_half_and_double_world(tmp_path):
    """Sharded-update x durable (docs/ZERO.md acceptance): SIGKILL a
    2-rank sharded-update job mid-run, then resume it at HALF (1) and
    DOUBLE (4) the world size — the sharded Adam state rides the
    checkpoint in its world-independent full form and re-shards on
    restore, and the final parameters are BITWISE-identical to an
    uninterrupted 2-rank run's (the worker's gradient quantization
    makes the trajectory exactly world-size-independent)."""
    # Uninterrupted 2-rank reference run.
    ckpt_u = str(tmp_path / "ckpt_u")
    cmd, env = _launch(ckpt_u, np_=2, script="sharded_durable_worker.py",
                       extra_env={"DURABLE_TEST_STEP_SLEEP": "0.1"})
    ref = subprocess.run(cmd, env=env, timeout=240, capture_output=True,
                         text=True)
    assert ref.returncode == 0, (ref.stdout, ref.stderr)
    ref_crcs = _commit_crcs(ref.stdout)
    ref_done = DONE_LINE.findall(ref.stdout)
    assert len(ref_done) == 2 and all(int(s) == 24 for _, s, _ in ref_done)
    ref_final = ref_done[0][2]

    # Killed run: same trajectory, SIGKILLed once a mid-run manifest
    # exists.
    ckpt = str(tmp_path / "ckpt")
    pid_dir = str(tmp_path / "pids")
    os.makedirs(pid_dir)
    cmd, env = _launch(ckpt, np_=2, script="sharded_durable_worker.py",
                       pid_dir=pid_dir, total=200,
                       extra_env={"DURABLE_TEST_STEP_SLEEP": "0.1"})
    proc, output = _popen_to_files(cmd, env, tmp_path)
    deadline = time.monotonic() + 120
    while True:
        manifest, _ = latest_valid_manifest(ckpt)
        if manifest is not None and manifest["step"] >= 6:
            break
        assert proc.poll() is None, output()
        assert time.monotonic() < deadline, "no durable manifest in 120s"
        time.sleep(0.1)
    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    for name in os.listdir(pid_dir):
        pid = int(open(os.path.join(pid_dir, name)).read())
        try:
            os.killpg(os.getpgid(pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    proc.wait(timeout=30)
    out1, _ = output()
    crcs1 = _commit_crcs(out1)
    assert crcs1, out1
    # The killed run's commits match the uninterrupted run's bitwise.
    for step, crc in crcs1.items():
        assert ref_crcs.get(step) == crc, (step, crc, ref_crcs.get(step))

    def resume(np_, total, prior_crcs):
        cmd, env = _launch(ckpt, np_=np_, total=total,
                           script="sharded_durable_worker.py",
                           extra_env={"DURABLE_TEST_STEP_SLEEP": "0.1"})
        result = subprocess.run(cmd, env=env, timeout=240,
                                capture_output=True, text=True)
        assert result.returncode == 0, (result.stdout, result.stderr)
        starts = [(int(s), crc, int(n))
                  for _, s, crc, n in START_LINE.findall(result.stdout)]
        resumed = [x for x in starts if x[0] > 0]
        assert resumed, ("no resume from the durable checkpoint",
                         result.stdout)
        step0, crc0, size0 = resumed[0]
        assert size0 == np_
        # Bitwise resume: params + re-shardable full Adam state.
        assert step0 in prior_crcs, (step0, sorted(prior_crcs))
        assert crc0 == prior_crcs[step0], \
            "sharded state corrupted across restart"
        done = DONE_LINE.findall(result.stdout)
        assert len(done) == np_ and all(int(s) == total
                                        for _, s, _ in done)
        return _commit_crcs(result.stdout), done[0][2]

    # HALF the world size (1): finishes step 16 on the reference
    # trajectory bitwise.
    half_crcs, _ = resume(1, 16, crcs1)
    for step, crc in half_crcs.items():
        assert ref_crcs.get(step) == crc, (step, crc)
    # DOUBLE the world size (4): resumes the 1-rank run's step-16
    # state, trains 8 more steps, and lands on the uninterrupted run's
    # final CRC exactly.
    all_crcs = dict(crcs1)
    all_crcs.update(half_crcs)
    _, final = resume(4, 24, all_crcs)
    assert final == ref_final, (final, ref_final)


@pytest.mark.e2e
def test_chaos_storage_faults_never_crash_and_restore_skips_invalid(
        tmp_path):
    """Acceptance: with torn writes and bit flips injected across the
    run, training completes (storage faults degrade, never kill), and a
    relaunch restores the newest CRC-valid manifest — proven by
    corrupting the newest valid checkpoint post-hoc and watching the
    resume land one valid checkpoint earlier."""
    ckpt_dir = str(tmp_path / "ckpt")
    spec = ("seed=3;op=shard,prob=0.25,action=bitflip,count=-1;"
            "op=manifest,prob=0.2,action=torn,count=-1;"
            "op=shard,prob=0.1,action=slowfsync,delay_ms=200,count=-1")
    cmd, env = _launch(ckpt_dir, np_=2,
                       extra_env={"HVD_TPU_CKPT_FAULT_SPEC": spec,
                                  "HVD_TPU_CKPT_KEEP": "50"})
    result = subprocess.run(cmd, env=env, timeout=240,
                            capture_output=True, text=True)
    assert result.returncode == 0, (result.stdout, result.stderr)
    crcs1 = _commit_crcs(result.stdout)
    done = DONE_LINE.findall(result.stdout)
    assert len(done) == 2, result.stdout

    # The faults fired: with p=0.25 per shard over ~12 checkpoints the
    # run must contain at least one invalid checkpoint directory.
    entries = list_checkpoints(ckpt_dir)
    validity = {p: validate_manifest(p) is not None
                for _, _, p in entries}
    assert any(not ok for ok in validity.values()), \
        "fault injection produced no invalid checkpoint — spec inert?"
    manifest, best = latest_valid_manifest(ckpt_dir)
    assert manifest is not None
    # Invariant: everything newer than the chosen manifest is invalid.
    for step, gen, path in entries:
        if (step, gen) > (manifest["step"], manifest["generation"]):
            assert not validity[path]

    # Corrupt the newest VALID one too; the restore must fall back to
    # the next-older valid manifest, never touch the corrupt ones.
    shard = [n for n in os.listdir(best) if n.startswith("shard-")][0]
    spath = os.path.join(best, shard)
    data = bytearray(open(spath, "rb").read())
    data[0] ^= 0xFF
    with open(spath, "wb") as f:
        f.write(bytes(data))
    manifest2, best2 = latest_valid_manifest(ckpt_dir)
    assert manifest2 is not None and best2 != best
    assert manifest2["step"] <= manifest["step"]

    cmd, env = _launch(ckpt_dir, np_=2)
    result2 = subprocess.run(cmd, env=env, timeout=240,
                             capture_output=True, text=True)
    assert result2.returncode == 0, (result2.stdout, result2.stderr)
    starts = [(int(s), crc) for _, s, crc, _ in
              START_LINE.findall(result2.stdout)]
    resumed = [x for x in starts if x[0] > 0]
    assert resumed, result2.stdout
    step0, crc0 = resumed[0]
    assert step0 == manifest2["step"]
    assert crcs1.get(step0) == crc0


@pytest.mark.e2e
def test_driver_restart_from_ckpt_below_min_np(tmp_path):
    """--restart-from-ckpt: both workers die in generation 0, the world
    cannot reach --min-np=2 (host blacklisted), and instead of tearing
    down the driver performs a full-job restart whose fresh cohort
    auto-resumes from the last durable commit and finishes."""
    ckpt_dir = str(tmp_path / "ckpt")
    cmd, env = _launch(
        ckpt_dir, np_=2,
        extra_env={"DURABLE_TEST_CRASH_STEP": "7",
                   "DURABLE_TEST_CRASH_WIDS": "0,1",
                   # Long cooldown: the blacklisted host cannot return
                   # on its own, so only the restart path can save the
                   # job.
                   "HVD_TPU_ELASTIC_COOLDOWN": "600",
                   "HVD_TPU_START_TIMEOUT": "15"})
    cmd = cmd[:cmd.index("--")] + ["--min-np", "2",
                                   "--restart-from-ckpt"] + \
        cmd[cmd.index("--"):]
    # The worker command's --min-np 1 from _launch is overridden by the
    # later --min-np 2 (argparse keeps the last occurrence).
    t0 = time.monotonic()
    result = subprocess.run(cmd, env=env, timeout=240,
                            capture_output=True, text=True)
    elapsed = time.monotonic() - t0
    out, err = result.stdout, result.stderr
    assert result.returncode == 0, (out, err)
    assert out.count("crashing now") == 2, out
    assert "full-job restart 1/" in err, err
    crcs = _commit_crcs(out)
    starts = [(int(s), crc) for _, s, crc, _ in START_LINE.findall(out)]
    resumed = [x for x in starts if x[0] > 0]
    assert resumed, out
    step0, crc0 = resumed[0]
    # Crash at step 7, commits every 2: the restart resumes from the
    # step-6 durable commit, bitwise-identical.
    assert step0 == 6
    assert crcs[6] == crc0
    done = DONE_LINE.findall(out)
    assert len(done) == 2 and all(int(s) == 24 for _, s, _ in done)
    assert elapsed < 180, "restart recovery took %.0fs" % elapsed


@pytest.mark.e2e
def test_launcher_failure_summary_names_last_durable_step(tmp_path):
    """The static launcher's failure summary reports what a restart
    would recover when --ckpt-dir is set."""
    from tests.conftest import clean_worker_env

    ckpt_dir = str(tmp_path / "ckpt")
    write_ckpt(ckpt_dir, step=12)
    env = clean_worker_env()
    env["HVD_TPU_CKPT_DIR"] = ckpt_dir
    result = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run.run", "-np", "1", "--",
         sys.executable, "-c", "import sys; sys.exit(3)"],
        env=env, timeout=120, capture_output=True, text=True)
    assert result.returncode != 0
    assert "last durable checkpoint: step 12" in result.stderr, \
        result.stderr
