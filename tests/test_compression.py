"""Pluggable gradient compression (docs/COMPRESSION.md): codec unit
tests (round-trip error bounds per block size/dtype, wire-size math
pinned against the native layout), the jax ring allreduce with fused
per-hop quantization, negotiation/cache semantics (mode change = cache
miss; mixed-mode ranks rejected naming both modes), and the hvd-top
renderer's tolerance for workers that predate the cmp fields."""

import numpy as np
import pytest

from horovod_tpu import compression as comp


# --- codec units ------------------------------------------------------------


@pytest.mark.parametrize("block", [64, 128, 256, 512])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_int8_roundtrip_error_bound(block, dtype):
    """|x - dequant(quant(x))| <= scale/2 per element, for every block
    size and float dtype (f64 goes through the f32 wire view)."""
    rng = np.random.RandomState(block)
    for scale_mag in (1e-4, 1.0, 1e4):
        x = (rng.randn(block * 3 + 17) * scale_mag).astype(dtype)
        q, scales = comp.quantize_int8(x, block=block)
        y = comp.dequantize_int8(q, scales, block=block)
        bound = np.repeat(scales / 2.0, block)[:x.size]
        # + one f32 ulp of the input magnitude: the f64 input is first
        # narrowed to the f32 wire dtype.
        slack = np.abs(x).max() * 1e-6 + 1e-12
        assert np.all(np.abs(x.astype(np.float32) - y) <= bound + slack), \
            (block, scale_mag)


def test_int8_exact_on_constants_and_zeros():
    # A constant block quantizes exactly (q = +-127, scale = |c|/127);
    # all-zero blocks produce scale 0 and decode to exact zeros.
    for c in (1.0, -3.5, 0.0):
        x = np.full(1000, c, np.float32)
        q, s = comp.quantize_int8(x)
        y = comp.dequantize_int8(q, s)
        np.testing.assert_allclose(y, x, rtol=1e-6, atol=0)


def test_int8_nonfinite_blocks_stay_nonfinite():
    """An overflowed (inf/NaN) gradient must NOT decode to finite
    numbers — downstream isfinite / loss-scale skip-step guards have to
    keep firing after the allreduce (numpy and jax planes agree)."""
    import jax.numpy as jnp

    x = np.ones(600, np.float32)
    x[300] = np.nan
    x[10] = np.inf
    q, s = comp.quantize_int8(x)
    y = comp.dequantize_int8(q, s)
    # Both poisoned blocks decode nonfinite; clean blocks stay clean.
    assert not np.isfinite(y[:512]).any()
    assert np.isfinite(y[512:]).all()

    xj = jnp.zeros(512, jnp.float32).at[5].set(jnp.nan)
    qj, sj = comp.quantize_int8_jax(xj)
    yj = np.asarray(comp.dequantize_int8_jax(qj, sj))
    assert not np.isfinite(yj[:256]).any()
    assert np.isfinite(yj[256:]).all()


def test_int8_symmetric_range():
    """-128 is never produced (symmetric [-127, 127])."""
    x = np.linspace(-1000, 1000, 4096).astype(np.float32)
    q, _ = comp.quantize_int8(x)
    assert q.min() >= -127 and q.max() <= 127


def test_bf16_roundtrip_matches_ml_dtypes():
    ml_dtypes = pytest.importorskip("ml_dtypes")
    rng = np.random.RandomState(7)
    x = (rng.randn(4096) * 100).astype(np.float32)
    got = comp.bf16_roundtrip(x)
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(got, want)


def test_wire_bytes_matches_native_layout():
    from horovod_tpu.common.basics import get_basics
    b = get_basics()
    for count in (0, 1, 255, 256, 257, 1000, 1 << 20):
        for mode_name, mode_id in (("none", 0), ("bf16", 1), ("int8", 2)):
            assert comp.wire_bytes(count, mode_name) == \
                b.compressed_size(count, mode_id), (count, mode_name)
    # ~3.9x for block-aligned int8, exactly 2x for bf16.
    n = 1 << 20
    assert comp.wire_bytes(n, "none") / comp.wire_bytes(n, "int8") > 3.8
    assert comp.wire_bytes(n, "none") == 2 * comp.wire_bytes(n, "bf16")


def test_effective_mode_degrades_non_f32():
    from horovod_tpu.common.basics import get_basics, numpy_to_hvd_dtype
    b = get_basics()
    f32 = numpy_to_hvd_dtype(np.float32)
    for np_dtype in (np.int32, np.int64, np.float64, np.float16, np.uint8):
        hv = numpy_to_hvd_dtype(np_dtype)
        assert b.effective_compression(comp.INT8, hv) == comp.NONE
        assert b.effective_compression(comp.BF16, hv) == comp.NONE
    assert b.effective_compression(comp.INT8, f32) == comp.INT8


def test_resolve_and_env_default(monkeypatch):
    assert comp.resolve(None) == comp.Compression.none
    assert comp.resolve("bf16") is comp.Compression.bf16
    assert comp.resolve("INT8") is comp.Compression.int8
    assert comp.resolve(comp.Compression.int8).name == "int8"
    assert comp.resolve(2) is comp.Compression.int8
    monkeypatch.setenv(comp.ENV_VAR, "int8")
    assert comp.resolve(None) is comp.Compression.int8
    # Explicit none overrides the env.
    assert comp.resolve("none") is comp.Compression.none
    # A typo'd env must not silently quantize.
    monkeypatch.setenv(comp.ENV_VAR, "int4")
    assert comp.resolve(None) is comp.Compression.none
    with pytest.raises(ValueError):
        comp.resolve("fp8")
    # Legacy codec objects belong to the binding layer, not the wire.
    from horovod_tpu import jax as hvd_jax
    with pytest.raises(TypeError):
        comp.resolve(hvd_jax.Compression.fp16)


# --- jax ring allreduce -----------------------------------------------------


def _mesh8():
    import jax
    from jax.sharding import Mesh
    devs = jax.devices("cpu")
    return Mesh(np.array(devs), ("hvd",))


@pytest.mark.parametrize("mode,tol", [("none", 1e-5), ("bf16", 2e-2),
                                      ("int8", 4e-2)])
def test_ring_allreduce_matches_psum(mode, tol):
    import jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.ring import ring_allreduce

    mesh = _mesh8()
    rng = np.random.RandomState(0)
    # Deliberately NOT a multiple of 8 * BLOCK: exercises pad/unpad.
    x = (rng.randn(8, 1003) * 5).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: ring_allreduce(v, "hvd", compression=mode),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"), check_vma=False))
    out = np.asarray(f(x))
    want = x.sum(axis=0, keepdims=True).repeat(8, 0)
    err = np.max(np.abs(out - want)) / np.max(np.abs(want))
    assert err < tol, (mode, err)
    # Every rank must hold the IDENTICAL reduced values (the allgather
    # phase forwards encoded chunks verbatim — no per-hop requant drift).
    for r in range(1, 8):
        assert np.array_equal(out[0], out[r]), (mode, r)


def test_ring_allreduce_non_f32_passthrough():
    import jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.ring import ring_allreduce

    mesh = _mesh8()
    x = np.arange(8 * 64, dtype=np.int32).reshape(8, 64)
    f = jax.jit(jax.shard_map(
        lambda v: ring_allreduce(v, "hvd", compression="int8"),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"), check_vma=False))
    out = np.asarray(f(x))
    np.testing.assert_array_equal(out[0], x.sum(axis=0))


@pytest.mark.parametrize("mode,tol", [("bf16", 2e-2), ("int8", 4e-2)])
def test_jax_allreduce_compressed_in_jit(mode, tol):
    """hvd.jax.allreduce(compression=...) inside shard_map: compressed
    average matches the exact mean within the codec bound."""
    import jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import jax as hvd_jax

    mesh = _mesh8()
    rng = np.random.RandomState(3)
    x = (rng.randn(8, 500) * 2).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: hvd_jax.allreduce(v, average=True, compression=mode),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"), check_vma=False))
    out = np.asarray(f(x))
    want = x.mean(axis=0, keepdims=True).repeat(8, 0)
    err = np.max(np.abs(out - want)) / np.max(np.abs(want))
    assert err < tol, (mode, err)


def test_jax_allreduce_legacy_codecs_still_work():
    import jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import jax as hvd_jax

    mesh = _mesh8()
    x = np.full((8, 32), 2.0, np.float32)
    for codec in (hvd_jax.Compression.none, hvd_jax.Compression.fp16,
                  hvd_jax.Compression.bf16):
        f = jax.jit(jax.shard_map(
            lambda v: hvd_jax.allreduce(v, average=True, compression=codec),
            mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
            check_vma=False))
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, 2.0, rtol=1e-2)


# --- multi-process e2e (launcher) -------------------------------------------


@pytest.mark.e2e
@pytest.mark.parametrize("np_", [2, 4])
def test_compression_worker(run_launcher, np_):
    # The worker asserts cache-key semantics (a changed mode invalidates
    # the cached entry), so the response cache is pinned on: the always-on
    # tuner otherwise samples cache-off windows, in which nothing is
    # counted as invalidated (seen once in four whole-suite runs).
    proc = run_launcher(np_, "compression_worker.py",
                        extra_env={"HVD_TPU_CACHE_CAPACITY": "1024"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(np_):
        assert ("rank %d: compression worker passed" % r) in proc.stdout, \
            proc.stdout + proc.stderr


@pytest.mark.e2e
def test_mixed_mode_rejected_at_negotiation(run_launcher):
    proc = run_launcher(2, "compression_mixed_worker.py")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(2):
        assert ("rank %d: mixed worker passed" % r) in proc.stdout, \
            proc.stdout + proc.stderr


@pytest.mark.e2e
def test_env_default_engages_compression(run_launcher):
    """HVD_TPU_COMPRESSION=int8 with no per-call argument: the fuzz
    worker's f32 allreduces ride the int8 wire (constant fills quantize
    exactly, so its value assertions hold bit-for-bit)."""
    proc = run_launcher(2, "negotiation_fuzz_worker.py",
                        extra_env={"HVD_TPU_COMPRESSION": "int8",
                                   "HVD_TPU_METRICS": "1",
                                   "HVD_TPU_FUZZ_TENSORS": "12"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("negotiation fuzz passed") == 2, \
        proc.stdout + proc.stderr


# --- hvd-top renderer tolerance ---------------------------------------------


def _job(per_rank):
    return {"size": len(per_rank), "generation": 1,
            "per_rank": per_rank,
            "age_seconds": {r: 0.0 for r in per_rank},
            "rank_lag_seconds": [0.0] * len(per_rank)}


def test_hvd_top_tolerates_workers_without_cmp_fields():
    """Mixed-version elastic job: rank 0 reports the new compression
    fields, rank 1 (older worker) does not. The renderer must keep the
    columns aligned and show '-' for the missing cmp value — not
    misalign or crash."""
    from horovod_tpu.run import top

    new_worker = {"cycles_total": 100.0, "cycle_seconds_sum": 1.0,
                  "compression_bytes_in_total": 4.0e6,
                  "compression_bytes_out_total": 1.0e6,
                  "cache_hit_total": 5, "cache_miss_total": 5}
    old_worker = {"cycles_total": 90.0, "cycle_seconds_sum": 1.0,
                  "cache_hit_total": 5, "cache_miss_total": 5}
    frame = top.render(_job({"0": new_worker, "1": old_worker}), None, 0.0,
                       "test:0")
    lines = frame.splitlines()
    rows = [ln for ln in lines if ln.strip().startswith(("0", "1"))]
    assert len(rows) == 2, frame
    header = next(ln for ln in lines if " cmp" in ln)
    cmp_col = header.index(" cmp")
    # New worker shows the live ratio; old worker shows '-' in the SAME
    # column span (no shift).
    assert "4.0x" in rows[0], frame
    assert rows[1][cmp_col:cmp_col + 5].strip() == "-", frame
    # Every row is exactly as wide as the header (nothing misaligned).
    assert all(len(r) == len(rows[0]) for r in rows), frame


def test_hvd_top_cmp_ratio_rendering():
    from horovod_tpu.run import top

    w = {"cycles_total": 10.0, "cycle_seconds_sum": 0.1,
         "compression_bytes_in_total": 39.0e6,
         "compression_bytes_out_total": 10.0e6}
    frame = top.render(_job({"0": w}), None, 0.0, "test:0")
    assert "3.9x" in frame, frame
    # Zero bytes out (compression never engaged) renders '-', not a
    # division error.
    w0 = dict(w, compression_bytes_in_total=0.0,
              compression_bytes_out_total=0.0)
    frame0 = top.render(_job({"0": w0}), None, 0.0, "test:0")
    assert frame0
