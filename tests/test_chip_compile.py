"""The main path's Pallas kernels compile for the chip, with no chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`on-chip-measurement` guide, section 2). Interpret
mode cannot see what it refuses — a slice not aligned to the tiling, more
fast memory than a kernel may use — so the kernels `chip_smoke.py` runs are
compiled here at its shapes, about two seconds each. A compile that passes
is not a chip run; `python chip_smoke.py` is.

Only one process may load the TPU's library, and it keeps it until it
exits: the topology is described inside the module-scoped fixture below
(never at import time), in this test's own process, and all such tests
live in this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import kernel_calls as _kernels, kernel_named as _named
from horovod_tpu.ops import BandMask, BlockDiffusionMask
from horovod_tpu import profile
from horovod_tpu.ops.flash_attention import (_flash, _flash_gated,
                                             _flash_shared,
                                             _pallas_forward_lse,
                                             flash_plan,
                                             flash_ring_bwd_step,
                                             flash_ring_step)


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 to compile for. The persistent compile cache
    is off around these compiles: an entry written for a described chip
    cannot be read back without one, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # The compiler otherwise logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compiles fn for the described chip; returns the program text.

    Under the ambient matmul precision the models run with, not the
    "highest" that other test modules set process-wide at import: Mosaic
    refuses an fp32-precision matmul on bf16 operands ("Bad lhs type"),
    so with that setting every bf16 flash/ring kernel fails to compile
    for the chip (found by this file; recorded in ROADMAP.md)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text()


# (B, H, G, L, D): the attention of chip_smoke.py's L=1024 LM and of its
# long-context h6 / gqa2 shape (the one shape here that `flash_plan` sends
# down the gridded path, by its length alone and in all three kernels: k
# and v whole are 32 MiB, and the backward held by the q block 96), and of the
# benchmark's LM configurations on a chip (`neox1b4_w2048`: 2 x 2048;
# `olmoe1b7_w2048` and `ouro2b6_w2048`: 1 x 4096), whose resident
# backward, one kernel, asks for more than the default VMEM limit, and of
# a grouped shape short enough that the one kernel holds it (three heads'
# rows and dQ's accumulator: 12 MiB).
_GRIDDED = (1, 6, 2, 32768, 128)
_LM_SHAPES = [(8, 12, 12, 1024, 64), _GRIDDED, (2, 16, 16, 2048, 128),
              (1, 16, 16, 4096, 128), (2, 6, 2, 1024, 128)]


@pytest.mark.parametrize("B,H,G,L,D", _LM_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, B, H, G, L, D):
    fwd = functools.partial(_pallas_forward_lse, scale=D ** -0.5,
                            causal=True, interpret=False)
    bf16 = jnp.bfloat16
    text = _compile(one_chip, fwd, ((B, H, L, D), bf16),
                    ((B, G, L, D), bf16), ((B, G, L, D), bf16))
    assert _kernels(text) == 1, text[:2000]
    assert _named(text, profile.FLASH_FWD)


@pytest.mark.parametrize("B,H,G,L,D", _LM_SHAPES)
def test_flash_backward_compiles_for_v5e(one_chip, B, H, G, L, D):
    def bwd(q, k, v, g):
        # interpret=False names the kernel path itself: the dispatcher in
        # flash_attention() asks jax.default_backend(), which is the CPU
        # here.
        _, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, True, False),
            q, k, v)
        return vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, bwd, ((B, H, L, D), bf16),
                    ((B, G, L, D), bf16), ((B, G, L, D), bf16),
                    ((B, H, L, D), bf16))
    # forward (for the residuals) and the backward: one kernel where
    # `flash_plan` finds it resident, two custom calls a layer; dQ and
    # dK/dV apart where not, three.
    paths = {name: p.path for backward in (False, True)
             for name, p in flash_plan(B, H, L, D, H // G, jnp.bfloat16,
                                       backward).items()}
    assert paths == ({
        profile.FLASH_FWD: "gridded", profile.FLASH_DQ: "gridded",
        profile.FLASH_DKV: "gridded"} if (B, H, G, L, D) == _GRIDDED else {
        profile.FLASH_FWD: "resident", profile.FLASH_BWD: "resident"})
    assert _kernels(text) == len(paths), text[:2000]
    for name in paths:
        assert _named(text, name), name


# The programs the plain kernels compiled to before the kernels learnt to
# take a mask by rule (PR 38), as `benchmark/rehearse_text.py` hashes a
# program: forward + backward of `_flash` with every source location taken
# out. The one-kernel backward held by the k block (1 x 16 x 4096 x 128) and
# the backward under grouped heads (2 x 6 on 2 x 8192 x 128): two kernels
# with dK/dV gridded until PR 45, then held by the q block, and since PR 49
# ONE kernel held by the q block (k, v, dk, dv and two accumulators
# resident, 24 MiB: each of those PRs moved the second pair of hashes, as it
# meant to, and not the first), and since PR 57 on every operand in the
# public layout ([B, L, heads * D], a q block the group's heads side by side
# on the lanes; it moved the second pair again, and left the first, a call
# with one query head a kv head, to the letter). A
# change that is MEANT to move these kernels replaces the hashes; one that
# adds a rule, a product or a path beside them does not.
_PLAIN_PROGRAMS = {
    (1, 16, 16, 4096, 128, True): "3e42888fafb106fc",
    (1, 16, 16, 4096, 128, False): "0a992eb5acbb888b",
    (2, 6, 2, 8192, 128, True): "6e97032054c30c1c",
    (2, 6, 2, 8192, 128, False): "17d8f98a7e6bbecb"}


@pytest.mark.parametrize("B,H,G,L,D,causal", list(_PLAIN_PROGRAMS))
def test_causal_and_full_calls_lower_to_the_text_they_had(one_chip, B, H, G,
                                                          L, D, causal):
    import hashlib

    from benchmark.rehearse_text import without_locations

    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, causal, False),
            q, k, v)
        return (out,) + vjp(g)

    bf16 = jnp.bfloat16
    text, _ = without_locations(_compile(
        one_chip, fwd_bwd, ((B, H, L, D), bf16), ((B, G, L, D), bf16),
        ((B, G, L, D), bf16), ((B, H, L, D), bf16)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PLAIN_PROGRAMS[B, H, G, L, D, causal]


# The attention of the benchmark's block-diffusion cell (`sdar30b_1chip`):
# 32 heads on 4, a noisy and a clean copy of 4096 tokens, blocks of 4 (the
# backward ONE kernel held by the q block since PR 49: `hvd_flash_bwd` in the
# program's text and neither `hvd_flash_dq` nor `hvd_flash_dkv`); and a shape
# short enough that the one kernel is held by the k block. Beside each, the
# same call under the causal band (`mellum12b_1chip`'s window layers: a
# query on itself and the 1023 keys before it; and a window that is no
# multiple of a tile). All four rows compile the forward's merged walk
# (`_walk_runs_merged`), the two "q" rows the backward's walk with lone
# sub-tiles (`_walk_cut_runs`); the "k" rows' backward walks the q blocks
# (`_walk_q`).
@pytest.mark.parametrize("H,G,S,rule,held", [
    (32, 4, 8192, BlockDiffusionMask(4096, 4), "q"),
    (16, 16, 2048, BlockDiffusionMask(1024, 4), "k"),
    (32, 4, 8192, BandMask(1024), "q"), (16, 16, 2048, BandMask(300), "k")])
def test_ruled_flash_compiles_for_v5e(one_chip, H, G, S, rule, held):
    D = 128

    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, False, False, rule),
            q, k, v)
        return (out,) + vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, fwd_bwd, ((1, H, S, D), bf16),
                    ((1, G, S, D), bf16), ((1, G, S, D), bf16),
                    ((1, H, S, D), bf16))
    plans = {name: p for backward in (False, True)
             for name, p in flash_plan(1, H, S, D, H // G, bf16, backward,
                                       mask=rule).items()}
    paths = {name: (p.path, p.held) for name, p in plans.items()}
    assert paths == {profile.FLASH_FWD: ("resident", "q"),
                     profile.FLASH_BWD: ("resident", held)}
    # what compiled: the forward's runs of a kind in one loop; the backward
    # held by the q block with a cut k block's lone sub-tile of 256 keys a
    # turn (PR 53), the one held by the k block by k blocks
    assert {name: p.cut_k for name, p in plans.items()} == {
        profile.FLASH_FWD: plans[profile.FLASH_FWD].block_k,
        profile.FLASH_BWD: 256 if held == "q" else plans[
            profile.FLASH_BWD].block_k}
    assert _kernels(text) == len(paths), text[:2000]
    for name in (profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV,
                 profile.FLASH_BWD):
        assert _named(text, name) == (name in paths), name
    # no score array of the whole sequence anywhere in the program
    assert "[%d,%d]" % (S, S) not in text


# The two flash calls of the benchmark's `laguna33b_1chip`, forward and
# backward: a full layer's 48 query heads on 8 (group 6: a tile of 6 x 256
# rows, the shape `_grouped_blocks` had swept at group 3 only) under the
# causal triangle, and a window layer's 64 on 8 (group 8) under a band of
# 512 keys, one k block wide, so that every k block a query tile visits is
# cut at an edge. At 8192 positions the k-held backward does not fit either:
# the one kernel held by the q block, 29 MiB of VMEM at 1536 rows.
@pytest.mark.parametrize("H,rule", [(48, None), (64, BandMask(512))])
def test_the_laguna_cells_flash_calls_compile_for_v5e(one_chip, H, rule):
    G, S, D = 8, 8192, 128

    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, rule is None, False,
                                   rule), q, k, v)
        return (out,) + vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, fwd_bwd, ((1, H, S, D), bf16),
                    ((1, G, S, D), bf16), ((1, G, S, D), bf16),
                    ((1, H, S, D), bf16))
    plans = {name: p for backward in (False, True)
             for name, p in flash_plan(
                 1, H, S, D, H // G, bf16, backward,
                 **({} if rule is None else {"mask": rule})).items()}
    assert {name: (p.path, p.held) for name, p in plans.items()} == {
        profile.FLASH_FWD: ("resident", "q"),
        profile.FLASH_BWD: ("resident", "q")}
    assert plans[profile.FLASH_FWD].block_q == (1536 if H == 48 else 1024)
    assert _kernels(text) == 2, text[:2000]
    for name in (profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV,
                 profile.FLASH_BWD):
        assert _named(text, name) == (name in plans), name
    assert "[%d,%d]" % (S, S) not in text


# The LFM2 cell's call (`lfm2moe8b_1chip`, PR 65): 2 x 32 query heads on 8 kv
# heads at head width 64, 8192 positions, causal: a grouped call at D = 64,
# "a slab a head" (every other cell's grouped call is D = 128 and goes by
# position), swept until then at L = 1024 and 2048 only. Both directions
# Pallas kernels, resident, the backward ONE kernel held by the q block at
# exactly the 24 MiB limit of whole-sequence operands.
def test_the_lfm2_cells_flash_call_compiles_for_v5e(one_chip):
    B, H, G, S, D = 2, 32, 8, 8192, 64

    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, True, False), q, k, v)
        return (out,) + vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, fwd_bwd, ((B, H, S, D), bf16),
                    ((B, G, S, D), bf16), ((B, G, S, D), bf16),
                    ((B, H, S, D), bf16))
    plans = {name: p for backward in (False, True)
             for name, p in flash_plan(B, H, S, D, H // G, bf16,
                                       backward).items()}
    assert {name: (p.path, p.held, p.block_q, p.block_k)
            for name, p in plans.items()} == {
        profile.FLASH_FWD: ("resident", "q", 2048, 512),
        profile.FLASH_BWD: ("resident", "q", 2048, 512)}
    assert plans[profile.FLASH_BWD].resident_bytes == 24 << 20
    assert _kernels(text) == 2, text[:2000]
    for name in (profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV,
                 profile.FLASH_BWD):
        assert _named(text, name) == (name in plans), name
    assert "[%d,%d]" % (S, S) not in text


# The same two calls with the cell's head gate (PR 63: `flash_attention`'s
# ``gate``, [1, H, 8192] f32 as `_flash_gated` takes it): the forward takes
# the gates' reciprocals as one more q-side operand (f32[1, 8, 8192, group]:
# a q block's stacked to the tile's rows in VMEM, a factor of the rows'
# normalisers), the backward is the ungated kernel on the gated rows' lse and
# on delta / gate; still two `tpu_custom_call`s a call, the gate's gradient a
# [1, H, 8192] division of delta. The programs they compile to, hashed as
# `_PLAIN_PROGRAMS`' are: a change MEANT to move the gated calls replaces
# these.
_GATED_PROGRAMS = {(48, None): "cba8b8dd0dc424d0",
                   (64, BandMask(512)): "809ce64502faba9a"}


@pytest.mark.parametrize("H,rule", list(_GATED_PROGRAMS))
def test_the_laguna_cells_gated_calls_compile_to_their_text(one_chip, H,
                                                            rule):
    import hashlib

    from benchmark.rehearse_text import without_locations

    G, S, D = 8, 8192, 128

    def fwd_bwd(q, k, v, gate, g):
        out, vjp = jax.vjp(
            lambda q, k, v, gate: _flash_gated(
                q, k, v, gate, D ** -0.5, rule is None, False, rule),
            q, k, v, gate)
        return (out,) + vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, fwd_bwd, ((1, H, S, D), bf16),
                    ((1, G, S, D), bf16), ((1, G, S, D), bf16),
                    ((1, H, S), jnp.float32), ((1, H, S, D), bf16))
    plans = {name: p for backward in (False, True)
             for name, p in flash_plan(
                 1, H, S, D, H // G, bf16, backward, gate=True,
                 **({} if rule is None else {"mask": rule})).items()}
    assert {name: (p.path, p.held, p.gate) for name, p in plans.items()} == {
        profile.FLASH_FWD: ("resident", "q", "kernel"),
        profile.FLASH_BWD: ("resident", "q", "lse")}
    assert _kernels(text) == 2, text[:2000]
    for name in plans:
        assert _named(text, name), name
    # the forward's one operand more: a kv head's values a position's lanes
    assert text.count("f32[1,%d,%d,%d]{3,2,1,0}" % (G, S, H // G)) == 1
    assert "[%d,%d]" % (S, S) not in text
    text, _ = without_locations(text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _GATED_PROGRAMS[H, rule]


# The ring LM of `chip_smoke.py --chips 4`: B2 x H6 per chip, L=8192 over
# four chips, D=128; and the same shard with its query heads three to a kv
# head: the ring's kernels take a group's rows as the plain kernels do
# (PR 57: a kv head's query heads stacked), their operands a slab a head.
_RING = dict(BG=12, L=2048, D=128)


@pytest.mark.parametrize("group", [1, 3])
def test_ring_forward_step_compiles_for_v5e(one_chip, group):
    BG, L, D = _RING["BG"], _RING["L"], _RING["D"]
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    text = _compile(
        one_chip,
        functools.partial(flash_ring_step, causal=True, group=group),
        ((BG, L, D), bf16), ((BG // group, L, D), bf16),
        ((BG // group, L, D), bf16), ((BG, L, D), f32), ((BG, L, 8), f32),
        ((BG, L, 8), f32), ((), i32), ((), i32))
    assert _kernels(text) == 1, text[:2000]


@pytest.mark.parametrize("group", [1, 3])
def test_ring_backward_step_compiles_for_v5e(one_chip, group):
    BG, L, D = _RING["BG"], _RING["L"], _RING["D"]
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    text = _compile(
        one_chip,
        functools.partial(flash_ring_bwd_step, causal=True, group=group),
        ((BG, L, D), bf16), ((BG // group, L, D), bf16),
        ((BG // group, L, D), bf16), ((BG, L, D), bf16), ((BG, L, 8), f32),
        ((BG, L, 8), f32), ((BG, L, D), f32), ((BG // group, L, D), f32),
        ((BG // group, L, D), f32), ((), i32), ((), i32))
    # dQ, dK/dV
    assert _kernels(text) == 2, text[:2000]


# The experts of OLMoE-1B-7B on one chip: 8 x 4096 assigned rows in 64
# groups, f32 matrices of 2048 x 1024 under bf16 rows (`benchmark`'s cell
# `olmoe1b7_1chip`), the up projection's shapes and the down projection's.
@pytest.mark.parametrize("K,N", [(2048, 1024), (1024, 2048)])
def test_grouped_matmul_compiles_for_v5e(one_chip, K, N):
    from horovod_tpu.ops.grouped_matmul import grouped_matmul

    def fwd_bwd(lhs, rhs, sizes, g):
        out, vjp = jax.vjp(
            lambda l, r: grouped_matmul(l, r, sizes, interpret=False),
            lhs, rhs)
        return out, vjp(g)

    text = _compile(one_chip, fwd_bwd, ((32768, K), jnp.bfloat16),
                    ((64, K, N), jnp.float32), ((64,), jnp.int32),
                    ((32768, N), jnp.bfloat16))
    # forward, the rows' gradient, the matrices' gradient
    assert _kernels(text) == 3, text[:2000]
    for name in profile.MOE_GMM_KERNELS:
        assert _named(text, name), name


# A hyper-connection of Xing4.0 on one chip: four streams of 4096 tokens,
# 3584 wide, against phi's 24 columns (`benchmark`'s cell `xing29b_1chip`).
def test_hc_stat_compiles_for_v5e(one_chip):
    from horovod_tpu.ops.hc_stat import hc_plan, hc_stat

    def fwd_bwd(X, phi, g_s, g_p):
        out, vjp = jax.vjp(lambda X, phi: hc_stat(X, phi, interpret=False),
                           X, phi)
        return out, vjp((g_s, g_p))

    f32 = jnp.float32
    text = _compile(one_chip, fwd_bwd, ((4, 1, 4096, 3584), jnp.bfloat16),
                    ((4 * 3584, 24), f32), ((1, 4096), f32),
                    ((1, 4096, 24), f32))
    assert hc_plan(4, 4096, 3584, 24, jnp.bfloat16)["path"] == "kernel"
    # the forward's, and phi's gradient; X's own gradient is XLA's
    assert _kernels(text) == 2, text[:2000]
    for name in (profile.HC_STAT, profile.HC_STAT_DPHI):
        assert _named(text, name), name


# A routed layer of Xing4.0 on one rank of eight: 4 x 4096 assignments of
# 3584-wide tokens, of which the router decides how many are this rank's
# (`benchmark`'s cell `xing29b_1chip`).
def test_moe_rows_compile_for_v5e(one_chip, monkeypatch):
    from horovod_tpu.ops import moe_rows

    T, k, D = 4096, 4, 3584
    bf16, i32 = jnp.bfloat16, jnp.int32

    def fwd_bwd(x, ys, w, order, inv, n_live, g_xs, g_gate, g_y):
        # the rows twice, as a gated expert's two first matmuls use them
        out, vjp = jax.vjp(lambda x, ys, w: (
            moe_rows.dispatch(x, order, inv, n_live, k, 2, False),
            moe_rows.combine(ys, w, order, inv, n_live, False)), x, ys, w)
        return out, vjp(((g_xs, g_gate), g_y))

    text = _compile(one_chip, fwd_bwd, ((T, D), bf16), ((k * T, D), bf16),
                    ((k, T), jnp.float32), ((k * T,), i32), ((k * T,), i32),
                    ((), i32), ((k * T, D), bf16), ((k * T, D), bf16),
                    ((T, D), bf16))
    # each kernel once forward and once, as the other's transpose, backward
    assert _kernels(text) == 4, text[:2000]
    for name in profile.MOE_ROWS_KERNELS:
        assert _named(text, name), name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = profile.moe_rows_plan(T, k, D, bf16)
    assert plan["path"] == "kernel" and plan["tile_rows"] == 1024


# The same two ops on the order a layer forms where it holds a part of the
# experts (`expert.held_order`, PR 55; `sdar30b_1chip` / `mellum12b_1chip`:
# 8 x 8192 assignments, 16 bins; `nemo3s120b_1chip`: 22 x 4096, 8 bins, the
# buffer cut to 8 x 4096 rows): one sort forward and one backward, and no
# gather of a [k*T] vector on either side of the kernels.
@pytest.mark.parametrize("T,k,D,count", [(8192, 8, 2048, 16),
                                         (4096, 22, 1024, 8)])
def test_moe_rows_on_the_counted_order_compile_for_v5e(one_chip, T, k, D,
                                                       count):
    from horovod_tpu.ops import moe_rows
    from horovod_tpu.parallel import expert

    bf16, i32 = jnp.bfloat16, jnp.int32
    bound = min(k * T, count * T)

    def fwd_bwd(x, ys, w, flat, sizes, g_xs, g_y):
        n_live = jnp.sum(sizes)

        def ops(x, ys, w):
            every, inv, scale = expert.held_order(flat, w, 3, sizes)
            return (moe_rows.dispatch(x, every[:bound], inv, n_live, k, 1,
                                      False)[0],
                    moe_rows.combine(ys, w, every[:bound], inv, n_live,
                                     False, (scale[:bound], every)))

        out, vjp = jax.vjp(ops, x, ys, w)
        return out, vjp((g_xs, g_y))

    text = _compile(one_chip, fwd_bwd, ((T, D), bf16), ((bound, D), bf16),
                    ((k, T), jnp.float32), ((k * T,), i32), ((count,), i32),
                    ((bound, D), bf16), ((T, D), bf16))
    assert _kernels(text) == 4, text[:2000]
    assert text.count(" sort(") == 2 and " gather(" not in text


# The activation between the grouped matmuls of the three cells whose layers
# hold a part of their experts, with the last matmul, as `moe_ffn` calls them:
# (rows, F, the activation, gated, experts held, the matmul's width).
MOE_ACT_SHAPES = {
    "sdar30b_1chip": (65536, 768, "silu", True, 16, 2048),
    "nemo3s120b_1chip": (32768, 2688, "relu2", False, 8, 1024),
    "xing29b_1chip": (16384, 1024, "silu", True, 8, 3584),
}


@pytest.mark.parametrize("cell", sorted(MOE_ACT_SHAPES))
def test_moe_act_compiles_for_v5e(one_chip, monkeypatch, cell):
    from horovod_tpu.ops import moe_act
    from horovod_tpu.parallel.expert import ACTIVATIONS

    rows, F, act, gated, held, width = MOE_ACT_SHAPES[cell]
    bf16 = jnp.bfloat16

    def fwd_bwd(g, h, n_live, w_out, sizes, dy):
        out, vjp = jax.vjp(lambda g, h, w_out: moe_act.activated_matmul(
            ACTIVATIONS[act], h, n_live, w_out, sizes, g if gated else None,
            False), g, h, w_out)
        return out, vjp(dy)

    text = _compile(one_chip, fwd_bwd, ((rows, F), bf16), ((rows, F), bf16),
                    ((), jnp.int32), ((held, F, width), jnp.float32),
                    ((held,), jnp.int32), ((rows, width), bf16))
    # the activation forward and backward between the grouped matmul's three
    assert _kernels(text) == 5, text[:2000]
    for name in profile.MOE_ACT_KERNELS + profile.MOE_GMM_KERNELS:
        assert _named(text, name), name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = profile.moe_act_plan(rows, F, bf16, gated=gated)
    assert plan["path"] == "kernel" and plan["buffer_rows"] == rows
    assert rows % plan["tile_rows"] == 0 and F % plan["block_cols"] == 0
    assert plan["vmem_bytes"] <= moe_act._VMEM_LIMIT_BYTES
    assert profile.moe_act_plan(rows, F, bf16, gated=gated,
                                held=False)["path"] == "xla"


# One rank's share of Nemotron-3-Super's layers on one chip (`benchmark`'s
# cell `nemo3s120b_1chip`): attention's 16 query heads on ONE kv head at 4096
# positions, a head group the one backward kernel cannot hold by the k block
# (q + dO of 16 heads): the forward and, since PR 49, the ONE backward kernel
# resident and held by the q block.
def test_flash_at_a_head_group_of_16_compiles_for_v5e(one_chip):
    B, H, G, L, D = 1, 16, 1, 4096, 128

    def bwd(q, k, v, g):
        _, vjp = jax.vjp(
            lambda q, k, v: _flash(q, k, v, D ** -0.5, True, False),
            q, k, v)
        return vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, bwd, ((B, H, L, D), bf16),
                    ((B, G, L, D), bf16), ((B, G, L, D), bf16),
                    ((B, H, L, D), bf16))
    plans = {name: (p.path, p.held) for backward in (False, True)
             for name, p in flash_plan(B, H, L, D, H // G, bf16,
                                       backward).items()}
    assert plans == {profile.FLASH_FWD: ("resident", "q"),
                     profile.FLASH_BWD: ("resident", "q")}
    # the forward (for the residuals) and the backward
    assert _kernels(text) == 2, text[:2000]
    for name in (profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV,
                 profile.FLASH_BWD):
        assert _named(text, name) == (name in plans), name


# Latent attention's call (1 x 32 heads, D=128, a second score product 64
# wide on one key a position): every form of it is a kernel (PR 56). At
# 4096 the one-kernel backward held by the k block (`xing29b_1chip`); at
# 8192 held by the q block, the whole-sequence operands in one pipeline
# buffer each and the shared key's gradient summed over the heads in VMEM
# (`kanana30b_1chip`); at 16384 dQ beside a gridded dK/dV; at 32768 all
# three gridded.
_TWO_PRODUCT_FORMS = {
    4096: {profile.FLASH_FWD: ("resident", "q"),
           profile.FLASH_BWD: ("resident", "k")},
    8192: {profile.FLASH_FWD: ("resident", "q"),
           profile.FLASH_BWD: ("resident", "q")},
    16384: {profile.FLASH_FWD: ("resident", "q"),
            profile.FLASH_DQ: ("resident", "q"),
            profile.FLASH_DKV: ("gridded", "k")},
    32768: {profile.FLASH_FWD: ("gridded", "q"),
            profile.FLASH_DQ: ("gridded", "q"),
            profile.FLASH_DKV: ("gridded", "k")}}


@pytest.mark.parametrize("L", sorted(_TWO_PRODUCT_FORMS))
def test_flash_of_two_products_compiles_for_v5e(one_chip, L):
    B, H, D, D2 = 1, 32, 128, 64

    def bwd(q, k, v, q2, k2, g):
        _, vjp = jax.vjp(lambda *a: _flash_shared(
            *a, (D + D2) ** -0.5, True, False), q, k, v, q2, k2)
        return vjp(g)

    bf16 = jnp.bfloat16
    text = _compile(one_chip, bwd, *(((B, H, L, D), bf16),) * 3,
                    ((B, H, L, D2), bf16), ((B, 1, L, D2), bf16),
                    ((B, H, L, D), bf16))
    plans = {name: (p.path, p.held) for backward in (False, True)
             for name, p in flash_plan(B, H, L, D, 1, bf16, backward,
                                       shared_dim=D2).items()}
    assert plans == _TWO_PRODUCT_FORMS[L]
    assert _kernels(text) == len(plans), text[:2000]
    for name in (profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV,
                 profile.FLASH_BWD):
        assert _named(text, name) == (name in plans), name


# Its routed layer: top-22 of 512 over 4096 tokens, 8 experts held, in a
# 1024-wide latent, relu2 experts of width 2688 without a gate: the rows'
# kernels, the grouped matmuls and the activation's between them on a buffer
# of 32768 rows (a token picks an expert once), not of 90112.
def test_latent_routed_layer_compiles_for_v5e(one_chip, monkeypatch):
    from horovod_tpu.parallel import expert

    T, D, R, F, E, k, held = 4096, 4096, 1024, 2688, 512, 22, (0, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fwd_bwd(x, rows, router, bias, w_in, w_out, g):
        out, vjp = jax.vjp(
            lambda x, rows, router, w_in, w_out: expert.moe_ffn(
                x, router, w_in, w_out, capacity_factor=None,
                act=expert.relu2, top_k=k, scoring="sigmoid", bias=bias,
                scale=5.0, held=held, rows=rows)[0],
            x, rows, router, w_in, w_out)
        return out, vjp(g)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compile(one_chip, fwd_bwd, ((T, D), bf16), ((T, R), bf16),
                    ((D, E), f32), ((E,), f32), ((held[1], R, F), f32),
                    ((held[1], F, R), f32), ((T, R), bf16))
    # 2 + 4 grouped matmuls, each rows' kernel forward and backward, and
    # the activation's two between the matmuls
    assert _kernels(text) == 12, text[:2000]
    for name in profile.MOE_GMM_KERNELS + profile.MOE_ROWS_KERNELS \
            + profile.MOE_ACT_KERNELS:
        assert _named(text, name), name
    # the buffer is count x T rows from the dispatch to the combine, never
    # k x T: no [90112, .] array of rows, no slice of one and no fill-up
    assert "[32768,2688]" in text and "[32768,1024]" in text
    assert "[90112,2688]" not in text and "[90112,1024]" not in text
    assert profile.moe_rows_plan(T, k, R, bf16)["path"] == "kernel"


# Its Mamba-2 layer's scan: 64 heads of 64 in 4 groups, a state of 128, 32
# chunks of 128 (jnp: no kernel; the carry is the one loop).
def test_chunked_scan_compiles_for_v5e(one_chip):
    from horovod_tpu.ops.ssd import ssd_scan

    L, H, P, G, N = 4096, 64, 64, 4, 128

    def fwd_bwd(x, dt, a, b, c, g):
        out, vjp = jax.vjp(
            lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, 128)[0],
            x, dt, a, b, c)
        return out, vjp(g)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compile(one_chip, fwd_bwd, ((1, L, H, P), bf16),
                    ((1, L, H), f32), ((H,), f32), ((1, L, G, N), bf16),
                    ((1, L, G, N), bf16), ((1, L, H, P), f32))
    assert _kernels(text) == 0 and profile.SSD in text
    # never an [L, L] array a head
    assert "4096,4096" not in text


# The Kimi-Linear cell's KDA layer: 32 heads of 128 at 8192 tokens in chunks
# of 64 (PR 58; since PR 59 the chunk stage is kernels alone). The operands
# come as the mixer has them, [B, L, H D] (a head's slab is a block of
# columns; the TPU tiles a [.., 32, 128] array by heads, so the 4-D form as a
# PARAMETER would be re-laid): `hvd_kda_wy` and `hvd_kda_wy_bwd` once each (8
# chunks a grid step, four side by side in the inverse's [64, 256] x [256,
# 256] f32 products at full precision, the transposed products of the
# backward, squares placed and taken by slices along the lanes), the own
# blocks' `hvd_kda_scores` and `hvd_kda_scores_bwd` once each in the same
# layout (a [1024, 128] slab of the same [1, 8192, 4096] operand), the
# chunks tied by `hvd_kda_scan` and `hvd_kda_scan_bwd` once each (PR 61: a
# grid step a chunk of 8 heads, the state [8, 128, 128] f32 resident in VMEM
# over a head's 128 chunks, K e^(G_last - G) and W over Q e^G entering
# products by their first axis, the output written and its cotangent read as
# the mixer has them, [1, 8192, 32, 128] tiled by heads: a head a sublane of
# a token's tile), no `while`, no [L, L], libtpu's solve gone, and no copy of
# an activation between the operands and the kernels or between the chunk
# stage's kernels and the scan's.
def test_chunked_kda_compiles_for_v5e(one_chip, monkeypatch):
    import re

    from horovod_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, H, D = 8192, 32, 128
    assert kda.chunk_plan(1, L, H, D, D, 64, 16) == kda.BLOCK_CHUNKS

    def fwd_bwd(q, k, v, g, beta, cot):
        heads = lambda t: t.reshape(1, L, H, D)  # noqa: E731
        out, vjp = jax.vjp(
            lambda q, k, v, g, beta: kda.kda_chunked(
                heads(q), heads(k), heads(v), heads(g), beta,
                chunk=64)[0].reshape(1, L, H * D), q, k, v, g, beta)
        return out, vjp(cot)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compile(one_chip, fwd_bwd, *[((1, L, H * D), bf16)] * 3,
                    ((1, L, H * D), f32), ((1, L, H), f32),
                    ((1, L, H * D), f32))
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    for name in profile.KDA_KERNELS + profile.KDA_SCAN_KERNELS:
        assert len([line for line in calls if re.search(
            r"\b%s/pallas_call" % name, line)]) == 1
    assert _kernels(text) == 6
    assert profile.KDA_CHUNK in text and profile.KDA_CARRY in text
    # the chunks are a kernel's grid: no loop of XLA's under the scan's scope
    assert not [line for line in text.splitlines()
                if profile.KDA_CARRY in line and " while(" in line]
    # never an [L, L] array a head; libtpu's 64-step solve is gone
    assert "8192,8192" not in text
    assert "riangular" not in text and "1,32,128,1,64,64" not in text

    def passes(scope):
        """The `copy` / `transpose` instructions under `scope` that move an
        activation ([8192, 32, 128] elements or more)."""
        found = []
        for line in text.splitlines():
            m = re.search(r"= \w+\[([0-9,]+)\]\S* (copy|transpose)\(", line)
            if m and scope in line and functools.reduce(
                    lambda a, b: a * int(b), m.group(1).split(","),
                    1) >= L * H * D:
                found.append(line.strip()[:160])
        return found

    assert passes(profile.KDA_CHUNK) == []
    assert passes(profile.KDA_CARRY) == []


# The same layer's short convolutions (PR 64): `proj` [1, 8192, 12576] as
# the in-projection's matmul leaves it, of which `hvd_kda_qkv` and
# `hvd_kda_qkv_bwd` read the first 12288 columns through their BlockSpecs (a
# grid step [1024, 512] of each of q, k, v with the 16 rows before it; loops
# over a head's 64 rows with sublane rotations for the taps, a lane reduction
# for a head's norm; the backward's blocks of rows in reverse, the taps'
# gradient resident), once each; q, k, v come out [1, 8192, 4096] bf16 as the
# chunk stage's kernels read them, and no f32 array of an activation's size
# lies anywhere under the scope.
def test_kda_qkv_compiles_for_v5e(one_chip, monkeypatch):
    import re

    from horovod_tpu.ops import kda_conv

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, H, D, C, taps = 8192, 32, 128, 2304, 4
    W = 3 * H * D + 2 * D + H
    plan = profile.kda_conv_plan(1, L, H, D, taps, jnp.bfloat16)
    assert (plan["path"], plan["block_rows"], plan["lane_tiles"]) == (
        "kernel", kda_conv.BLOCK_ROWS, kda_conv.BLOCK_LANES // 128)

    def fwd_bwd(x, w_in, w, *cot):
        def mixer(x, w_in, w):
            with jax.named_scope(profile.KDA_PROJ):
                proj = jnp.einsum("blc,cw->blw", x, w_in)
            with jax.named_scope(profile.KDA_CONV):
                return kda_conv.kda_qkv(proj, w, H, D)

        out, vjp = jax.vjp(mixer, x, w_in, w)
        return out, vjp(cot)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compile(one_chip, fwd_bwd, ((1, L, C), bf16), ((C, W), bf16),
                    ((taps, 3 * H * D), f32), *[((1, L, H * D), bf16)] * 3)
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    for name in profile.KDA_CONV_KERNELS:
        assert len([line for line in calls if re.search(
            r"\b%s/pallas_call" % name, line)]) == 1
    assert _kernels(text) == 2
    under = [line for line in text.splitlines() if profile.KDA_CONV in line]
    assert under
    # no pass of XLA's writes an f32 array of an activation's size under
    # the scope (the parent's backward: four padded f32[1, 8192 + 3, 12288]
    # and more)
    for line in under:
        m = re.search(r"= \(?f32\[([0-9,]+)\]\S* (fusion|copy|transpose|"
                      r"reshape)\(", line)
        assert not m or functools.reduce(
            lambda a, b: a * int(b), m.group(1).split(","),
            1) < L * H * D, line[:200]


# --- a conv layer's in-projection data gradient (PR 66) ---------------------

# The LFM2 cell's conv branch alone, x + GatedShortConv(rms_norm(x)) at
# [2, 8192, 2048] bf16, forward and backward. With the identity where the
# layer holds the cotangent (`bare`: the parent's program) libtpu joins the
# data gradient [16384, 6144] x [6144, 2048] with the norm's backward, one
# fusion of three results: the product, a sum along each of its rows and the
# scale's gradient, a sum across all of them. Held, the product is a plain
# fusion of ONE result and the two reductions a pass of their own under the
# mixer's scope alone (worth 4 ms of the cell's 523 a step: `PERF.md` s6,
# PR 66). If `bare` ever compiles to the plain form too, `_hold_cotangent`
# can go.
@pytest.mark.parametrize("form", ["held", "bare"])
def test_a_conv_layers_data_gradient_is_a_plain_product_when_held(
        one_chip, monkeypatch, form):
    from horovod_tpu import models
    from horovod_tpu.models import transformer

    if form == "bare":
        monkeypatch.setattr(transformer, "_hold_cotangent", lambda h: h)
    B, L, C = 2, 8192, 2048
    cfg = models.TransformerConfig(
        vocab_size=128, num_layers=1, num_heads=32, embed_dim=C,
        max_seq_len=L, conv_taps=3, attention_types=("conv",),
        norm_eps=1e-5, dtype=jnp.bfloat16)
    block = transformer.Block(cfg, transformer.Layer(
        branches=("conv",), norms=("norm1",), out_norms=(None,)))
    x = jax.ShapeDtypeStruct((B, L, C), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip),
        jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype), None)))

    def fwd_bwd(params, x, cot):
        out, vjp = jax.vjp(lambda p, x: block.apply(p, x, None), params, x)
        return out, vjp(cot)

    with jax.default_matmul_precision("default"):
        text = jax.jit(fwd_bwd).lower(params, x, x).compile().as_text()
    products = profile.product_fusions(text)
    # the in-projection's data gradient: the backward product under
    # `in_proj` whose result is an activation, not the weight's gradient
    dgrad = [f for f in products.values()
             if "transpose(" in f["op_name"] and "in_proj" in f["op_name"]
             and "bf16[%d,%d,%d]" % (B, L, C) in f["results"]]
    assert len(dgrad) == 1
    rows, columns = "f32[%d,%d]" % (B, L), "f32[%d]" % C
    scopes = profile.fused_scopes(text, profile.SCONV_SCOPES)
    # the pass that sums dh * x along the rows and across them
    norm_bwd = [name for name, f in scopes.items()
                if f["scope"] == profile.SCONV and not f["mixed"]
                and name.startswith("multiply_reduce_fusion")]
    if form == "held":
        assert dgrad[0]["results"] == ["bf16[%d,%d,%d]" % (B, L, C)]
        assert not any(columns in f["reduces"] for f in products.values())
        assert len(norm_bwd) == 1
        # the new name is the barrier's, which libtpu drops once the fusions
        # are decided: it names no instruction of a compiled program or trace
        assert profile.SCONV_HOLD not in text
    else:
        assert sorted(dgrad[0]["results"]) == sorted(
            [rows, columns, "bf16[%d,%d,%d]" % (B, L, C)])
        assert sorted(dgrad[0]["reduces"]) == sorted([rows, columns])
        assert not norm_bwd


# --- the data-parallel step's gradient all-reduces (PR 25) -----------------

def _lm_step(topo, chips, monkeypatch, **more):
    """`make_train_step` around a small flash-attention LM (`more`: further
    fields of its configuration) on a mesh of the first `chips` described
    devices, with its state as shapes: (step, abstract state, mesh). The
    kernel dispatchers ask for the default backend, which is the CPU here;
    the test steers them, not the program."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import models, parallel
    from horovod_tpu.ops.losses import chunked_softmax_cross_entropy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = models.TransformerConfig(**dict(dict(
        vocab_size=32768, num_layers=2, num_heads=4, embed_dim=512,
        mlp_dim=2048, max_seq_len=512, attention="flash",
        dtype=jnp.bfloat16), **more))
    model = models.Transformer(cfg)
    opt = optax.adam(1e-4)

    def loss_fn(params, batch):
        hid = model.apply({"params": params}, batch["x"], batch["pos"],
                          return_hidden=True)
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"],
            jnp.roll(batch["x"], -1, axis=1), chunk=256)

    mesh = parallel.data_parallel_mesh(devices=topo.devices[:chips])
    step = parallel.make_train_step(loss_fn, opt, mesh)

    def make_state(key):
        params = model.init(key, jnp.zeros((1, 512), jnp.int32))["params"]
        tokens = jnp.zeros((2 * chips, 512), jnp.int32)
        return params, opt.init(params), {"x": tokens, "pos": tokens}

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(mesh.axis_names[0]))
    state = jax.eval_shape(
        jax.jit(make_state, out_shardings=(rep, rep, dat)),
        jax.random.PRNGKey(0))
    return step, state, mesh


def _step_text(step, state):
    with jax.default_matmul_precision("default"):
        return step.lower(*state).compile().as_text()


def test_dp_step_gradient_allreduces_are_asynchronous_on_v5e(
        topo, monkeypatch):
    """Over the four described chips the step is compiled with
    `grad_overlap_options`, and the compiler then issues the large
    gradient all-reduces (the embedding's and the head's, 64 MiB each:
    over the combiner's threshold, so each stays one collective)
    asynchronously (read by `hvd.profile`)."""
    from horovod_tpu.parallel import train

    step, state, mesh = _lm_step(topo, 4, monkeypatch)
    assert train.grad_overlap_options(mesh)
    got = profile.grad_collectives(_step_text(step, state))
    total = got["sync"]["bytes"] + got["async"]["bytes"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state[0]))
    assert total == 4 * n_params + 4  # every f32 leaf once, and the loss
    assert got["async"]["count"] >= 2
    assert got["async"]["bytes"] >= 2 * 4 * 32768 * 512, got
    assert got["async"]["bytes"] > 0.5 * total, got
    # The same step without the options: nothing asynchronous (what every
    # PR before 25 compiled, and what the options are there to change).
    monkeypatch.setattr(train, "grad_overlap_options", lambda *a: {})
    step, state, _ = _lm_step(topo, 4, monkeypatch)
    before = profile.grad_collectives(_step_text(step, state))
    assert before["async"] == {"count": 0, "bytes": 0}
    assert before["sync"]["bytes"] == total


def test_one_device_step_is_compiled_as_before(topo, monkeypatch):
    """With one device on the axis no option is passed: the program text
    equals that of the step built with the rule switched off."""
    from horovod_tpu.parallel import train

    texts = []
    for rule in (train.grad_overlap_options, lambda *a: {}):
        monkeypatch.setattr(train, "grad_overlap_options", rule)
        step, state, mesh = _lm_step(topo, 1, monkeypatch)  # one call site
        assert rule(mesh) == {}
        texts.append(_step_text(step, state))
    assert texts[0] == texts[1]
    # Two a layer: flash forward, and the backward as one kernel.
    assert _kernels(texts[0]) == 4
    for name in (profile.FLASH_FWD, profile.FLASH_BWD):
        assert _named(texts[0], name), name


def test_a_routed_steps_kernel_calls_share_one_lowering_each(topo,
                                                             monkeypatch):
    """Two gated dropless routed layers (every expert held, as OLMoE's) in
    one step: the lowered module holds each DISTINCT kernel call of the
    routed feed-forward once, as a private function every call site calls
    (the grouped matmuls' 6 of 2 x 9: the gate's and the up projection's
    calls share their shapes; the rows' 4 of 2 x 4), where the plain flash
    calls are lowered a call site (2 x 2); the compiled step has them all
    inlined, each under its own site's scope path; and no XLA gather moves
    the [k*T, D] rows under the dispatch and the combine."""
    import re

    k, T, D = 2, 2 * 512, 512
    step, state, _ = _lm_step(
        topo, 1, monkeypatch, mlp_dim=256, moe_experts=8, moe_every=1,
        moe_top_k=k, moe_capacity_factor=None, moe_gated=True)
    assert profile.moe_rows_plan(T, k, D, jnp.bfloat16)["path"] == "kernel"
    with jax.default_matmul_precision("default"):
        lowered = step.lower(*state)
        text = lowered.compile().as_text()
    assert lowered.as_text().count("tpu_custom_call") == 6 + 4 + 2 * 2
    assert _kernels(text) == 2 * (9 + 4 + 2)
    ops = re.findall(r'op_name="([^"]*/pallas_call)"', text)
    parts = {profile.MOE_EXPERTS: profile.MOE_GMM_KERNELS,
             profile.MOE_DISPATCH: profile.MOE_ROWS_KERNELS,
             profile.MOE_COMBINE: profile.MOE_ROWS_KERNELS}
    for layer in range(2):
        for scope, names in parts.items():
            for name in names:  # forward or, transposed, backward
                path = r"\bblock_%d\b.*\b%s/%s\b.*\b%s\)*/pallas_call$" % (
                    layer, profile.MOE, scope, name)
                assert any(re.search(path, op) for op in ops), path
    rows = re.compile(r"\[%d,%d\]\S* gather\(" % (k * T, D))
    moved = [line for line in text.splitlines() if rows.search(line)
             and re.search("%s|%s" % (profile.MOE_DISPATCH,
                                      profile.MOE_COMBINE), line)]
    assert not moved, moved[:2]
