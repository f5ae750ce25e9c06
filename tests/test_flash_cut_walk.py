"""How the resident flash kernels held by the q block walk a mask by rule
(PR 53): the forward and dQ, whose loops carry their state, take the runs of
a kind in one loop (`flash_attention._walk_runs_merged`); the one-kernel
backward, whose loops carry nothing, takes a cut k block with ONE sub-tile in
sight as that sub-tile (`_walk_cut_runs`, `_cut_k`). The walks themselves,
with no kernel, against the dense mask; and what `flash_plan` counts at the
two benchmark cells' calls. The kernels under the walks are held to the dense
masked softmax in `tests/test_sdar.py` and `tests/test_mellum.py`."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile
from horovod_tpu.ops import BandMask, BlockDiffusionMask

fa = importlib.import_module("horovod_tpu.ops.flash_attention")


def _dense_mask(rule, S):
    return np.asarray(rule.visible(np.arange(S)[:, None],
                                   np.arange(S)[None, :], np))


def _walked(rule, S, bqp, bk, cut_k):
    """What `_walk_cut_runs` visits of every q tile, with a visit that counts
    (into a scratch of its own) in place of the kernel's: per (q tile,
    sub-tile) how often it was visited, how often under the mask pass, and
    how often as part of a `bk`-wide turn."""
    n = S // cut_k

    def tile(qi):
        seen = [jnp.zeros((n,), jnp.int32)] * 3

        def visit(j, masked, width):
            part = width // cut_k
            for i, hit in enumerate((1, int(masked), int(width == bk))):
                seen[i] = lax.dynamic_update_slice(
                    seen[i], lax.dynamic_slice(seen[i], (j,), (part,)) + hit,
                    (j,))

        # The walk's loops carry nothing: unroll them here, by runs whose
        # bounds are numbers (`lax.fori_loop` then calls its body in Python).
        q_lo = qi * bqp
        fa._walk_cut_runs(visit, rule.key_runs(q_lo, bqp, bk, np),
                          rule.key_runs(q_lo, bqp, cut_k, np), bk, cut_k)
        return seen

    with jax.disable_jit():
        rows = [tile(qi) for qi in range(S // bqp)]
    return [np.stack([np.asarray(r[i]) for r in rows]) for i in range(3)]


def _merged(rule, S, bqp, bk):
    """What `_walk_k` visits of every q tile under a rule: per (q tile, k
    block) how often it was visited and how often under the mask pass, and
    the order of the visits (the turn at which each k block came)."""
    n = S // bk

    def visit(j, carry, masked):
        seen, cut, turn, t = carry
        return (seen.at[j].add(1), cut.at[j].add(int(masked)),
                turn.at[j].set(t), t + 1)

    def tile(qi):
        zeros = jnp.zeros((n,), jnp.int32)
        return fa._walk_k(visit, (zeros, zeros, zeros - 1, 0), qi, bqp, bk, n,
                          False, rule)[:3]

    return [np.asarray(x) for x in jax.vmap(tile)(jnp.arange(S // bqp))]


S_BAND, LENGTH_BD = 2048, 1024
RULES = [(BandMask(w), S_BAND) for w in (1, 100, 300, 1024, 4096)] + [
    (BlockDiffusionMask(LENGTH_BD, b), 2 * LENGTH_BD) for b in (4, 128, 512)]
# (positions of a q tile, k block, cut_k): the cells' (128, 512, 256); quarters
# of the k block; a sub-tile wider than the q tile; a q tile wider than the k
# block.
STEPS = [(128, 512, 256), (128, 512, 128), (64, 512, 256), (256, 128, 32)]


@pytest.mark.parametrize("bqp,bk,cut_k", STEPS)
@pytest.mark.parametrize("rule,S", RULES, ids=lambda r: str(r))
def test_the_walk_takes_a_lone_sub_tile_alone(rule, S, bqp, bk, cut_k):
    rule.check(S, bqp, cut_k)
    ratio = bk // cut_k
    visited, masked, wide = _walked(rule, S, bqp, bk, cut_k)
    fine = _dense_mask(rule, S).reshape(S // bqp, bqp, S // cut_k, cut_k)
    some, every = fine.any(axis=(1, 3)), fine.all(axis=(1, 3))
    # every sub-tile with a visible pair is computed, and nothing twice
    assert visited.max() == 1 and np.all(visited >= some)
    # with no mask pass only where every pair is visible
    assert not np.any((visited == 1) & (masked == 0) & ~every)
    # a turn is of a whole k block, but for a cut k block with ONE sub-tile
    # in sight: that sub-tile alone, under the mask pass
    per_block = lambda x: x.reshape(x.shape[0], -1, ratio)  # noqa: E731
    in_sight = per_block(some).sum(axis=2)
    cut = per_block(some & ~every).any(axis=2) | (
        (in_sight > 0) & (in_sight < ratio))
    lone = cut & (in_sight == 1)
    assert np.array_equal(per_block(visited).sum(axis=2),
                          np.where(lone, 1, np.where(in_sight > 0, ratio, 0)))
    assert np.array_equal(per_block(wide).sum(axis=2),
                          np.where(lone, 0, np.where(in_sight > 0, ratio, 0)))
    narrow = (visited == 1) & (wide == 0)
    assert np.array_equal(narrow, some & np.repeat(lone, ratio, axis=1))
    assert np.all(masked[narrow] == 1)
    # the cells' steps meet lone sub-tiles under every rule here, but for
    # diffusion blocks as wide as a k block
    assert lone.any() or (bqp, bk, cut_k) != STEPS[0] or rule[-1] == bk


@pytest.mark.parametrize("bqp,bk", [(128, 512), (64, 256), (256, 128)])
@pytest.mark.parametrize("rule,S", RULES, ids=lambda r: str(r))
def test_the_merged_walk_visits_the_tiles_the_dense_mask_has(rule, S, bqp,
                                                             bk):
    """The forward's and dQ's walk: every k block with a visible pair once,
    the mask pass on those seen in part, in two loops: the whole k blocks
    first, ascending, then the cut ones, ascending."""
    rule.check(S, bqp, bk)
    visited, masked, turn = _merged(rule, S, bqp, bk)
    tiles = _dense_mask(rule, S).reshape(S // bqp, bqp, S // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    assert np.array_equal(visited, some.astype(int))
    # (a noisy tile's own k block is masked whole too where a diffusion
    # block is as wide as both)
    assert np.all(masked >= (some & ~every)) and np.all(masked <= some)
    own_whole = isinstance(rule, BlockDiffusionMask) and (
        rule.block >= max(bqp, bk))
    assert own_whole or np.array_equal(masked, (some & ~every).astype(int))
    for seen, cut, order in zip(visited, masked, turn):
        whole = order[(seen == 1) & (cut == 0)]
        edge = order[(seen == 1) & (cut == 1)]
        assert list(whole) == list(range(len(whole)))
        assert list(edge) == list(range(len(whole), len(whole) + len(edge)))


@pytest.mark.parametrize("rule,tiles,subtiles", [
    (BlockDiffusionMask(4096, 4), (1280, 384, 2816), (2304, 512)),
    (BandMask(1024), (720, 480, 3376), (1200, 720)),
    (BandMask(8192), (2176, 256, 1920), (4224, 384))], ids=str)
def test_flash_plan_counts_tiles_and_sub_tiles_at_the_cells_calls(
        rule, tiles, subtiles, monkeypatch):
    """1 x 32 heads on 4, 8192 positions, D 128 (`sdar30b_1chip`; a window
    and, counted as a band of the length, a full layer of
    `mellum12b_1chip`): the tiles at (128, 512) as before the cut (the
    builders compare them with the dense mask and the roofline's count
    multiplies them); the backward's sub-tiles by the dense mask at (128,
    256): two a k block with a visible pair, but one for a cut k block with
    one sub-tile in sight (two thirds of the block-diffusion rule's cut k
    blocks, half of the band's)."""
    S, group, ratio = 8192, 8, 2
    fine = _dense_mask(rule, S).reshape(S // 128, 128, S // 256, 256)
    some, every = fine.any(axis=(1, 3)), fine.all(axis=(1, 3))
    in_sight = some.reshape(S // 128, -1, ratio).sum(axis=2)
    whole = every.reshape(S // 128, -1, ratio).all(axis=2)
    turns = np.where(in_sight == 1, 1, np.where(in_sight > 0, ratio, 0))
    assert subtiles == (4 * turns.sum(), 4 * turns[~whole].sum())
    for backward in (False, True):
        (name, p), = fa.flash_plan(1, 32, S, 128, group, jnp.bfloat16,
                                   backward, mask=rule).items()
        assert (p.path, p.held, p.block_q, p.block_k) == (
            "resident", "q", 1024, 512), name
        assert (p.tiles_visited, p.tiles_masked, p.tiles_skipped) == tiles
        assert p.vmem_limit_bytes == (52 if backward else 30) * 2 ** 20
        if not backward:  # the forward walks k blocks alone
            assert (p.cut_k, p.subtiles_visited, p.subtiles_masked) == (
                512,) + tiles[:2]
            continue
        assert (p.cut_k, p.subtiles_visited, p.subtiles_masked) == (
            256,) + subtiles
        # in whole k blocks: a lone sub-tile saves the k block's other half
        lone = (ratio * p.tiles_visited - p.subtiles_visited) // (ratio - 1)
        assert ratio * p.tiles_masked - p.subtiles_masked == (
            ratio - 1) * lone and 0 < lone <= p.tiles_masked
    # no cut: the sub-tiles are the tiles
    monkeypatch.setattr(fa, "_CUT_K", 512)
    for backward in (False, True):
        (name, p), = fa.flash_plan(1, 32, S, 128, group, jnp.bfloat16,
                                   backward, mask=rule).items()
        assert p.cut_k == p.block_k == 512
        assert (p.subtiles_visited, p.subtiles_masked) == tiles[:2]


@pytest.mark.parametrize("held,path", [(("k", "q"), "resident"),
                                       ((), "gridded")])
def test_a_kernel_held_by_the_k_block_walks_at_its_own_blocks(held, path,
                                                              monkeypatch):
    """The cut is the one-kernel backward's held by the q block: a kernel
    that holds a k block and walks the q blocks (`_walk_q`), resident or
    gridded, says `cut_k == block_k` and counts its sub-tiles as its tiles,
    as dQ by its own kernel."""
    monkeypatch.setattr(fa, "_BWD_HELD", held)
    rule = BandMask(300)
    budget = fa.RESIDENT_VMEM_BUDGET if held else 2 ** 20
    plans = fa.flash_plan(1, 4, 1024, 128, 2, jnp.bfloat16, True,
                          block_q=256, block_k=512, vmem_budget=budget,
                          mask=rule)
    p = plans[profile.FLASH_BWD if held else profile.FLASH_DKV]
    assert (p.path, p.held, p.cut_k) == (path, "k", 512)
    assert (p.subtiles_visited, p.subtiles_masked) == (
        p.tiles_visited, p.tiles_masked)
    if not held:  # dQ by its own kernel carries its sum: k blocks alone
        dq = plans[profile.FLASH_DQ]
        assert (dq.path, dq.held, dq.cut_k) == ("resident", "q", 512)


@pytest.mark.parametrize("bk,cut,want", [(512, 128, 128), (256, 128, 128),
                                         (128, 128, 128), (64, 128, 64),
                                         (384, 128, 128), (192, 128, 192),
                                         (512, 256, 256), (512, 512, 512)])
def test_cut_k_follows_from_the_plan(bk, cut, want, monkeypatch):
    """`_CUT_K` where the k block is a whole number of them, else the k
    block: never a width the rule's `check` did not pass."""
    monkeypatch.setattr(fa, "_CUT_K", cut)
    monkeypatch.setattr(fa, "_BWD_HELD", ("q",))
    (_, p), = fa.flash_plan(1, 2, 1536, 128, 1, jnp.bfloat16, True,
                            block_q=64, block_k=bk,
                            mask=BandMask(200)).items()
    assert (p.held, p.cut_k) == ("q", want) and p.block_k % p.cut_k == 0
    assert fa.flash_plan(1, 2, 1536, 128, 1, jnp.bfloat16, True,
                         block_q=64, block_k=bk)[profile.FLASH_BWD].cut_k \
        is None
