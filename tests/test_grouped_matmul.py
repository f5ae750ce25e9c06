"""The grouped-matmul kernels of the dropless routed feed-forward
(`ops/grouped_matmul.py`) in Pallas' interpreter against `lax.ragged_dot`:
forward and both gradients, groups that cross tiles, empty groups, one group
with nearly every row, rows that need padding to a tile, f32 matrices under
bf16 rows; and the list of visits itself."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import grouped_matmul as gm

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 rows in parts of 8 and 16, so that sizes a CPU test can
    afford cross tiles and parts as 32768 rows cross the real ones."""
    monkeypatch.setattr(gm, "BLOCK_ROWS", 32)
    monkeypatch.setattr(gm, "SUB_ROWS", 8)
    monkeypatch.setattr(gm, "SUB_ROWS_DRHS", 16)  # two parts a tile


CASES = {
    "groups_cross_tiles": (64, 8, 24, [10, 0, 30, 24]),
    "one_group_has_every_row": (50, 16, 8, [0, 0, 50, 0]),
    "groups_end_on_tile_edges": (96, 8, 8, [32, 32, 32]),
    "rows_need_padding": (70, 8, 8, [5, 5, 5, 55]),
    "last_group_empty": (64, 8, 16, [40, 24, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_ragged_dot(small_tiles, case):
    M, K, N, sizes = CASES[case]
    rng = np.random.RandomState(len(case))
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(rng.randn(M, K), jnp.float32)
    rhs = jnp.asarray(rng.randn(len(sizes), K, N), jnp.float32)
    ct = jnp.asarray(rng.randn(M, N), jnp.float32)

    def both(fn):
        return jax.value_and_grad(
            lambda l, r: jnp.sum(fn(l, r) * ct), argnums=(0, 1))(lhs, rhs)

    loss, (d_lhs, d_rhs) = both(
        lambda l, r: gm.grouped_matmul(l, r, sizes, interpret=True))
    want, (w_lhs, w_rhs) = both(
        lambda l, r: lax.ragged_dot(l, r, sizes))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(d_lhs, w_lhs, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(d_rhs, w_rhs, atol=1e-5, rtol=1e-5)
    for g, n in enumerate(np.asarray(sizes)):
        if n == 0:  # an empty group's matrix gets exactly zero
            assert float(jnp.max(jnp.abs(d_rhs[g]))) == 0.0


def test_float32_matrices_under_bfloat16_rows(small_tiles):
    """The parameters stay f32: the kernel rounds a block to the rows'
    dtype, and the matrices' gradient comes back in f32 from the f32
    accumulator (equal to the products of the rounded operands summed in
    f32, not to a bf16 gradient converted afterwards)."""
    rng = np.random.RandomState(1)
    sizes = jnp.asarray([20, 44], jnp.int32)
    lhs = jnp.asarray(rng.randn(64, 16), jnp.bfloat16)
    rhs = jnp.asarray(rng.randn(2, 16, 8), jnp.float32)
    ct = jnp.asarray(rng.randn(64, 8), jnp.bfloat16)
    out, vjp = jax.vjp(
        lambda l, r: gm.grouped_matmul(l, r, sizes, interpret=True), lhs, rhs)
    d_lhs, d_rhs = vjp(ct)
    assert out.dtype == jnp.bfloat16 and d_lhs.dtype == jnp.bfloat16
    assert d_rhs.dtype == jnp.float32
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    rounded = f32(rhs.astype(jnp.bfloat16))
    want = lax.ragged_dot(f32(lhs), rounded, sizes)
    np.testing.assert_allclose(f32(out), want, rtol=1e-2, atol=1e-2)
    want_rhs = jnp.stack([f32(lhs[:20]).T @ f32(ct[:20]),
                          f32(lhs[20:]).T @ f32(ct[20:])])
    np.testing.assert_allclose(d_rhs, want_rhs, rtol=1e-5, atol=1e-5)


def test_visits_by_hand():
    # 4 tiles of 8 rows; groups of 3, 0, 13, 16 rows start at 0, 3, 3, 16:
    # group 0 is in tile 0; group 2 in tiles 0, 1; group 3 in tiles 2, 3.
    starts, group, tile, total = gm.visits(
        jnp.asarray([3, 0, 13, 16], jnp.int32), 32, 8)
    assert list(np.asarray(starts)) == [0, 3, 3, 16, 32]
    assert int(total[0]) == 5
    assert list(np.asarray(group))[:5] == [0, 2, 2, 3, 3]
    assert list(np.asarray(tile))[:5] == [0, 0, 1, 2, 3]
    # the visits past the real ones repeat the last (no block changes)
    assert set(np.asarray(group)[5:]) == {3} and set(np.asarray(tile)[5:]) == {3}
    assert group.shape == (4 + 4,)
    # an empty group is visited once where the kernel must zero what it owns
    _, group, tile, total = gm.visits(
        jnp.asarray([3, 0, 13, 16], jnp.int32), 32, 8, visit_empty=True)
    assert int(total[0]) == 6
    assert list(np.asarray(group))[:6] == [0, 1, 2, 2, 3, 3]


def test_non_tpu_backend_takes_ragged_dot():
    sizes = jnp.asarray([2, 6], jnp.int32)
    lhs = jnp.ones((8, 4), jnp.float32)
    rhs = jnp.stack([jnp.ones((4, 3)), 2 * jnp.ones((4, 3))])
    out = gm.grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(out[:2], 4.0)
    np.testing.assert_allclose(out[2:], 8.0)
    assert "ragged_dot" in str(jax.make_jaxpr(
        lambda l, r: gm.grouped_matmul(l, r, sizes))(lhs, rhs))


def test_the_visits_a_caller_formed_are_the_calls_own(small_tiles):
    """`meta=layer_visits(...)`: the result and both gradients are those of
    the call that forms its visits itself, and off a TPU, with no interpreter
    asked for, there is nothing to form."""
    rng = np.random.RandomState(3)
    sizes = jnp.asarray([5, 0, 17, 9], jnp.int32)
    lhs = jnp.asarray(rng.randn(40, 128), jnp.float32)
    rhs = jnp.asarray(rng.randn(4, 128, 128), jnp.float32)
    g = jnp.asarray(rng.randn(40, 128), jnp.float32)
    meta = gm.layer_visits(sizes, 40, True)
    assert gm.layer_visits(sizes, 40) is None

    def both(meta):
        out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, sizes, True, meta), lhs, rhs)
        live = (jnp.arange(40) < 31)[:, None]
        return (jnp.where(live, out, 0.0),) + vjp(jnp.where(live, g, 0.0))

    for mine, its in zip(both(meta), both(None)):
        np.testing.assert_array_equal(mine, its)
