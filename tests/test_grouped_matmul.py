"""The grouped products of ``ops/pallas_kernels/grouped_matmul.py`` through
the Pallas interpreter, against ``lax.ragged_dot`` and its ``jax.vjp``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddlebox_tpu.ops.pallas_kernels.grouped_matmul import (
    grouped_matmul, grouped_weight_grad, row_tile_schedule,
    scatter_add_rows)

ROWS, K, N, GROUPS, TILE = 64, 32, 48, 4, 8

# sizes of the four groups over 64 rows in tiles of 8
SIZES = {
    "all_rows": (16, 24, 8, 16),
    "fewer_rows": (16, 8, 8, 8),            # whole tiles past the last group
    "ends_inside_a_tile": (10, 20, 5, 9),
    "empty_first": (0, 20, 12, 8),
    "empty_middle": (12, 0, 20, 8),
    "empty_last": (12, 20, 8, 0),
    "nothing_held": (0, 0, 0, 0),
    "boundaries_inside_one_tile": (3, 2, 1, 9),
    "one_group_holds_all": (0, 64, 0, 0),
}
# every case at float32; bfloat16 operands where the masks matter most
CASES = ([(name, jnp.float32) for name in SIZES]
         + [(name, jnp.bfloat16) for name in (
             "all_rows", "ends_inside_a_tile", "empty_middle",
             "boundaries_inside_one_tile")])
IDS = [f"{name}-{jnp.dtype(dtype).name}" for name, dtype in CASES]


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (ROWS, K)).astype(dtype),
            jax.random.normal(ks[1], (GROUPS, K, N)).astype(dtype),
            jax.random.normal(ks[2], (ROWS, N)).astype(dtype),
            jax.random.normal(ks[3], (GROUPS, K, N)))


def _reference(x, w, dy, sizes):
    """``lax.ragged_dot`` and its ``jax.vjp`` on the operands as rounded,
    in float32 at full precision; the cotangent of rows past the last
    group is zero, as the dispatch's is."""
    f32 = jnp.float32
    live = (np.arange(ROWS) < int(sizes.sum()))[:, None]
    with jax.default_matmul_precision("highest"):
        y, back = jax.vjp(lambda x, w: lax.ragged_dot(x, w, sizes),
                          x.astype(f32), w.astype(f32))
        dx, dw = back(jnp.where(live, dy.astype(f32), 0.0))
    return np.where(live, y, 0.0), np.where(live, dx, 0.0), np.asarray(dw)


def _visited_rows(sizes):
    """Rows of the tiles some group reaches: what a product writes."""
    s = row_tile_schedule(sizes, ROWS, TILE)
    seen = np.zeros(ROWS // TILE, bool)
    seen[np.asarray(s.tiles)[:int(s.visits)]] = True
    return np.repeat(seen, TILE)[:, None]


def _close(got, want):
    # float32 operands: full precision both sides. bfloat16 operands:
    # their products are exact in float32, the sums differ by their order
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name,dtype", CASES, ids=IDS)
def test_rows_product_is_ragged_dot_on_the_rows_it_writes(name, dtype):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    x, w, dy, _ = _operands(dtype)
    want, _, _ = _reference(x, w, dy, sizes)
    got = grouped_matmul(x, w, sizes, tile=TILE, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (ROWS, N)
    seen = _visited_rows(sizes)
    # in a visited tile the rows of no group come back zero; a tile no
    # group reaches is not written, and its rows are the caller's to drop
    _close(np.where(seen, got, 0.0), want)
    assert seen[:int(sizes.sum())].all()
    assert np.abs(want).max() > 0 or name == "nothing_held"


@pytest.mark.parametrize("name,dtype", CASES, ids=IDS)
def test_rows_cotangent_is_ragged_dots_transpose(name, dtype):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    x, w, dy, _ = _operands(dtype, seed=1)
    _, want, _ = _reference(x, w, dy, sizes)
    got = grouped_matmul(dy, w, sizes, transpose_w=True, tile=TILE,
                         interpret=True)
    assert got.shape == (ROWS, K)
    _close(np.where(_visited_rows(sizes), got, 0.0), want)
    assert np.abs(want).max() > 0 or name == "nothing_held"


@pytest.mark.parametrize("name,dtype", CASES, ids=IDS)
def test_weights_gradient_is_added_to_the_sum_it_is_handed(name, dtype):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    x, w, dy, into = _operands(dtype, seed=2)
    _, _, want = _reference(x, w, dy, sizes)
    # rows past the last group hold anything: they contribute nothing
    past = (np.arange(ROWS) >= int(sizes.sum()))[:, None]
    got = grouped_weight_grad(jnp.where(past, jnp.nan, x),
                              jnp.where(past, jnp.inf, dy), sizes, into,
                              tile=TILE, interpret=True)
    _close(got, np.asarray(into) + want)
    # a group no row chose: its slice of the sum is the one handed in
    empty = np.asarray(sizes) == 0
    np.testing.assert_array_equal(np.asarray(got)[empty],
                                  np.asarray(into)[empty])


@pytest.mark.parametrize("name", sorted(SIZES))
def test_schedule_lists_each_tile_once_for_each_group_in_it(name):
    sizes = np.asarray(SIZES[name])
    s = row_tile_schedule(jnp.asarray(sizes, jnp.int32), ROWS, TILE)
    ends = np.cumsum(sizes)
    want = [(g, t) for g in range(GROUPS) for t in range(ROWS // TILE)
            if sizes[g] and t * TILE < ends[g]
            and (t + 1) * TILE > ends[g] - sizes[g]]
    n = int(s.visits)
    assert n == len(want) <= ROWS // TILE + GROUPS - 1 == s.groups.shape[0]
    assert list(zip(np.asarray(s.groups)[:n].tolist(),
                    np.asarray(s.tiles)[:n].tolist())) == want
    np.testing.assert_array_equal(np.asarray(s.offsets),
                                  np.concatenate([[0], ends]))


@pytest.mark.parametrize("name", ["ends_inside_a_tile", "empty_middle",
                                  "nothing_held"])
def test_off_the_tpu_the_products_are_xlas(name):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    x, w, dy, into = _operands(jnp.float32, seed=3)
    y, dx, dw = _reference(x, w, dy, sizes)
    with jax.default_matmul_precision("highest"):
        _close(grouped_matmul(x, w, sizes, use_pallas=False), y)
        _close(grouped_matmul(dy, w, sizes, transpose_w=True,
                              use_pallas=False), dx)
        _close(grouped_weight_grad(x, dy, sizes, into, use_pallas=False),
               np.asarray(into) + dw)


def test_operands_of_two_types_and_ragged_tiles_are_refused():
    x, w, dy, into = _operands(jnp.float32)
    sizes = jnp.asarray(SIZES["all_rows"], jnp.int32)
    with pytest.raises(ValueError, match="as they come"):
        grouped_matmul(x.astype(jnp.bfloat16), w, sizes, interpret=True)
    with pytest.raises(ValueError, match="float32 sum"):
        grouped_weight_grad(x, dy, sizes, into.astype(jnp.bfloat16),
                            interpret=True)
    with pytest.raises(ValueError, match="whole tiles"):
        grouped_matmul(x, w, sizes, tile=24, interpret=True)


# -- rows added to the rows an index names ------------------------------------

TOKENS = 32

# which of 32 rows the 64 values go to; TOKENS and above: nowhere
INDEX = {
    "every_row_twice": lambda k: jax.random.permutation(
        k, jnp.tile(jnp.arange(TOKENS), 2)),
    "some_rows_often_some_never": lambda k: jax.random.randint(
        k, (ROWS,), 0, TOKENS) // 5 * 5,
    "a_third_past_the_end": lambda k: jnp.where(
        jax.random.uniform(k, (ROWS,)) < 1 / 3, TOKENS,
        jax.random.randint(k, (ROWS,), 0, TOKENS)),
    "all_past_the_end": lambda k: jnp.full((ROWS,), TOKENS + 3),
    "one_row_takes_all": lambda k: jnp.full((ROWS,), 17),
}


@pytest.mark.parametrize("name", sorted(INDEX))
def test_scatter_add_rows_is_xlas_scatter_add_to_the_last_bit_but_order(name):
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    index = INDEX[name](k[0]).astype(jnp.int32)
    # values of every size: all 24 bits of each must arrive
    values = jax.random.normal(k[1], (ROWS, N)) * jnp.exp(
        4 * jax.random.normal(k[2], (ROWS, 1)))
    into = jax.random.normal(k[2], (TOKENS, N))
    want = np.asarray(into, np.float64)
    for r, t in enumerate(np.asarray(index)):
        if t < TOKENS:
            want[t] += np.asarray(values[r], np.float64)
    # what goes nowhere may hold anything
    past = (index >= TOKENS)[:, None]
    got = scatter_add_rows(into, index, jnp.where(past, jnp.nan, values),
                           tile=TILE, interpret=True)
    # float32 sums of up to 64 terms in another order than float64's
    scale = np.abs(np.asarray(values)).max() + 1
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=64 * 2.0 ** -24 * scale)
    np.testing.assert_allclose(
        np.asarray(scatter_add_rows(into, index, jnp.where(
            past, 0.0, values), use_pallas=False)), want, rtol=0,
        atol=64 * 2.0 ** -24 * scale)


def test_scatter_add_rows_of_ragged_shapes_is_left_to_xla():
    into = jnp.zeros((TOKENS + 3, N))
    values = jnp.ones((ROWS, N))
    index = jnp.arange(ROWS, dtype=jnp.int32) % (TOKENS + 3)
    got = scatter_add_rows(into, index, values, tile=TILE, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(into.at[index].add(values)))
