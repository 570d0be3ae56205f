"""Grouped matrix products over contiguous row segments, as Pallas TPU
kernels whose grids run over the row tiles that hold a live row.

``sizes[e]`` consecutive rows of ``x`` belong to group ``e`` (the sorted
assignments of one held expert, ``parallel/moe.py``); rows past the last
group belong to none. Three products:

- ``grouped_matmul(x, w, sizes)``: ``x[rows of e] @ w[e]``;
- ``grouped_matmul(dy, w, sizes, transpose_w=True)``: ``dy[rows of e] @
  w[e].T``, the rows' cotangent;
- ``grouped_weight_grad(x, dy, sizes, into)``: ``into[e] + x[rows of
  e].T @ dy[rows of e]``, summed into ``into``'s own buffer
  (``input_output_aliases``): a group no row belongs to is not visited and
  its slice is not moved;

and on the last of them ``scatter_add_rows(into, index, rows)``, ``into[
index[r]] += rows[r]``: the rows sorted by index are groups of ``into``'s
row tiles and the sum is a selection matrix's ``grouped_weight_grad``.

The grid's row axis counts *visits*, one for every (row tile, group) pair
that shares a row (``row_tile_schedule``; the count is a run-time value,
as ``num_active_tiles`` of ``jax.experimental.pallas.ops.tpu.megablox``,
which these kernels started from): a tile two groups meet in is visited
twice and each visit keeps its own group's rows. A tile no group reaches
is never read and never written: the rows' products come back zero in
the rows of a visited tile that belong to no group, and **unwritten** in
tiles past the last group, as ``lax.ragged_dot`` leaves them on a TPU.
Such rows are the caller's to drop (``parallel/moe.py`` adds them to no
token).

Operands are taken as they come: bfloat16 at one MXU pass, float32 at
full product precision (``Precision.HIGHEST``), as ``ssd_scan``'s; sums
are float32. Off the TPU (``use_pallas=False``) the same three products
through ``lax.ragged_dot`` / ``lax.ragged_dot_general``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a visit covers. The block-diffusion cell's trips hold 16 groups of
# ~640 rows: every group boundary inside a tile is a second visit of that
# tile, so 128 rows visit ~20% more rows than are live and 512 nearly
# twice as many (PERF.md section 5 has the kernels' times at both).
ROW_TILE = 128
# One operand block of the experts' weights, and one block of their
# gradient's sum, at most (the pipeline holds two of each, and two of the
# sum's as it comes in; a v5e core has 128 MiB of VMEM, 16 of them a
# call's by default).
_WEIGHT_BLOCK_BYTES = 8 * 2 ** 20
_SUM_BLOCK_BYTES = 4 * 2 ** 20
# A float32 value is the sum of three bfloat16 pieces (8 bits of mantissa
# each, 24 in all).
_EXACT_PIECES = 3


def _compiler_params(semantics, operand_blocks, result_block, full):
    """The pipeline holds two of every block (operands' and result's
    bytes) and the body up to three results more (the product, the
    selects); at ``full`` precision the MXU takes each float32 operand
    block as bfloat16 pieces, held beside it. Near the default scoped
    limit the call asks for what that takes."""
    need = (2 * (sum(operand_blocks) + result_block) + 3 * result_block
            + (2 * sum(operand_blocks) if full else 0) + 2 ** 21)
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=need if need > 12 * 2 ** 20 else None)


class RowTileSchedule(NamedTuple):
    """Which (row tile, group) pairs a grouped product visits, in order."""
    offsets: jax.Array      # [groups + 1] first row of each group, then end
    groups: jax.Array       # [bound] the group of visit v
    tiles: jax.Array        # [bound] the row tile of visit v
    visits: jax.Array       # [] how many of the ``bound`` entries count


def row_tile_schedule(sizes: jax.Array, rows: int,
                      tile: int = ROW_TILE) -> RowTileSchedule:
    """The visits of ``rows`` rows in tiles of ``tile``, group by group: a
    group of ``sizes[e] > 0`` rows visits every tile it has a row in, an
    empty group none. At most ``rows / tile + groups - 1`` visits (the
    static ``bound``); ``sum(sizes) <= rows``."""
    if rows % tile:
        raise ValueError(f"{rows} rows are not whole tiles of {tile}")
    sizes = sizes.astype(jnp.int32)
    count = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tile
    held = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    bound = rows // tile + count - 1
    groups = jnp.repeat(jnp.arange(count, dtype=jnp.int32), held,
                        total_repeat_length=bound)
    nth = (jnp.arange(bound, dtype=jnp.int32)
           - (jnp.cumsum(held) - held)[groups])
    return RowTileSchedule(
        jnp.concatenate([jnp.zeros(1, jnp.int32), ends]), groups,
        jnp.clip(first[groups] + nth, 0, rows // tile - 1),
        jnp.sum(held, dtype=jnp.int32))


def _precision(dtype):
    """Full product precision for float32 operands, one pass for
    bfloat16's, whatever ``jax.default_matmul_precision`` is around the
    call: Mosaic refuses bfloat16 operands at another."""
    return (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)


def _own_rows(offsets, groups, tiles, v, tile):
    """[tile, 1] bool: the rows of visit ``v``'s tile that are its
    group's."""
    g = groups[v]
    row = tiles[v] * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _first_of(ids, v):
    """Whether visit ``v`` is the first with its ``ids`` entry (visits of
    one tile, and of one group, are consecutive)."""
    return (v == 0) | (ids[jnp.maximum(v - 1, 0)] != ids[v])


def _divisor_block(n: int, limit: int) -> int:
    """The largest block of ``n`` columns that is ``n`` itself or a
    multiple of 128 dividing it, at most ``limit`` (128 if none is)."""
    if n <= limit:
        return n
    fits = [b for b in range(128, n, 128) if n % b == 0 and b <= limit]
    return max(fits, default=128 if n % 128 == 0 else n)


# -- x[rows of e] @ w[e] ------------------------------------------------------

def _rows_kernel(offsets, groups, tiles, x_ref, w_ref, o_ref, *, tile,
                 transpose_w):
    v = pl.program_id(1)
    y = lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (1 if transpose_w else 0,)), ((), ())),
        precision=_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)
    own = _own_rows(offsets, groups, tiles, v, tile)
    first = _first_of(tiles, v)

    @pl.when(first)
    def _():
        o_ref[...] = jnp.where(own, y, 0.0)

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[...] = jnp.where(own, y, o_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "transpose_w",
                                             "interpret"))
def _grouped_rows_call(offsets, groups, tiles, visits, x, w, *, tile,
                       transpose_w, interpret):
    m, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    bn = _divisor_block(n, max(_WEIGHT_BLOCK_BYTES // (k * w.dtype.itemsize),
                               128))
    w_block = (None, bn, k) if transpose_w else (None, k, bn)
    w_map = ((lambda j, v, offsets, groups, tiles: (groups[v], j, 0))
             if transpose_w else
             (lambda j, v, offsets, groups, tiles: (groups[v], 0, j)))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // bn, visits),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, v, offsets, groups, tiles:
                             (tiles[v], 0)),
                pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec(
                (tile, bn), lambda j, v, offsets, groups, tiles:
                (tiles[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            (tile * k * x.dtype.itemsize, k * bn * w.dtype.itemsize),
            tile * bn * 4, x.dtype == jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(x.size * x.dtype.itemsize * (n // bn)
                            + w.size * w.dtype.itemsize + m * n * 4)),
        interpret=interpret,
    )(offsets, groups, tiles, x, w)


# -- into[e] + x[rows of e].T @ dy[rows of e] ---------------------------------

def _weights_kernel(offsets, groups, tiles, x_ref, dy_ref, into_ref, o_ref,
                    *, tile, pieces):
    v = pl.program_id(2)
    own = _own_rows(offsets, groups, tiles, v, tile)
    # both sides: 0 x NaN is NaN, and a tile's other rows may hold anything
    x = jnp.where(own, x_ref[...], jnp.zeros((), x_ref.dtype))
    rest = jnp.where(own, dy_ref[...], jnp.zeros((), dy_ref.dtype))
    dims = (((0,), (0,)), ((), ()))
    if pieces == 1:
        part = lax.dot_general(x, rest, dims,
                               precision=_precision(x_ref.dtype),
                               preferred_element_type=jnp.float32)
    else:       # float32 dy as the sum of its bfloat16 pieces, one pass each
        part = jnp.zeros(o_ref.shape, jnp.float32)
        for i in range(pieces):
            piece = rest.astype(jnp.bfloat16)
            part += lax.dot_general(x, piece, dims,
                                    precision=lax.Precision.DEFAULT,
                                    preferred_element_type=jnp.float32)
            if i + 1 < pieces:
                rest = rest - piece.astype(jnp.float32)
    first = _first_of(groups, v)

    @pl.when(first)
    def _():
        o_ref[...] = into_ref[...] + part

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _grouped_weights_call(offsets, groups, tiles, visits, x, dy, into, *,
                          tile, interpret):
    m, k = x.shape
    n = dy.shape[1]
    bk, bn = k, n
    while bk * bn * 4 > _SUM_BLOCK_BYTES:
        if bk >= bn and bk % 256 == 0:
            bk //= 2
        elif bn % 256 == 0:
            bn //= 2
        else:
            break

    def sum_map(i, j, v, offsets, groups, tiles):
        return groups[v], i, j
    return pl.pallas_call(
        functools.partial(
            _weights_kernel, tile=tile,
            pieces=1 if x.dtype == dy.dtype else _EXACT_PIECES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k // bk, n // bn, visits),
            in_specs=[
                pl.BlockSpec((tile, bk), lambda i, j, v, offsets, groups,
                             tiles: (tiles[v], i)),
                pl.BlockSpec((tile, bn), lambda i, j, v, offsets, groups,
                             tiles: (tiles[v], j)),
                pl.BlockSpec((None, bk, bn), sum_map)],
            out_specs=pl.BlockSpec((None, bk, bn), sum_map)),
        out_shape=jax.ShapeDtypeStruct(into.shape, jnp.float32),
        input_output_aliases={5: 0},
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            (tile * bk * x.dtype.itemsize, tile * bn * dy.dtype.itemsize,
             bk * bn * 4), bk * bn * 4, x.dtype == jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(x.size * x.dtype.itemsize * (n // bn)
                            + dy.size * dy.dtype.itemsize * (k // bk)
                            + 2 * into.size * 4)),
        interpret=interpret,
    )(offsets, groups, tiles, x, dy, into)


# -- the three products -------------------------------------------------------

def _schedule_for(schedule, sizes, rows, tile):
    return (row_tile_schedule(sizes, rows, tile) if schedule is None
            else schedule)


def _on_kernels(use_pallas, interpret) -> bool:
    """``use_pallas=None``: the kernels on a TPU and in the interpreter."""
    if use_pallas is not None:
        return use_pallas
    from paddlebox_tpu.core import flags
    return interpret or flags.pallas_kernels_enabled()


def _in_a_group(sizes, rows):
    return (jnp.arange(rows) < jnp.sum(sizes))[:, None]


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   transpose_w: bool = False, tile: int = ROW_TILE,
                   schedule: RowTileSchedule | None = None,
                   use_pallas: bool | None = None,
                   interpret: bool = False) -> jax.Array:
    """``x[rows of e] @ w[e]`` (``@ w[e].T`` with ``transpose_w``) for the
    ``sizes[e]`` consecutive rows of each group: ``x`` ``[rows, K]``, ``w``
    ``[groups, K, N]`` (``[groups, N, K]``) of ``x``'s dtype -> ``[rows,
    N]`` float32. Rows past the last group: unwritten in tiles no group
    reaches (the module's note), zero off the TPU. ``schedule``: the
    visits of these ``sizes`` at this ``tile``, for a caller that runs
    several products over them."""
    use_pallas = _on_kernels(use_pallas, interpret)
    if x.dtype != w.dtype:
        raise ValueError(f"operands of {x.dtype} and {w.dtype}: a grouped "
                         "product takes both as they come")
    if not use_pallas:
        y = lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transpose_w else w,
                           sizes.astype(jnp.int32),
                           preferred_element_type=jnp.float32)
        return jnp.where(_in_a_group(sizes, x.shape[0]), y, 0.0)
    s = _schedule_for(schedule, sizes, x.shape[0], tile)
    return _grouped_rows_call(s.offsets, s.groups, s.tiles, s.visits, x, w,
                              tile=tile, transpose_w=transpose_w,
                              interpret=interpret)


def grouped_weight_grad(x: jax.Array, dy: jax.Array, sizes: jax.Array,
                        into: jax.Array, *, tile: int = ROW_TILE,
                        schedule: RowTileSchedule | None = None,
                        use_pallas: bool | None = None,
                        interpret: bool = False) -> jax.Array:
    """``into[e] + x[rows of e].T @ dy[rows of e]``: ``x`` ``[rows, K]``,
    ``dy`` ``[rows, N]`` of one dtype, ``into`` ``[groups, K, N]``
    float32, returned in its own buffer where the caller lets it go (a
    loop's carried sum). Rows past the last group contribute nothing,
    whatever they hold. A bfloat16 ``x`` may meet a float32 ``dy``: then
    ``x``'s entries count as exact (a selection's zeros and ones) and
    ``dy`` goes through the MXU as its three bfloat16 pieces, which is
    every bit of it."""
    use_pallas = _on_kernels(use_pallas, interpret)
    exact = x.dtype == jnp.bfloat16 and dy.dtype == jnp.float32
    if (x.dtype != dy.dtype and not exact) or into.dtype != jnp.float32:
        raise ValueError(f"operands of {x.dtype} and {dy.dtype} into "
                         f"{into.dtype}: one operand type, a float32 sum")
    if not use_pallas:
        live = _in_a_group(sizes, x.shape[0])
        return into + lax.ragged_dot_general(
            jnp.where(live, x, 0).astype(dy.dtype), jnp.where(live, dy, 0),
            sizes.astype(jnp.int32),
            lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            precision=lax.Precision.HIGHEST if exact else None,
            preferred_element_type=jnp.float32)
    s = _schedule_for(schedule, sizes, x.shape[0], tile)
    return _grouped_weights_call(s.offsets, s.groups, s.tiles, s.visits, x,
                                 dy, into, tile=tile, interpret=interpret)


def scatter_add_rows(into: jax.Array, index: jax.Array, rows: jax.Array, *,
                     tile: int = ROW_TILE, use_pallas: bool | None = None,
                     interpret: bool = False) -> jax.Array:
    """``into[index[r]] += rows[r]`` for float32 ``rows`` ``[R, F]`` into
    ``into`` ``[T, F]``, an index twice adding twice; ``index[r] >= T``
    adds nothing, whatever the row holds. XLA's scatter sorts the indices,
    gathers the rows and then adds them one row at a time (1.05 ms for
    8,192 rows of 2048 on a v5e); here the rows sorted by index are the
    groups of ``tile`` consecutive rows of ``into`` and the sum is
    ``grouped_weight_grad`` of a selection matrix (``x[r, index[r] %
    tile] = 1``, exact in bfloat16) with every bit of ``rows``. Shapes
    that are not whole tiles, and callers off the TPU, get XLA's."""
    use_pallas = _on_kernels(use_pallas, interpret)
    t, f = into.shape
    if not use_pallas or t % tile or rows.shape[0] % tile:
        return into.at[index].add(rows, mode="drop")
    order = jnp.argsort(index)
    index = index[order]
    ends = jnp.searchsorted(index, (jnp.arange(t // tile) + 1) * tile)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    select = (index[:, None] % tile == jnp.arange(tile)[None, :]).astype(
        jnp.bfloat16)
    return grouped_weight_grad(
        select, rows[order], sizes, into.reshape(t // tile, tile, f),
        tile=tile, use_pallas=True, interpret=interpret).reshape(t, f)
