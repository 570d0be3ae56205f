"""Sorted-stream pull gather — the CopyForPull-class kernel.

Role of the reference's pull-side CUDA kernels (``box_wrapper.cu``
CopyForPull + the HeterComm per-shard table get): materialize the pull
payload ``table[rows, :pw]`` for a batch of request rows at memory
bandwidth. XLA's TPU gather costs ~6 ns/element regardless of layout
(r02 chip run: 16.2 ms for [426K x 16], 25.4 ms at pull width 40) — two
orders of magnitude off HBM bandwidth for what is a streaming read. This
kernel instead SORTS the requests by destination row (XLA argsort —
cheap, and SHARED with the push-side ``sorted_scatter`` via
``sorted_stream_layout``), streams the table through VMEM one block at a
time via the Pallas pipeline, services each block's contiguous run of
requests with in-VMEM dynamic-row reads into per-block staging slots,
then inverse-permutes the slots back to original request order.

    out = sorted_gather(rows, table, width=pw)
    # == jnp.where(rows[:, None] < num_rows, table[rows, :pw], 0)  (exact)

Requests whose row >= ``num_rows`` are DROPPED (zeros) — callers use
that as the padding/trash sentinel, mirroring the scatter's drop
semantics (the lookup trash row carries zero pull columns, so dropping
is value-identical to gathering it).

Skew: per-block request counts are data-dependent, and on one chip the
rows arrive un-deduplicated (a Zipf(1.2) CTR batch asks 73K of its 426K
requests of one row). A repeated row is the same bytes however often it
is asked for, and the result already ends in a fan-out (one staging-slot
index per request), so three tiers, selected by what the layout shows:

  1. every block's run fits the per-block budget (``max_run <= UCAP``):
     the sorted stream is served as it is;
  2. else every block's run of DISTINCT rows fits
     (``max_distinct_run <= UCAP``): the stream is compacted to its
     distinct rows, the same kernel serves each once, and duplicates
     share a staging slot — a skewed batch is fewer row reads than a
     uniform one;
  3. else (more than UCAP distinct rows asked of one BLOCK-row block:
     tables of a few blocks with tens of thousands of requests) the XLA
     gather, kept as the exactness net.

The kernel itself never reads past its budget. The budget, block size
and DMA alignment constants are the scatter's: the two kernels must
agree for one argsort to serve both (``embedding/lookup.py`` shares the
layout per width group per step).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
    ALIGN, BLOCK, UCAP, WINDOW, block_starts)


def _distinct_rank(sorted_rows: jax.Array, n: int) -> jax.Array:
    """[n] int32: how many distinct rows precede sorted rank s's row —
    its position once every run of equal rows is served once."""
    heads = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (sorted_rows[1:n] != sorted_rows[:n - 1]
                              ).astype(jnp.int32)])
    return jnp.cumsum(heads) - 1


def sorted_stream_layout(rows: jax.Array, num_rows: int) -> Tuple[
        jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The per-(rows, num_rows) sort layout BOTH sorted-stream kernels
    consume: (sorted_rows [n+WINDOW] incl. the sentinel pad, order [n],
    starts [nblocks+1], max_run [], max_distinct_run []). Computing it
    once per width group and passing it to ``sorted_gather`` (pull) and
    ``sorted_scatter_accumulate`` (push) makes the step pay the argsort
    once instead of twice — rows >= num_rows are remapped to the
    one-past-the-last-block sentinel so they sort past every block
    boundary and count toward no block's run (the scatter's exact
    dropped-row convention). ``max_distinct_run`` (the most distinct
    rows any block is asked for) is counted only when ``max_run``
    exceeds the budget; below it ``max_run`` bounds it and stands in."""
    rows = rows.astype(jnp.int32)
    n = rows.shape[0]
    rows_pad = -(-num_rows // BLOCK) * BLOCK
    rows = jnp.where(rows >= num_rows, rows_pad, rows)
    order = jnp.argsort(rows).astype(jnp.int32)
    sorted_rows = jnp.concatenate(
        [rows[order], jnp.full((WINDOW,), rows_pad, jnp.int32)])
    starts = block_starts(sorted_rows, rows_pad // BLOCK)
    max_run = jnp.max(starts[1:] - starts[:-1])

    def count_distinct():
        # distinct rows among sorted ranks [0, i), read at block starts
        before = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            _distinct_rank(sorted_rows, n) + 1])[starts]
        return jnp.max(before[1:] - before[:-1])

    max_distinct_run = lax.cond(max_run > UCAP, count_distinct,
                                lambda: max_run)
    return sorted_rows, order, starts, max_run, max_distinct_run


def stream_tier(layout: Tuple) -> jax.Array:
    """int32 scalar: which of ``sorted_gather``'s tiers serves a layout
    (module docstring) — 0 the stream as it is, 1 its distinct rows (a
    run over the budget, served by the kernels all the same), 2 the XLA
    gather."""
    return ((layout[3] > UCAP).astype(jnp.int32)
            + (layout[4] > UCAP).astype(jnp.int32))


def _kernel(starts_ref, rows_ref, tbl_ref, out_ref, rows_s, sem):
    b = pl.program_id(0)
    lo = starts_ref[b]
    cnt = starts_ref[b + 1] - lo

    # Stage this block's run of request rows into SMEM (read one scalar
    # at a time at a data-dependent index — see sorted_scatter._kernel
    # for why SMEM + the ALIGN'd window): the copy starts at the run's
    # offset rounded down to the tile boundary and the loop skips the
    # `off` leading rows of slack. The rows input is padded by WINDOW so
    # the fixed-size slice never reads out of bounds.
    lo_a = pl.multiple_of((lo // ALIGN) * ALIGN, ALIGN)
    off = lo - lo_a
    dma = pltpu.make_async_copy(rows_ref.at[pl.ds(lo_a, WINDOW)], rows_s,
                                sem)
    dma.start()
    # Staging slots the run does not fill must not leak garbage (the
    # inverse permute only reads filled slots, but zeroing is one cheap
    # VMEM store and keeps interpret/compiled bit-identical); overlaps
    # the rows DMA like the scatter's accumulator zeroing.
    out_ref[:] = jnp.zeros_like(out_ref)
    dma.wait()

    base = b * BLOCK
    pw = out_ref.shape[1]

    def body(j, _):
        r = rows_s[j] - base
        out_ref[pl.ds(j - off, 1), :] = tbl_ref[pl.ds(r, 1), :pw]
        return 0

    lax.fori_loop(off, off + jnp.minimum(cnt, UCAP), body, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sorted_gather_blocks(sorted_rows: jax.Array, table: jax.Array,
                          pw: int, interpret: bool) -> jax.Array:
    """[nblocks * UCAP, pw] staging slots: block b's run of requests
    lands at slots [b*UCAP, b*UCAP + run_len) in sorted order."""
    num_rows, w = table.shape
    nblocks = -(-num_rows // BLOCK)
    starts = block_starts(sorted_rows, nblocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # sorted rows (HBM)
            # The table streams through VMEM one [BLOCK, w] slab at a
            # time — the Pallas pipeline double-buffers the HBM reads,
            # so the random-access gather becomes a sequential sweep.
            # The last block may overhang num_rows; its padding rows are
            # never indexed (rows >= num_rows carry the sort sentinel).
            pl.BlockSpec((BLOCK, w), lambda b, starts: (b, 0)),
        ],
        out_specs=pl.BlockSpec((UCAP, pw), lambda b, starts: (b, 0)),
        scratch_shapes=[
            pltpu.SMEM((WINDOW,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks * UCAP, pw), jnp.float32),
        interpret=interpret,
    )(starts, sorted_rows, table)


def sorted_gather(rows: jax.Array, table: jax.Array, *,
                  width: int = None, interpret: bool = False,
                  layout: Tuple = None) -> jax.Array:
    """``jnp.where(rows[:, None] < num_rows, table[rows, :width], 0)``,
    exactly — via sort + VMEM-streamed block service. rows [n] int32
    (entries >= num_rows yield zeros); table [num_rows, W<=128] float32;
    width <= W selects the leading pull slice. ``layout`` is an optional
    precomputed ``sorted_stream_layout(rows, num_rows)`` (the push
    scatter shares it). A hot row's run is served by the kernel once per
    distinct row; only more than UCAP distinct rows asked of one block
    goes to the XLA gather (module docstring: the three tiers)."""
    n = rows.shape[0]
    num_rows, w = table.shape
    pw = w if width is None else width
    if w > 128:
        raise ValueError(
            f"table width {w} > 128: the kernel streams full fused rows "
            f"through single-tile (128-lane) VMEM blocks; gather wider "
            f"records with the XLA path or split the record")
    if not 0 < pw <= w:
        raise ValueError(f"width {pw} outside (0, {w}]")
    table = table.astype(jnp.float32)
    rows_pad = -(-num_rows // BLOCK) * BLOCK
    nblocks = rows_pad // BLOCK
    if layout is None:
        layout = sorted_stream_layout(rows, num_rows)
    sorted_rows, order, starts = layout[:3]
    if sorted_rows.shape[0] != n + WINDOW or starts.shape[0] != nblocks + 1:
        raise ValueError(
            f"shared layout shapes {sorted_rows.shape[0]}/"
            f"{starts.shape[0]} do not match rows/table "
            f"({n + WINDOW}/{nblocks + 1}) — it was built for different "
            f"(rows, num_rows)")
    srows = sorted_rows[:n]
    blk = jnp.minimum(srows // BLOCK, nblocks)

    def served(stream, rank, stream_starts):
        """The kernel over ``stream`` (ascending rows, sentinel-padded),
        fanned out to request order: sorted rank s reads the staging
        slot of stream position ``rank[s]``."""
        staged = _sorted_gather_blocks(stream, table, pw, interpret)
        # Slot of a stream position: its block's slot base + its rank
        # within the block's run. Sentinel (dropped) entries get the
        # one-past-the-end slot, turned into zeros after the gather.
        nslots = nblocks * UCAP
        slot = blk * UCAP + (rank - stream_starts[blk])
        slot = jnp.where(srows < num_rows, slot, nslots)
        # Inverse permute: order maps sorted rank -> original position,
        # so one small int32 scatter routes every slot index home and
        # the payload moves in a single compact gather.
        idx = jnp.zeros((n,), jnp.int32).at[order].set(slot)
        picked = staged[jnp.minimum(idx, nslots - 1)]
        return jnp.where((idx < nslots)[:, None], picked, 0.0)

    def stream_path():
        return served(sorted_rows, jnp.arange(n, dtype=jnp.int32), starts)

    def distinct_path():
        # Compact the stream to its distinct rows (ascending, sentinel
        # behind them; static shape: at worst all n are distinct).
        # Every member of a run writes the run's row to the run's rank,
        # so the scatter's duplicates agree.
        drank = _distinct_rank(sorted_rows, n)
        drows = jnp.full((n + WINDOW,), rows_pad, jnp.int32
                         ).at[drank].set(srows)
        return served(drows, drank, block_starts(drows, nblocks))

    def xla_path():
        keep = rows < num_rows
        safe = jnp.where(keep, rows, 0)
        return jnp.where(keep[:, None], table[safe, :pw], 0.0)

    return lax.switch(stream_tier(layout),
                      (stream_path, distinct_path, xla_path))
