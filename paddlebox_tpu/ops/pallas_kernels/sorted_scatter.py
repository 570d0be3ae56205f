"""Sorted segment scatter-accumulate — the CopyForPush-class kernel.

Role of the reference's push-side CUDA kernels (``box_wrapper.cu``
CopyForPush + ``heter_comm`` dynamic_merge_grad): merge a batch of
per-occurrence sparse updates into a per-row accumulator at memory
bandwidth. XLA's TPU scatter costs ~7 ns/element regardless of hints
(r02 chip run) — ~55 ms for the DeepFM step's 426K×20 update. This kernel
instead SORTS the updates by destination row (XLA sort — cheap) and
streams the accumulator through VMEM one block at a time, applying each
block's contiguous run of updates with in-VMEM dynamic-row adds.

    acc = sorted_scatter_accumulate(rows, payload, num_rows)
    # == jnp.zeros((num_rows, AW)).at[rows].add(payload)  (exact)

Updates whose row == ``num_rows`` (or anything >= the padded row bound)
are DROPPED — callers use that as the padding/trash sentinel.

Skew: per-block update counts are data-dependent, and on one chip the
rows arrive un-deduplicated, so a hot row's run can be any length (a
Zipf(1.2) CTR batch puts 73K of 426K updates on one row). The staging
buffer is the only static budget; the accumulator block stays resident
in VMEM for the whole grid step, so the kernel walks a run in as many
WINDOW-sized stagings as it has. A block whose run fits the budget (any
uniform block) takes exactly one. Adds happen in sorted order, which is
request order within a row (``jnp.argsort`` is stable): a row's float32
sum is the sequence ``np.add.at`` performs. There is no XLA branch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

# Rows per accumulator block streamed through VMEM. f32 lane padding makes
# a [BLOCK, AW<=128] block cost BLOCK*128*4 bytes of VMEM (~4 MB at 8192).
BLOCK = 8192
# Static per-block budget: the gather's staging slots per block, and
# (with ALIGN) the size of one staging window. Uniform-hash rows give
# ~n/nblocks per block; 4096 covers the binomial tail by orders of
# magnitude — a longer run means a genuinely hot row: the scatter walks
# it in several windows, the gather serves each distinct row once
# (sorted_gather's distinct tier).
UCAP = 4096
# DMA source offsets must be provably tile-aligned (i32 1-D VMEM tiles at
# 1024 elements; f32 2-D at 8 sublanes — 1024 covers both): each block's
# staging copy starts at the run's offset rounded DOWN to ALIGN and the
# window carries ALIGN rows of slack, with the kernel skipping into it.
ALIGN = 1024
WINDOW = UCAP + ALIGN


def block_starts(stream: jax.Array, nblocks: int) -> jax.Array:
    """[nblocks + 1] int32: where each BLOCK-row table block's run
    begins in an ascending row stream (entry b + 1 ends block b's)."""
    boundaries = jnp.arange(nblocks + 1, dtype=jnp.int32) * BLOCK
    return jnp.searchsorted(stream, boundaries).astype(jnp.int32)


def _kernel(starts_ref, rows_ref, payload_ref, acc_ref, rows_s, pay_s,
            sem0, sem1):
    b = pl.program_id(0)
    lo = starts_ref[b]
    cnt = starts_ref[b + 1] - lo

    # Stage this block's run of (row, payload) updates: row ids into SMEM
    # (they are read one scalar at a time at a data-dependent index — VMEM
    # vector loads need tile-aligned offsets Mosaic cannot prove for a
    # dynamic scalar index), payloads into VMEM. The first copy starts at
    # the run's offset rounded down to the tile boundary (ALIGN) — Mosaic
    # requires provably aligned DMA source offsets — and the loop skips
    # the leading rows of slack (fewer than ALIGN); a run longer than
    # the staging buffer continues in further windows, WINDOW (a
    # multiple of ALIGN) apart. Inputs are padded by WINDOW rows so the
    # fixed-size slice never reads out of bounds. A run of <= UCAP
    # updates always fits the first window.
    lo_a = (lo // ALIGN) * ALIGN
    end = lo - lo_a + cnt            # run end, relative to lo_a
    base = b * BLOCK
    aw = acc_ref.shape[1]

    def body(j, _):
        r = rows_s[j] - base
        acc_ref[pl.ds(r, 1), :] += pay_s[pl.ds(j, 1), :aw]
        return 0

    def window(k, _):
        at = pl.multiple_of(lo_a + k * WINDOW, ALIGN)
        dma0 = pltpu.make_async_copy(rows_ref.at[pl.ds(at, WINDOW)],
                                     rows_s, sem0)
        dma1 = pltpu.make_async_copy(payload_ref.at[pl.ds(at, WINDOW), :],
                                     pay_s, sem1)
        dma0.start()
        dma1.start()

        @pl.when(k == 0)
        def _():                     # overlaps the first window's DMAs
            acc_ref[:] = jnp.zeros_like(acc_ref)

        dma0.wait()
        dma1.wait()
        lax.fori_loop(jnp.maximum(lo - at, 0),
                      jnp.minimum(end - k * WINDOW, WINDOW), body, 0)
        return 0

    # An empty block still takes its one window: the zeroing rides it.
    lax.fori_loop(0, jnp.maximum(pl.cdiv(end, WINDOW), 1), window, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sorted_accumulate(sorted_rows: jax.Array, sorted_payload: jax.Array,
                       rows_pad: int, interpret: bool) -> jax.Array:
    npad, aw = sorted_payload.shape
    nblocks = rows_pad // BLOCK
    starts = block_starts(sorted_rows, nblocks)

    # DMA slices must cover full 128-lane tiles: pad the payload's lane
    # dim to the physical width (the HBM buffer is (1,128)-tiled and
    # lane-padded regardless — this only makes the logical shape match
    # so Mosaic accepts the copy; the kernel adds back only aw lanes).
    lanes = 128
    pay_full = jnp.pad(sorted_payload, ((0, 0), (0, lanes - aw)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # sorted rows (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # payload (HBM)
        ],
        out_specs=pl.BlockSpec((BLOCK, aw), lambda b, starts: (b, 0)),
        scratch_shapes=[
            pltpu.SMEM((WINDOW,), jnp.int32),
            pltpu.VMEM((WINDOW, lanes), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_pad, aw), jnp.float32),
        interpret=interpret,
    )(starts, sorted_rows, pay_full)


def sorted_scatter_accumulate(rows: jax.Array, payload: jax.Array,
                              num_rows: int, *,
                              interpret: bool = False,
                              layout=None) -> jax.Array:
    """zeros([num_rows, AW]).at[rows].add(payload), exactly — via sort +
    VMEM-streamed accumulation. rows [n] int32 (entries >= num_rows are
    dropped); payload [n, AW<=128] float32. ``layout`` is an optional
    precomputed ``sorted_gather.sorted_stream_layout(rows, num_rows)``
    so the pull gather and this push scatter share ONE argsort per step.
    Any run length is served by the kernel (a hot row's run is walked in
    several staging windows); a row's sum is the sequential float32 sum
    in request order."""
    n, aw = payload.shape
    if aw > 128:
        raise ValueError(
            f"payload width {aw} > 128: the kernel stages updates in "
            f"single-tile (128-lane) VMEM rows; split wider payloads "
            f"into <=128-wide accumulations")
    rows_pad = -(-num_rows // BLOCK) * BLOCK

    if layout is None:
        # Dropped rows (>= num_rows) are remapped to rows_pad so they
        # sort PAST the last block boundary and fall in no block: left
        # in [num_rows, rows_pad) the last block would walk every
        # padding lane's zero update. Pad by WINDOW so the kernel's
        # fixed-size aligned DMA slices stay in bounds; pad rows use the
        # same drop sentinel.
        remapped = jnp.where(rows >= num_rows, rows_pad, rows)
        order = jnp.argsort(remapped)
        sorted_rows = jnp.concatenate(
            [remapped[order].astype(jnp.int32),
             jnp.full((WINDOW,), rows_pad, jnp.int32)])
    else:
        sorted_rows, order = layout[:2]
        nblocks = rows_pad // BLOCK
        if (sorted_rows.shape[0] != n + WINDOW
                or layout[2].shape[0] != nblocks + 1):
            raise ValueError(
                f"shared layout shapes {sorted_rows.shape[0]}/"
                f"{layout[2].shape[0]} do not match rows/num_rows "
                f"({n + WINDOW}/{nblocks + 1}) — it was built for "
                f"different (rows, num_rows)")
    sorted_payload = jnp.concatenate(
        [payload[order].astype(jnp.float32),
         jnp.zeros((WINDOW, aw), jnp.float32)])
    acc = _sorted_accumulate(sorted_rows, sorted_payload, rows_pad,
                             interpret)[:num_rows]
    # Hand the accumulator out rows-minor — the layout the fused table is
    # stored in and its per-column optimizer sweep reads — converted
    # once, whole. Left to itself XLA:TPU carries the kernel's row-major
    # (8, 128) tiling into the sweep, where every [rows, 1] column slice
    # pads to 128 lanes (10.85 GB of step temporaries instead of 4.32 GB
    # at 4M rows, compiled for the v5e); with the layout alone it sinks
    # the conversion below the column slices, each then a sweep of the
    # padded 2.15 GB buffer (five of 6.5 ms in day_uniform's step; my
    # chip run, PR 26). The XLA branch this function used to end in was
    # such a boundary by accident.
    return lax.optimization_barrier(
        with_layout_constraint(acc, Layout(major_to_minor=(1, 0))))
