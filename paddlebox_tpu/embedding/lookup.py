"""Device-side sparse pull/push: bucketed all-to-all over the table axis.

Role of the HeterComm data path (``heter_comm_inl.h``):
- pull: ``split_input_to_shard`` → ``walk_to_dest`` → per-shard table get →
  ``walk_to_src`` (heter_comm_inl.h:1628; NVLink-staged P2P in the
  reference) → here one XLA ``all_to_all`` pair over the ICI mesh axis,
  serving ONE contiguous slice ``vals[:, :D+3]`` of the fused record.
- push: ``dynamic_merge_grad`` + ``update_one_table`` (cub sort +
  segment-reduce dedup then in-kernel optimizer, heter_comm.h:69,150) →
  here ONE scatter-add of the grad payload into a per-shard accumulator
  followed by a DENSE vectorized optimizer sweep over the local table
  block. Mathematically identical to dedup-then-update — the accumulator
  carries the per-row gradient SUM and the sweep applies the nonlinear
  optimizer once per touched row — but it lowers to one scatter plus
  streaming elementwise work instead of 3 sorts + 6 gathers + 6 scatters
  (XLA TPU scatter costs ~7 ns/element plus ~5 ms fixed per op; the r02
  layout paid that 6x per step).

Everything is static-shape: per-destination buckets have fixed capacity
``C = ceil(n_unique/num_shards * slack)`` (flags
``embedding_shard_slack`` / ``embedding_unique_frac``); overflow entries
fall into the per-shard trash row. Bucketing is SORT-FREE (one-hot
cumsum ranks in original element order — zero sorts in the whole step),
DEDUPED (duplicate ids share one bucket cell, so pull/push transfer
unique rows only and duplicate grads merge sender-side before the
exchange — roles of dedup_keys_and_fillidx, heter_comm.h:192, and
dynamic_merge_grad, heter_comm.h:69-83, without their radix sorts), and
computed once per step, shared by pull and push (``compute_bucketing``,
which with ``axis=`` also runs the rows all_to_all ONCE for both sides —
3 collectives per width group, not 4 — and builds the one shared argsort
layout the Pallas sorted-stream pull gather / push scatter consume:
``sparse_gather_kernel`` / ``sparse_scatter_kernel``). All functions are
*per-device* bodies meant to run inside ``jax.shard_map`` with the table's
leading dim sharded over ``axis`` and id/grad batches sharded likewise.
With ``num_shards == 1`` (single-chip or replicated-table configs) the
bucketing + all_to_all pair is skipped entirely — pull is one gather and
push is one scatter-add + sweep.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.core import flags
from paddlebox_tpu.embedding.optimizers import SparseAdagrad, SparseOptimizer
from paddlebox_tpu.embedding.table import PassTable, TableConfig


def bucket_capacity(n: int, num_shards: int, slack: Optional[float] = None,
                    unique_frac: Optional[float] = None) -> int:
    """Static per-destination bucket size for n ids over num_shards.

    Mean + 4σ binomial headroom (keys hash ~uniformly across shards), scaled
    by the ``embedding_shard_slack`` flag: overflow probability per bucket is
    ~3e-5 at 4σ, and overflowing entries degrade to a dropped lookup (zeros)
    /dropped grad rather than corruption.

    With dedup enabled (``embedding_dedup``) a bucket cell holds a UNIQUE
    key, so capacity sizes to the expected unique-id count
    ``n * embedding_unique_frac`` instead of the occurrence count — this is
    where dedup turns into an all-to-all byte reduction (the reference gets
    the same effect from transferring d_merged_keys after
    dedup_keys_and_fillidx, heter_comm.h:192).
    """
    if slack is None:
        slack = flags.flag("embedding_shard_slack")
    if unique_frac is None:
        unique_frac = (flags.flag("embedding_unique_frac")
                       if flags.flag("embedding_dedup") else 1.0)
    n_eff = max(min(int(n * unique_frac + 0.999999), n), 1)
    mean = n_eff / num_shards
    c = int(slack * (mean + 4.0 * mean ** 0.5 + 8.0)) + 1
    c = min(max(c, 1), n)
    return -(-c // 8) * 8 if c >= 8 else c


def _bucket_by_shard(dev_rows: jax.Array, num_shards: int, block: int,
                     cap: int, dedup: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Assign ids to per-destination-shard buckets of static capacity.

    Role of split_input_to_shard + fill_shard_key (heter_comm_inl.h:273)
    plus — with ``dedup`` (flag ``embedding_dedup``, default on) —
    dedup_keys_and_fillidx (heter_comm.h:192): only the FIRST occurrence
    of each id consumes a bucket cell; later occurrences map to the same
    (shard, pos) cell, so the pull reply fans back out through the
    existing routing gather and push payloads for duplicates SUM into one
    cell via the existing bucket scatter-add — the pre-exchange merge the
    reference does with dynamic_merge_grad (heter_comm.h:69-83). A hot
    key therefore occupies exactly one cell and can never overflow a
    bucket by repetition; all-to-all bytes scale with UNIQUE ids.

    SORT-FREE, dedup included: destinations rank by one-hot cumsum (no
    argsort), and representatives are found by a scatter-min of the
    element index over the destination-row space (first occurrence = min
    index) — one [R]-scratch scatter-min + two [n] gathers, still zero
    sorts in the whole step (the reference's dedup is 2x cub radix sort,
    heter_comm.h:196-205).

    Returns (send_rows [num_shards, cap] dest-local rows with trash-row
    fill, slot_shard [n], slot_pos [n]) where (slot_shard[j],
    slot_pos[j]) locates element j's bucket cell; slot_pos >= cap marks
    overflow (dropped — reply reads are masked). With dedup, duplicate
    elements share a cell (same id -> same cell, by construction).
    """
    if dedup is None:
        dedup = bool(flags.flag("embedding_dedup"))
    n = dev_rows.shape[0]
    trash = block - 1  # last row of each shard block is the trash row
    shard_of = jnp.clip(dev_rows // block, 0, num_shards - 1
                        ).astype(jnp.int32)
    local_row = (dev_rows % block).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    onehot = (shard_of[:, None]
              == jnp.arange(num_shards, dtype=jnp.int32)[None, :])
    if dedup:
        # Representative (first occurrence) per destination row: the row
        # space is exact (shard * block + local), so there are no hash
        # collisions and the merge is never wrong — the [R] int32 scratch
        # is small next to the [R, W] table it indexes into.
        key = shard_of * block + local_row
        buf = jnp.full((num_shards * block,), n, jnp.int32)
        buf = buf.at[key].min(idx, mode="drop")
        first_idx = buf[key]
        is_first = first_idx == idx
        # Only representatives consume bucket cells.
        onehot = onehot & is_first[:, None]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    pos = jnp.take_along_axis(ranks, shard_of[:, None], axis=1)[:, 0] - 1
    if dedup:
        # Every occurrence adopts its representative's bucket cell.
        pos = pos[first_idx]
    send_rows = jnp.full((num_shards, cap), trash, jnp.int32)
    # Overflow entries (pos >= cap) use an out-of-range column index so the
    # scatter drops them instead of clobbering cell 0. Under dedup,
    # duplicates write the SAME local_row into the same cell — idempotent.
    send_rows = send_rows.at[shard_of, pos].set(local_row, mode="drop")
    return send_rows, shard_of, pos


def _wire_mode() -> str:
    """Wire mode for the pull-reply / push-grad all_to_all payloads
    (``embedding_exchange_dtype``): 'f32' (exact — the default path
    must stay bit-identical, so it exchanges the payload untouched),
    'bf16' (cast sender-side, half the bytes, widened back BEFORE any
    accumulation), or 'int8' (symmetric per-block quantization with f32
    scales riding a second small all_to_all — quarter the payload
    bytes; EQuARX-style: quantize the wire, accumulate in full
    precision). Row/request exchanges are int32 and never cast."""
    mode = flags.flag("embedding_exchange_dtype")
    if mode in ("f32", "bf16", "int8"):
        return mode
    raise ValueError(
        f"unknown embedding_exchange_dtype {mode!r} "
        "(want 'f32'/'bf16'/'int8')")


def _exchange_payload(x: jax.Array, axis: str) -> jax.Array:
    """One f32 payload all_to_all under the configured wire mode.
    f32 mode is the UNTOUCHED pre-flag exchange (bit-exact); reduced
    modes encode sender-side and widen back to f32 receiver-side, so
    whatever the caller accumulates stays full precision."""
    mode = _wire_mode()
    if mode == "f32":
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    if mode == "bf16":
        return lax.all_to_all(
            x.astype(jnp.bfloat16), axis, split_axis=0, concat_axis=0,
            tiled=True).astype(jnp.float32)
    from paddlebox_tpu.multihost.quant import (dequantize_blocked,
                                               quantize_blocked)
    block = int(flags.flag("embedding_quant_block"))
    q, scales = quantize_blocked(x, block)
    recv_q = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                            tiled=True)
    recv_s = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                            tiled=True)
    return dequantize_blocked(recv_q, recv_s, x.shape[-1], block)


def _kernel_mode(flag_name: str) -> Optional[str]:
    """Resolve a sorted-stream kernel flag to 'pallas' / 'interpret' /
    None (XLA). One predicate so the gather and scatter sites — and the
    shared-layout builder that must know whether EITHER will consume a
    sort — can never disagree on what 'auto' means."""
    mode = flags.flag(flag_name)
    if mode in ("pallas", "interpret"):
        return mode
    if mode == "auto" and flags.pallas_kernels_enabled():
        return "pallas"
    return None


def _stream_layout_for(rows: jax.Array, block: int) -> Optional[Tuple]:
    """The shared sorted-stream layout (sorted_gather.sorted_stream_layout
    over the trash-remapped rows) for one width group's pull gather AND
    push scatter — or None when neither kernel is enabled (the argsort
    would be pure cost on the XLA paths). Trash rows (block - 1) are
    remapped past the row bound so both kernels DROP them — the trash
    row's pull columns are zero by contract, so the drop is
    value-identical to gathering it, and the scatter must not pay the
    concentrated padding run (see _accumulate)."""
    if (_kernel_mode("sparse_gather_kernel") is None
            and _kernel_mode("sparse_scatter_kernel") is None):
        return None
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import (
        sorted_stream_layout)
    trash = block - 1
    rows_k = jnp.where(rows == trash, block, rows).astype(jnp.int32)
    return sorted_stream_layout(rows_k, block)


def compute_bucketing(table: PassTable, dev_rows: jax.Array,
                      cap: Optional[int] = None, *,
                      axis: Optional[str] = None) -> Optional[Tuple]:
    """The bucket-by-shard layout for one (table, ids) pair — the ONE
    source of truth for block/cap so a caller sharing the layout between
    pull_local and push_local (both bucket the same dev_rows; computing
    it twice pays the one-hot cumsum + bucket scatter twice per step)
    can never drift from their internal fallback. None when the table is
    unsharded (single-shard paths never bucket) and no kernel layout
    applies.

    ``cap`` overrides the n-based capacity bound — the trainer's
    measured auto-capacity path (FLAGS_embedding_auto_capacity) sizes it
    from the pass data's actual per-shard unique-id maximum. The cap
    rides INSIDE the returned tuple, so pull_local/push_local consuming
    a shared layout always mask with the capacity it was built at —
    capacity cannot drift between the layout and its consumers.

    ``axis`` (the table mesh axis, when called inside shard_map) extends
    the tuple with the OWNER-SIDE shared state: the pull's request
    exchange and the push's row exchange move the SAME ``send_rows``, so
    the rows all_to_all runs ONCE here (3 collectives per width group
    instead of 4), and — when a sorted-stream kernel is enabled — the
    received rows' argsort layout is built ONCE and consumed by both the
    pull gather (CopyForPull) and the push scatter (CopyForPush), so the
    step pays one argsort instead of two. Tuple shapes:

        no axis:   (send_rows, slot_shard, slot_pos, cap)     — legacy
        axis:      (send_rows, slot_shard, slot_pos, cap,
                    recv_rows [S*C], stream_layout | None)
        axis, 1-shard: (None, None, None, None, dev_rows,
                    stream_layout)  — sort sharing only, or None when
                    no kernel is enabled (nothing to share)."""
    block = table.rows_per_shard + 1
    if table.num_shards == 1:
        if axis is None:
            return None
        layout = _stream_layout_for(dev_rows, block)
        if layout is None:
            return None
        return (None, None, None, None, dev_rows, layout)
    if cap is None:
        cap = bucket_capacity(dev_rows.shape[0], table.num_shards)
    bk = _bucket_by_shard(dev_rows, table.num_shards, block, cap)
    if axis is None:
        return bk + (cap,)
    recv_rows = lax.all_to_all(bk[0], axis, split_axis=0, concat_axis=0,
                               tiled=True).reshape(table.num_shards * cap)
    return bk + (cap, recv_rows, _stream_layout_for(recv_rows, block))


def _stream_tier_is(bucketing: Optional[Tuple], tier: int) -> jax.Array:
    """int32 scalar, 1 when the step's shared sorted-stream layout is
    served by ``tier`` (``sorted_gather.stream_tier``), 0 otherwise and
    when no kernel layout is in play."""
    if bucketing is None or len(bucketing) != 6 or bucketing[5] is None:
        return jnp.zeros((), jnp.int32)
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import stream_tier
    return (stream_tier(bucketing[5]) == tier).astype(jnp.int32)


def kernel_hot_served(bucketing: Optional[Tuple]) -> jax.Array:
    """int32 scalar, 1 when a block's run in this step's shared
    sorted-stream layout exceeded the kernels' per-block budget
    (``max_run > UCAP``: a hot row, repeated without dedup — the
    one-chip path hands the raw ids to the kernels) and the kernels
    served it: the pull gather once per distinct row, the push scatter
    in several staging windows. Else 0. The trainer sums it into the
    pass stats next to ``kernel_fallback``."""
    return _stream_tier_is(bucketing, 1)


def kernel_fallback(bucketing: Optional[Tuple]) -> jax.Array:
    """int32 scalar, 1 when a sorted-stream kernel gave way to XLA at
    run time on this step's shared layout: some block was asked for
    more than UCAP DISTINCT rows (``max_distinct_run > UCAP``), which
    the pull gather hands to the XLA gather (its third tier; the push
    scatter serves every run length and never gives way). Else 0, also
    when no kernel layout is in play. The trainer sums it into the pass
    stats next to ``lookup_overflow``."""
    return _stream_tier_is(bucketing, 2)


def exchange_bytes(table: PassTable, n: int,
                   cap: Optional[int] = None) -> int:
    """Static per-device all-to-all bytes for one pull+push round over
    ``n`` ids — the observable that dedup + ``embedding_unique_frac``
    (or a measured ``cap``) shrink (the reference transfers
    d_merged_keys/grads after dedup, heter_comm.h:192; here the byte
    count is a pure function of the static bucket capacity, so trainers
    can report it per step without touching the hot path)."""
    if table.num_shards == 1:
        return 0
    if cap is None:
        cap = bucket_capacity(n, table.num_shards)
    s = table.num_shards
    # Payload bytes follow the wire dtype (embedding_exchange_dtype);
    # the two row exchanges (pull requests shared with push dests via
    # compute_bucketing, so ONE exchange — but exchange_bytes predates
    # the sharing and deliberately reports the pull+push round as two
    # independent halves, each carrying its rows) stay int32. int8
    # payloads count padded values PLUS the per-block f32 scales.
    mode = _wire_mode()
    if mode == "int8":
        from paddlebox_tpu.multihost.quant import quantized_wire_bytes
        block = int(flags.flag("embedding_quant_block"))
        pull = s * cap * 4 + quantized_wire_bytes(
            s * cap, table.pull_width, block)
        push = s * cap * 4 + quantized_wire_bytes(
            s * cap, table.dim + 4, block)
        return pull + push
    esize = 2 if mode == "bf16" else 4
    pull = s * cap * 4 + s * cap * table.pull_width * esize
    push = s * cap * 4 + s * cap * (table.dim + 4) * esize
    return pull + push


def record_exchange_stats(tables, group_n, caps) -> int:
    """Per-pass exchange telemetry: total static per-device all-to-all
    bytes for one pull+push round across all width groups, published
    into the metric registry (``lookup/…``) and as a trace counter so
    the exchange shows up in the pass report AND the timeline. Pure
    host arithmetic over static shapes — never touches the hot path."""
    from paddlebox_tpu.core import monitor, trace
    total = int(sum(exchange_bytes(t, n, cap=c)
                    for t, n, c in zip(tables, group_n, caps)))
    monitor.set_stat("lookup/exchange_bytes_per_step", total)
    monitor.set_gauge("lookup/wire_bits",
                      {"f32": 32.0, "bf16": 16.0,
                       "int8": 8.0}[_wire_mode()])
    trace.counter("lookup/exchange_bytes", per_step=total)
    return total


def _gather_rows(vals: jax.Array, rows: jax.Array, width: int, block: int,
                 layout: Optional[Tuple] = None) -> jax.Array:
    """vals[rows, :width] by the configured backend
    (``sparse_gather_kernel`` flag): the Pallas sorted-stream gather
    (CopyForPull role — the XLA gather is the pull path's dominant op,
    r02 chip run) or the XLA gather. On the kernel path trash rows
    (block - 1: padding/overflow requests) are DROPPED to zeros — the
    trash row's pull columns are zero by contract (apply_accumulated
    keeps them so), so the result is identical while the concentrated
    padding run stays out of the kernel's stream. ``layout`` is
    the shared sorted-stream layout from compute_bucketing (one argsort
    serves this gather and the push scatter)."""
    mode = _kernel_mode("sparse_gather_kernel")
    # Fused records wider than one 128-lane tile cannot stream through
    # the kernel's VMEM blocks — serve them with XLA, and say so.
    wide = mode is not None and vals.shape[-1] > 128
    flags.note_kernel("sparse_gather",
                      "xla:width>128" if wide else mode or "xla")
    if mode is None or wide:
        return vals[rows, :width]
    from paddlebox_tpu.ops.pallas_kernels.sorted_gather import sorted_gather
    trash = block - 1
    rows_k = jnp.where(rows == trash, block, rows).astype(jnp.int32)
    return sorted_gather(rows_k, vals, width=width, layout=layout,
                         interpret=(mode == "interpret"))


def pull_local(table: PassTable, dev_rows: jax.Array, *, axis: str,
               bucketing: Optional[Tuple] = None,
               cap: Optional[int] = None) -> Dict[str, jax.Array]:
    """Per-device pull: ids [n] (device-row space) → {emb [n, D], w [n],
    show [n], click [n], overflow []}. Padding/overflow ids yield the
    trash row (zeros unless polluted — push keeps it zeroed).

    ``overflow`` counts THIS device's real (non-trash) ids that fell past
    their destination bucket's static capacity and degraded to a dropped
    lookup (zeros) — the same positions drop their grads in push_local.
    The capacity contract (`bucket_capacity`): with dedup (default) a
    cell holds a UNIQUE id, so repetition — the realistic skew in CTR
    data, where a hot key can be 30%+ of a batch — cannot overflow a
    BUCKET at all; what remains is uniform-hash spread of unique ids
    (~3e-5 per bucket at default slack, less any margin given away via
    ``embedding_unique_frac``). Overflows remain counted, honest drops
    (contrast: the reference's HeterComm never drops,
    heter_comm_inl.h:273 — it re-walks; we trade bounded drop odds for
    static shapes and expose the count). Single shard: one sliced
    gather, no collective, no bucket and so no possible overflow — but
    also no dedup: the raw ids reach the sorted-stream kernels, which
    serve a hot row's run themselves (``kernel_hot_served``; a request
    is never dropped for repetition).
    """
    num_shards = table.num_shards
    block = table.rows_per_shard + 1
    d = table.dim
    pw = table.pull_width

    if num_shards == 1:
        # Shared sorted-stream layout (compute_bucketing with axis): the
        # push scatter sorts the same dev_rows — one argsort serves both.
        layout = (bucketing[5] if bucketing is not None
                  and len(bucketing) == 6 else None)
        picked = _gather_rows(table.vals, dev_rows, pw, block,
                              layout=layout)
        return {
            "emb": picked[:, :d],
            "w": picked[:, d],
            "show": picked[:, d + 1],
            "click": picked[:, d + 2],
            "overflow": jnp.zeros((1,), jnp.int32),
        }

    n = dev_rows.shape[0]
    trash = block - 1

    # ``bucketing``: the train step computes the bucket-by-shard layout
    # ONCE per width group (compute_bucketing) and shares it between
    # this pull and the matching push — both bucket the SAME dev_rows,
    # so recomputing would pay the layout twice per step for identical
    # results. The shared tuple CARRIES its capacity: masks below must
    # use the capacity the buckets were built at, never a local guess.
    # With axis-extended tuples it also carries the received rows (the
    # push exchanges the same send_rows — one collective, not two) and
    # the owner-side sorted-stream layout for the Pallas kernels.
    recv_rows = layout = None
    if bucketing is None:
        if cap is None:
            cap = bucket_capacity(n, num_shards)
        send_rows, slot_shard, slot_pos = _bucket_by_shard(
            dev_rows, num_shards, block, cap)
    else:
        send_rows, slot_shard, slot_pos, cap = bucketing[:4]
        if len(bucketing) == 6:
            recv_rows, layout = bucketing[4], bucketing[5]
    # Shape [1] (not scalar) so prefix out_specs like P(axis) remain
    # valid for the returned dict under shard_map.
    overflow = jnp.sum(((slot_pos >= cap)
                        & (dev_rows % block != trash)
                        ).astype(jnp.int32)).reshape(1)

    # Exchange requests: recv_req[s, c] = row requested by peer s.
    if recv_rows is None:
        recv_rows = lax.all_to_all(send_rows, axis, split_axis=0,
                                   concat_axis=0, tiled=True
                                   ).reshape(num_shards * cap)
    recv_req = recv_rows.reshape(num_shards, cap)
    # Serve from the local shard block: the fused record's pull payload
    # [emb | w | show | click] is one contiguous slice, so the reply path
    # is a single gather (or the Pallas sorted-stream kernel) + a single
    # collective.
    served = _gather_rows(table.vals, recv_rows, pw, block,
                          layout=layout).reshape(num_shards * cap, pw)
    # Reduced-precision wire (embedding_exchange_dtype): the reply
    # payload is encoded sender-side (bf16 cast / int8 per-block
    # quantize) and widened back to f32 receiver-side; f32 mode takes
    # the untouched path (bit-exact).
    reply = _exchange_payload(served, axis).reshape(num_shards, cap, pw)
    # Route replies back: (slot_shard, slot_pos) are in original element
    # order (sort-free bucketing), so one gather finishes the pull.
    in_cap = slot_pos < cap
    picked = reply[slot_shard, jnp.where(in_cap, slot_pos, 0)]
    picked = jnp.where(in_cap[:, None], picked, 0)
    return {
        "emb": picked[:, :d],
        "w": picked[:, d],
        "show": picked[:, d + 1],
        "click": picked[:, d + 2],
        "overflow": overflow,
    }


def _accumulate(rows: jax.Array, payload: jax.Array, block: int,
                layout: Optional[Tuple] = None) -> jax.Array:
    """zeros([block, AW]).at[rows].add(payload) by the configured backend
    (``sparse_scatter_kernel`` flag): the Pallas sorted-stream kernel
    (CopyForPush role — XLA TPU scatter is the step's dominant cost,
    r02 chip run) or the XLA scatter. Trash-row entries (row == block-1:
    padding/overflow, all-zero or count-only payload) are dropped on the
    kernel path — apply_accumulated re-zeroes the trash row either way,
    and every padding lane on one row is a run the kernel would walk
    for nothing. ``layout`` is the
    shared sorted-stream layout from compute_bucketing (one argsort
    serves this scatter and the pull gather)."""
    mode = _kernel_mode("sparse_scatter_kernel")
    flags.note_kernel("sparse_scatter", mode or "xla")
    if mode is None:
        acc = jnp.zeros((block, payload.shape[-1]), payload.dtype)
        return acc.at[rows].add(payload)
    from paddlebox_tpu.ops.pallas_kernels.sorted_scatter import (
        sorted_scatter_accumulate)
    trash = block - 1
    rows_k = jnp.where(rows == trash, block, rows).astype(jnp.int32)
    acc = sorted_scatter_accumulate(rows_k, payload, block,
                                    interpret=(mode == "interpret"),
                                    layout=layout)
    return acc


def apply_accumulated(vals: jax.Array, acc: jax.Array, *, dim: int,
                      ke: int, block: int,
                      opt: SparseOptimizer) -> jax.Array:
    """Dense optimizer sweep: apply per-row accumulated grads to the fused
    local table block (role of update_one_table's in-kernel optimizer,
    heter_comm.h:150 / optimizer.cuh.h:31).

    ``vals [m, W]`` fused records; ``acc [m, D+4]`` accumulated
    [g_emb(D) | g_w | show | click | count]. Rows with count == 0 are
    untouched (their state — incl. adam beta-pows — must not advance);
    trash rows (local index block-1 of each shard block) keep zero value
    columns regardless.
    """
    m = vals.shape[0]
    g_emb = acc[:, :dim]
    g_w = acc[:, dim]
    touched = acc[:, dim + 3] > 0

    emb = vals[:, :dim]
    w = vals[:, dim]
    show = vals[:, dim + 1]
    click = vals[:, dim + 2]
    emb_state = vals[:, dim + 3:dim + 3 + ke]
    w_state = vals[:, dim + 3 + ke:]

    new_emb, new_emb_st = opt.update_vector(emb, emb_state, g_emb)
    new_w, new_w_st = opt.update_scalar(w, w_state, g_w)
    new_show = show + acc[:, dim + 1]
    new_click = click + acc[:, dim + 2]

    new_vals = jnp.concatenate([
        new_emb, new_w[:, None], new_show[:, None], new_click[:, None],
        new_emb_st, new_w_st], axis=1)
    out = jnp.where(touched[:, None], new_vals, vals)
    # Trash rows: padding/overflow grads land here; keep the PULL columns
    # zeroed so padding pulls keep returning zeros (optimizer state on the
    # trash row may drift — it is never read).
    is_trash = (jnp.arange(m) % block) == (block - 1)
    zero_pull = jnp.concatenate(
        [jnp.zeros((m, dim + 3), out.dtype), out[:, dim + 3:]], axis=1)
    return jnp.where(is_trash[:, None], zero_pull, out)


def push_local(table: PassTable, dev_rows: jax.Array, grad_emb: jax.Array,
               grad_w: jax.Array, shows: jax.Array, clicks: jax.Array, *,
               axis: str, opt: Optional[SparseOptimizer] = None,
               dcn_axis: Optional[str] = None,
               bucketing: Optional[Tuple] = None,
               cap: Optional[int] = None) -> PassTable:
    """Per-device push: scatter-accumulate + dense fused optimizer sweep.

    dev_rows [n]; grad_emb [n, D]; grad_w/shows/clicks [n]. Padding entries
    must carry zero grads (guaranteed upstream because padding ids map to
    the discard segment) — they land in the trash row regardless.

    ``dcn_axis`` (multi-slice): the pass table is sharded over ``axis``
    INSIDE each slice and replicated across slices, so the bucketed
    all_to_all stays on ICI; the per-shard grad accumulator is then
    psum'd once over the slice axis — the single DCN stage — before the
    optimizer sweep, so every slice applies the identical global update
    and replicas stay bit-equal (role of gather_multi_node_grad's
    inter-node allreduce of node-merged grads, ``heter_comm.h:156-172``,
    landed on the dense accumulator instead of a sorted key list because
    the accumulator has the same static shape on every slice).
    """
    if opt is None:
        opt = SparseAdagrad()
    ke = opt.emb_state_width(table.dim)
    kw = opt.w_state_width()
    if table.ke != ke or table.kw != kw:
        raise ValueError(
            f"optimizer {type(opt).__name__} expects state widths "
            f"({ke}, {kw}) but table carries ({table.ke}, {table.kw}) — "
            f"push opt must match the TableConfig.optimizer the table was "
            f"built with")
    num_shards = table.num_shards
    block = table.rows_per_shard + 1
    n = dev_rows.shape[0]
    d = table.dim
    aw = d + 4  # accumulator width: [g_emb | g_w | show | click | count]

    # Payload per id: grads + stats + a count of 1 (the count column marks
    # the row as touched; filler bucket cells carry 0 everywhere).
    payload = jnp.concatenate([
        grad_emb, grad_w[:, None], shows[:, None], clicks[:, None],
        jnp.ones((n, 1), grad_emb.dtype)], axis=-1)

    if num_shards == 1:
        # Shared sorted-stream layout (compute_bucketing with axis): the
        # pull gather sorted the same dev_rows — one argsort for both.
        layout = (bucketing[5] if bucketing is not None
                  and len(bucketing) == 6 else None)
        acc = _accumulate(dev_rows, payload, block, layout=layout)
        if dcn_axis is not None:
            acc = lax.psum(acc, dcn_axis)
        new_vals = apply_accumulated(table.vals, acc, dim=d, ke=ke,
                                     block=block, opt=opt)
        return PassTable(vals=new_vals, rows_per_shard=table.rows_per_shard,
                         num_shards=1, dim=d, ke=ke, kw=kw)

    recv_rows = layout = None
    if bucketing is None:
        if cap is None:
            cap = bucket_capacity(n, num_shards)
        send_rows, slot_shard, slot_pos = _bucket_by_shard(
            dev_rows, num_shards, block, cap)
    else:
        # Shared layout carries its own capacity (compute_bucketing) —
        # and, when axis-extended, the already-exchanged rows (the pull
        # moved the same send_rows) plus the owner-side sort layout.
        send_rows, slot_shard, slot_pos, cap = bucketing[:4]
        if len(bucketing) == 6:
            recv_rows, layout = bucketing[4], bucketing[5]
    send_payload = jnp.zeros((num_shards, cap, aw), payload.dtype)
    # (slot_shard, slot_pos) are in original element order — the payload
    # scatters straight into its bucket cells, no permutation gather.
    # Out-of-range positions (overflow) are dropped by the scatter.
    send_payload = send_payload.at[slot_shard, slot_pos].add(
        payload, mode="drop")

    if recv_rows is None:
        recv_rows = lax.all_to_all(send_rows, axis, split_axis=0,
                                   concat_axis=0, tiled=True
                                   ).reshape(num_shards * cap)
    # Reduced-precision wire (embedding_exchange_dtype): grads merged
    # sender-side in f32 (the bucket scatter-add above), encoded for
    # the exchange only (bf16 cast / int8 per-block quantize), widened
    # back before the owner-side accumulate — accumulation never
    # happens in reduced precision.
    send_flat = send_payload.reshape(num_shards * cap, aw)
    recv_payload = _exchange_payload(send_flat, axis)

    # Owner-side accumulate (role of dynamic_merge_grad): filler cells
    # point at the trash row with all-zero payload, so they are no-ops.
    acc = _accumulate(recv_rows, recv_payload, block, layout=layout)
    if dcn_axis is not None:
        # The one DCN stage: combine each shard's slice-local grad sums
        # across slices (table replicas) before the optimizer applies.
        acc = lax.psum(acc, dcn_axis)
    new_vals = apply_accumulated(table.vals, acc, dim=d, ke=ke,
                                 block=block, opt=opt)
    return PassTable(vals=new_vals, rows_per_shard=table.rows_per_shard,
                     num_shards=num_shards, dim=d, ke=ke, kw=kw)


# ---------------------------------------------------------------------------
# Standalone jitted wrappers (tests + simple trainers). Production train
# steps inline pull_local/push_local into their own shard_map body.
# ---------------------------------------------------------------------------

def make_pull_fn(mesh: Mesh, axis: str = "dp"):
    """Jitted (table, dev_rows) -> pulled dict, table/ids sharded on axis.

    ``P(axis)`` is a pytree prefix: it shards every PassTable leaf's
    leading dim over the table axis.
    """

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis), check_vma=False)
    def pull(table: PassTable, dev_rows: jax.Array):
        return pull_local(table, dev_rows, axis=axis)

    return pull


def make_push_fn(mesh: Mesh, axis: str = "dp",
                 opt: Optional[SparseOptimizer] = None):
    """Jitted sparse-grad apply with table donation."""

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis), check_vma=False)
    def push_sm(table, dev_rows, g_emb, g_w, shows, clicks):
        return push_local(table, dev_rows, g_emb, g_w, shows, clicks,
                          axis=axis, opt=opt)

    return jax.jit(push_sm, donate_argnums=(0,))
