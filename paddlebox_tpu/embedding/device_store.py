"""HBM-resident persistent feature store — the device tier.

THE PaddleBox thesis, TPU edition: the reference keeps the sparse table
GPU-resident between passes (AIBox/BoxPS — ``README.md:48``'s
"100B features on GPU boxes"; HeterPS hashtables live in HBM across the
pass loop, ``heter_ps/hashtable.h``) and only exchanges deltas with the
CPU/SSD tiers. Here the persistent value store is ONE fused ``[rows, W]``
float32 array resident in HBM (same column layout as PassTable /
CommonFeatureValue, ``feature_value.h:44``), and the host keeps only the
key → row index (``native/store.cc`` incremental hash — the GPU
hashtable's role moved host-side where it is cheap, so the device side
stays a dense array XLA can gather/scatter at line rate).

Why: a host-RAM store moves every pass's values across the host link
twice (about 600 MB each way for a 4M-key pass). With the device tier,
feed_pass/end_pass move only int32 row indices (~16 MB per 4M-key pass)
— values never leave HBM except for checkpoints. The link's measured
rate is in PERF.md ("Bring-up facts").

Row assignment: append-only, round-robin across shards — key k's dense
row r (from the host index) lives on shard ``r % S`` at slot ``r // S``,
so shards stay balanced as the table grows and rows never move (no
rehash). Each shard block carries one scratch slot (index C) absorbing
padded lanes of bucketed transfers. Capacity doubles by a device-side
reshape+pad when a shard fills. All per-pass device programs have
power-of-two-stable shapes, so steady-state passes reuse compiled code.

Capacity ceiling is HBM; for tables beyond it use the host-RAM
:class:`~paddlebox_tpu.embedding.store.FeatureStore` /
``ShardedFeatureStore`` tiers (same interface) — mirroring the
reference's GPU-mem vs CPU-mem vs SSD tier split.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.core import faults, log, monitor, trace
from paddlebox_tpu.embedding import lifecycle
from paddlebox_tpu.embedding.table import (PassTable, TableConfig,
                                           extract_pass_values_host,
                                           fuse_values_host, lay_fused_host,
                                           plan_shards, table_widths)
from paddlebox_tpu.native import store_py as native_store

_FIELDS = ("emb", "emb_state", "w", "w_state", "show", "click")


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


# ---------------------------------------------------------------------------
# Cached jitted device programs. Keyed by static shape params so
# steady-state passes (stable pow2 sizes) never recompile.
#
# Every program operates on a TUPLE of column-part arrays (`widths` is
# the per-part column split of the fused record W). `fused` placement is
# the 1-tuple (W,) — byte-identical programs to the pre-split store;
# `split`/`host` carve the optimizer-slot columns into a sibling part.
# Gathers serve each part at the same indices and concatenate into the
# FUSED pass block (concat-then-gather == gather-then-concat, so the
# PassTable the trainer sees is bit-identical across placements);
# scatters split the fused block's columns back. The index plumbing and
# the collective count per boundary are unchanged — one request
# all_to_all, one fused-width reply.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _grow_fn(s: int, c_old: int, c_new: int, widths: Tuple[int, ...]):
    def grow_one(v, w):
        v3 = v.reshape(s, c_old + 1, w)
        out = jnp.zeros((s, c_new + 1, w), v.dtype)
        out = out.at[:, :c_old].set(v3[:, :c_old])
        return out.reshape(s * (c_new + 1), w)

    def grow(vs):
        return tuple(grow_one(v, w) for v, w in zip(vs, widths))
    return jax.jit(grow)


def _u32_uniform_device(keys_lo: jax.Array, dim: int, seed32: int,
                        scale: float) -> jax.Array:
    """On-device twin of store._u32_uniform / native pbx_init_uniform —
    bit-exact (32-bit integer ops + f32 arithmetic in the same order)."""
    k = keys_lo.astype(jnp.uint32)[:, None]
    j = jnp.arange(1, dim + 1, dtype=jnp.uint32)[None, :]
    z = k + j * jnp.uint32(0x9E3779B9) + jnp.uint32(seed32)
    z = z ^ (z >> jnp.uint32(16))
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> jnp.uint32(13))
    z = z * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> jnp.uint32(16))
    u = (z >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24))
    return ((jnp.float32(2.0) * u - jnp.float32(1.0))
            * jnp.float32(scale)).astype(jnp.float32)


def _split_cols(block: jax.Array, widths: Tuple[int, ...]):
    """Column-split a fused [n, W] block into the part widths."""
    out, off = [], 0
    for w in widths:
        out.append(lax.slice_in_dim(block, off, off + w, axis=1))
        off += w
    return out


@functools.lru_cache(maxsize=64)
def _append_fn_local(widths: Tuple[int, ...], cap: int, dim: int,
                     seed32: int, scale: float):
    """Masked dynamic-update-slice append of cnt (<= cap) NEW rows at slot
    `start`: rows are BUILT ON DEVICE from 4-byte key hashes (emb columns
    via the shared deterministic init; the state tail from a constant
    template row) — the host transfers cap*4 bytes, not cap*W*4."""
    def upd(vs, keys_lo, template, start, cnt):
        emb = _u32_uniform_device(keys_lo, dim, seed32, scale)
        keep = (jnp.arange(cap) < cnt)[:, None]
        out, off = [], 0
        for v, w in zip(vs, widths):
            rows = jnp.broadcast_to(template[off:off + w], (cap, w))
            if off == 0:
                rows = jnp.concatenate([emb, rows[:, dim:]], axis=1)
            cur = lax.dynamic_slice(v, (start, 0), (cap, w))
            out.append(lax.dynamic_update_slice(
                v, jnp.where(keep, rows, cur), (start, 0)))
            off += w
        return tuple(out)
    return jax.jit(upd, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _append_fn_sharded(mesh: Mesh, axis: str, widths: Tuple[int, ...],
                       cap: int, dim: int, seed32: int, scale: float):
    wsum = sum(widths)

    def body(vs, keys_lo, template, start, cnt):
        emb = _u32_uniform_device(keys_lo.reshape(cap), dim, seed32, scale)
        keep = (jnp.arange(cap) < cnt[0])[:, None]
        tmpl = template.reshape(1, wsum)
        out, off = [], 0
        for v, w in zip(vs, widths):
            rows = jnp.broadcast_to(tmpl[:, off:off + w], (cap, w))
            if off == 0:
                rows = jnp.concatenate([emb, rows[:, dim:]], axis=1)
            cur = lax.dynamic_slice(v, (start[0], 0), (cap, w))
            out.append(lax.dynamic_update_slice(
                v, jnp.where(keep, rows, cur), (start[0], 0)))
            off += w
        return tuple(out)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis),
                                 P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(sm, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _gather_fn_local(widths: Tuple[int, ...], rps: int):
    """vs[*][idx] into a FUSED pass block [rps+1, W]. idx == scratch (the
    store's last row) marks padding/missing lanes — they read zero.
    init_idx/init_vals overlay host-computed init records onto missing
    pass rows (read-only pulls; pads point init_idx at the trash row,
    re-zeroed)."""
    w = sum(widths)

    def gather(vs, idx, init_idx, init_vals):
        scratch = vs[0].shape[0] - 1
        miss = (idx == scratch)[:, None]
        picked = jnp.concatenate(
            [jnp.where(miss, 0.0, v[idx]) for v in vs], axis=1)
        block = jnp.concatenate([picked, jnp.zeros((1, w), picked.dtype)])
        block = block.at[init_idx].set(init_vals)
        return block.at[rps].set(0.0)
    return jax.jit(gather)


@functools.lru_cache(maxsize=64)
def _scatter_fn_local(widths: Tuple[int, ...], rps: int):
    """Write pass block rows back into store parts: vs[p][idx[i]] =
    block[i, part p's columns] for i < rps (pads point idx at the
    scratch slot)."""
    def scatter(vs, block, idx):
        parts = _split_cols(block[:rps], widths)
        return tuple(v.at[idx].set(b) for v, b in zip(vs, parts))
    return jax.jit(scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _gather_fn_sharded(mesh: Mesh, axis: str, s: int, cap: int,
                       widths: Tuple[int, ...], rps: int, store_cap: int):
    w = sum(widths)

    def body(vs, rq, pl, init_idx, init_vals):
        rq2 = rq.reshape(s, cap)
        # rq2[s2, c]: slots I request from store-shard s2. Exchange so
        # each store shard receives its requests, serve, exchange back.
        recv = lax.all_to_all(rq2, axis, split_axis=0, concat_axis=0,
                              tiled=True).reshape(s, cap)
        # Scratch-slot requests (padding / missing keys) serve zeros.
        miss = (recv == store_cap)[..., None]
        served = jnp.concatenate(
            [jnp.where(miss, 0.0, v[recv]) for v in vs], axis=-1)
        reply = lax.all_to_all(
            served.reshape(s * cap, w), axis, split_axis=0,
            concat_axis=0, tiled=True).reshape(s * cap, w)
        block = jnp.zeros((rps + 1, w), served.dtype)
        block = block.at[pl.reshape(s * cap)].set(reply)
        # Read-only pulls: overlay init records for missing keys.
        block = block.at[init_idx.reshape(-1)].set(init_vals)
        # Pads aimed at the trash row are re-zeroed.
        return block.at[rps].set(0.0)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis),
                                 P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(sm)


@functools.lru_cache(maxsize=64)
def _scatter_fn_sharded(mesh: Mesh, axis: str, s: int, cap: int,
                        widths: Tuple[int, ...]):
    w = sum(widths)

    def body(vs, b, sr, ds):
        sr2 = sr.reshape(s, cap)
        payload = b[sr2]                              # [s, cap, w]
        sent = lax.all_to_all(
            payload.reshape(s * cap, w), axis, split_axis=0,
            concat_axis=0, tiled=True)
        recv_dst = lax.all_to_all(ds.reshape(s, cap), axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        idx = recv_dst.reshape(s * cap)
        parts = _split_cols(sent.reshape(s * cap, w), widths)
        return tuple(v.at[idx].set(p) for v, p in zip(vs, parts))
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(sm, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _merge_fn_local(widths: Tuple[int, ...], rps: int):
    """Late half of the split pass build: overlay store rows vs[*][idx[i]]
    at block[place[i]] — the shared-key remainder gather AFTER the
    previous pass's write-back, merged into the early-built block. Pads
    point idx at the scratch row and place at the trash row (re-zeroed),
    so the early-gathered rows elsewhere are untouched."""
    def merge(block, vs, idx, place):
        picked = jnp.concatenate([v[idx] for v in vs], axis=1)
        out = block.at[place].set(picked)
        return out.at[rps].set(0.0)
    return jax.jit(merge, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _merge_fn_sharded(mesh: Mesh, axis: str, s: int, cap: int,
                      widths: Tuple[int, ...], rps: int, store_cap: int):
    w = sum(widths)

    def body(block, vs, rq, pl):
        rq2 = rq.reshape(s, cap)
        recv = lax.all_to_all(rq2, axis, split_axis=0, concat_axis=0,
                              tiled=True).reshape(s, cap)
        miss = (recv == store_cap)[..., None]
        served = jnp.concatenate(
            [jnp.where(miss, 0.0, v[recv]) for v in vs], axis=-1)
        reply = lax.all_to_all(
            served.reshape(s * cap, w), axis, split_axis=0,
            concat_axis=0, tiled=True).reshape(s * cap, w)
        out = block.at[pl.reshape(s * cap)].set(reply)
        return out.at[rps].set(0.0)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(sm, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _fused_boundary_fn_local(widths: Tuple[int, ...], rps_prev: int,
                             rps_next: int):
    """ONE device program for the pass boundary (FLAGS_pass_boundary_
    fuse): the previous pass's EndPass scatter followed by the next
    pass's shared-remainder gather — the gather reads the POST-scatter
    store, so shared keys observe the write-back exactly as the serial
    sequencing guarantees, but the host pays one dispatch, not two.
    Under split placement BOTH parts scatter and serve inside this same
    dispatch — the slot columns update in lockstep with the values."""
    def fused(vs, prev_block, prev_idx, next_block, idx, place):
        parts = _split_cols(prev_block[:rps_prev], widths)
        vs = tuple(v.at[prev_idx].set(p) for v, p in zip(vs, parts))
        picked = jnp.concatenate([v[idx] for v in vs], axis=1)
        nb = next_block.at[place].set(picked)
        return vs, nb.at[rps_next].set(0.0)
    return jax.jit(fused, donate_argnums=(0, 3))


@functools.lru_cache(maxsize=64)
def _fused_boundary_fn_sharded(mesh: Mesh, axis: str, s: int,
                               cap_prev: int, cap_next: int,
                               widths: Tuple[int, ...],
                               rps_prev: int, rps_next: int,
                               store_cap: int):
    w = sum(widths)

    def body(vs, b_prev, sr, ds, b_next, rq, pl):
        # EndPass scatter leg (the _scatter_fn_sharded structure).
        payload = b_prev[sr.reshape(s, cap_prev)]
        sent = lax.all_to_all(
            payload.reshape(s * cap_prev, w), axis, split_axis=0,
            concat_axis=0, tiled=True)
        recv_dst = lax.all_to_all(ds.reshape(s, cap_prev), axis,
                                  split_axis=0, concat_axis=0, tiled=True)
        idx_w = recv_dst.reshape(s * cap_prev)
        parts = _split_cols(sent.reshape(s * cap_prev, w), widths)
        vs = tuple(v.at[idx_w].set(p) for v, p in zip(vs, parts))
        # Remainder-gather leg (the _merge_fn_sharded structure) over
        # the post-scatter values.
        recv = lax.all_to_all(rq.reshape(s, cap_next), axis, split_axis=0,
                              concat_axis=0, tiled=True).reshape(s,
                                                                 cap_next)
        miss = (recv == store_cap)[..., None]
        served = jnp.concatenate(
            [jnp.where(miss, 0.0, v[recv]) for v in vs], axis=-1)
        reply = lax.all_to_all(
            served.reshape(s * cap_next, w), axis, split_axis=0,
            concat_axis=0, tiled=True).reshape(s * cap_next, w)
        nb = b_next.at[pl.reshape(s * cap_next)].set(reply)
        return vs, nb.at[rps_next].set(0.0)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis),) * 7,
                       out_specs=(P(axis), P(axis)), check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 4))


@functools.lru_cache(maxsize=64)
def _decay_fn(d: int, decay: float):
    def dec(v):
        sc = v[:, d + 1:d + 3] * decay
        return jnp.concatenate([v[:, :d + 1], sc, v[:, d + 3:]], axis=1)
    return jax.jit(dec, donate_argnums=(0,))


class DeviceFeatureStore:
    """FeatureStore-compatible persistent tier living in device HBM."""

    shared = False

    def __init__(self, config: TableConfig, *, mesh: Optional[Mesh] = None,
                 table_axis: str = "dp", seed: int = 0,
                 capacity_hint: int = 0):
        self.config = config
        from paddlebox_tpu.core import flags
        from paddlebox_tpu.embedding.optimizers import make_sparse_optimizer
        self.opt = make_sparse_optimizer(config)
        self.dim, self.ke, self.kw = table_widths(config)
        self.width = self.dim + 3 + self.ke + self.kw
        self.mesh = mesh
        self.axis = table_axis
        self.num_shards = (int(mesh.shape[table_axis])
                           if mesh is not None else 1)
        self._sharding = (NamedSharding(mesh, P(table_axis))
                          if mesh is not None else None)
        # FLAGS_table_slot_placement: where the per-row optimizer-slot
        # columns live. 'fused' is the historic single [rows, W] record;
        # 'split' carves emb_state/w_state into a sibling [rows, Ke+Kw]
        # part (hot array holds exactly (D+3)*4 bytes/row); 'host'
        # additionally pins that part to host memory — HBM then holds
        # values, not values×slots, with transient crossings around the
        # boundary programs. An optimizer without slot columns has
        # nothing to carve, so it degrades to fused.
        placement = str(flags.flag("table_slot_placement"))
        if placement not in ("fused", "split", "host"):
            raise ValueError("table_slot_placement must be "
                             f"fused|split|host, got {placement!r}")
        slot_w = self.ke + self.kw
        if slot_w == 0:
            placement = "fused"
        self.placement = placement
        self._widths = ((self.width,) if placement == "fused"
                        else (self.dim + 3, slot_w))
        self._part_shardings = self._resolve_part_shardings()
        self._index = native_store.KeyIndex()
        if capacity_hint:
            self._index.reserve(capacity_hint)
        s = self.num_shards
        self._cap = _pow2(max(1 << 10, -(-int(capacity_hint) // s)))
        self._parts = self._place_parts(tuple(
            jnp.zeros((s * (self._cap + 1), w), jnp.float32)
            for w in self._widths))
        self._seed = int(seed)
        # Serializes mutations of (_index, _parts, _cap, _dirty_parts).
        # NOT reentrant: public methods lock, _*_locked helpers assume it.
        self._lock = threading.Lock()
        self._dirty_parts: List[np.ndarray] = []
        self._shrunk_since_base = False
        # Per-row unseen-days age aligned with dense row ids (host side,
        # like the key index — the HBM record is untouched): bumped by
        # shrink, zeroed by any write-back of the row's key.
        self._unseen = np.zeros((0,), np.int32)

    # -- plumbing ----------------------------------------------------------

    def _resolve_part_shardings(self) -> Tuple:
        """Persistent placement per part: device sharding for the hot
        part; 'host' pins the slot part to the backend's host memory
        kind (pinned_host on TPU; CPU backends expose unpinned_host,
        which IS their default memory — the placement is then a no-op
        byte-wise but exercises the same code path)."""
        if self.placement != "host":
            return tuple(self._sharding for _ in self._widths)
        from jax.sharding import SingleDeviceSharding
        from paddlebox_tpu.parallel.zero import _resolve_host_kind
        if self.mesh is not None:
            kind = _resolve_host_kind(self.mesh, "pinned_host")
            slot_sh = NamedSharding(self.mesh, P(self.axis),
                                    memory_kind=kind)
            return (self._sharding, slot_sh)
        dev = jax.devices()[0]
        try:
            kinds = {m.kind for m in dev.addressable_memories()}
        except Exception:
            kinds = set()
        kind = ("pinned_host" if "pinned_host" in kinds
                else "unpinned_host" if "unpinned_host" in kinds else None)
        slot_sh = (SingleDeviceSharding(dev, memory_kind=kind)
                   if kind is not None else None)
        return (None, slot_sh)

    def _place(self, arr):
        if self._sharding is not None:
            return jax.device_put(arr, self._sharding)
        return arr

    def _place_parts(self, parts) -> Tuple:
        return tuple(
            jax.device_put(p, sh) if sh is not None else p
            for p, sh in zip(parts, self._part_shardings))

    def _compute_parts(self) -> Tuple:
        """Parts staged for a jitted device program. 'host' placement
        pays its transient HBM crossing here (slot part host -> device);
        other placements pass through untouched."""
        if self.placement != "host":
            return self._parts
        dev_sh = (self._sharding if self._sharding is not None
                  else jax.devices()[0])
        return (self._parts[0],) + tuple(
            jax.device_put(p, dev_sh) for p in self._parts[1:])

    def _settle_parts(self, parts) -> Tuple:
        """Inverse of :meth:`_compute_parts`: stream mutated parts back
        to their persistent placement (slot part device -> host)."""
        if self.placement != "host":
            return tuple(parts)
        return (parts[0],) + tuple(
            jax.device_put(p, sh)
            for p, sh in zip(parts[1:], self._part_shardings[1:]))

    @property
    def num_features(self) -> int:
        return self._index.size

    def memory_stats(self) -> Dict[str, object]:
        """Measured per-device memory bytes of the live store arrays
        (actual shardings + memory kinds, not flag arithmetic), split
        hot vs slot columns; also lands the table/*_hbm_bytes gauges the
        benches record. Under 'fused' the slot share is the column
        fraction of the one array; under 'host' on TPU the slot part is
        in host memory and measures 0 HBM bytes."""
        from paddlebox_tpu.parallel.zero import tree_hbm_bytes_per_device
        with self._lock:
            parts = self._parts
        if self.placement == "fused":
            total = tree_hbm_bytes_per_device(parts[0])
            hot = total * (self.dim + 3) // self.width
            slot = total - hot
        else:
            hot = tree_hbm_bytes_per_device(parts[0])
            slot = tree_hbm_bytes_per_device(parts[1:])
        stats = {"hot_hbm_bytes": int(hot), "slot_hbm_bytes": int(slot),
                 "placement": self.placement}
        monitor.set_gauge("table/hot_hbm_bytes", float(hot))
        monitor.set_gauge("table/slot_hbm_bytes", float(slot))
        return stats

    def _ensure_capacity_locked(self, total_rows: int) -> None:
        s = self.num_shards
        need = -(-total_rows // s)
        if need <= self._cap:
            return
        c_new = self._cap
        while c_new < need:
            c_new *= 2
        log.vlog(1, "device store grow: %d -> %d slots/shard",
                 self._cap, c_new)
        self._parts = self._place_parts(
            _grow_fn(s, self._cap, c_new, self._widths)(
                self._compute_parts()))
        self._cap = c_new

    def _host_init_fused(self, keys: np.ndarray) -> np.ndarray:
        """[n, W] fused init record for brand-new keys (deterministic
        per-key init — store.py pull_for_pass contract)."""
        n = keys.shape[0]
        d = self.dim
        out = np.zeros((n, self.width), np.float32)
        out[:, :d] = native_store.init_uniform(keys, d, self._seed,
                                               self.config.init_scale)
        out[:, d + 3:d + 3 + self.ke] = self.opt.init_emb_state(n, d)
        out[:, d + 3 + self.ke:] = self.opt.init_w_state(n)
        return out

    def ensure_rows(self, keys: np.ndarray) -> np.ndarray:
        """Find-or-create store rows for (deduped, nonzero) keys; new keys
        are initialized on device. Returns dense rows [n]."""
        with self._lock:
            return self._ensure_rows_locked(keys)

    def _ensure_rows_locked(self, keys: np.ndarray) -> np.ndarray:
        with trace.span("store/ensure_rows", keys=int(np.size(keys))):
            k = np.ascontiguousarray(keys, np.uint64)
            base = self._index.size
            if (base == 0 and k.size
                    and native_store.is_sorted_unique_nonzero(k)):
                # Fresh-build bypass (sorted-run store build, round 13):
                # pass-key arrays arrive sorted unique (dedup_keys /
                # run-merge output), so the first build skips the serial
                # find-or-insert walk — bulk placement parallelizes and the
                # rows (0..n-1 in input order) are bit-identical to upsert
                # on an empty index.
                rows = self._index.bulk_build(k)
                self._append_rows_locked(k, 0, int(k.size))
                monitor.add("device_store/new_keys", int(k.size))
                monitor.add("device_store/bulk_builds", 1)
                return rows
            rows, n_new = self._index.upsert(k)
            if n_new:
                new_keys = k[rows >= base]
                # upsert assigns new rows in input order, so new_keys (input
                # order) aligns with rows base..base+n_new-1.
                self._append_rows_locked(new_keys, base, n_new)
                monitor.add("device_store/new_keys", int(n_new))
            return rows

    @property
    def _template_row(self) -> np.ndarray:
        """[W] constant init record tail: emb columns are overwritten on
        device by the per-key hash; w/show/click zero; optimizer-state
        columns from the optimizer's init pattern (constant per column)."""
        t = getattr(self, "_template_cache", None)
        if t is None:
            t = np.zeros((self.width,), np.float32)
            d = self.dim
            t[d + 3:d + 3 + self.ke] = self.opt.init_emb_state(1, d)[0]
            t[d + 3 + self.ke:] = self.opt.init_w_state(1)[0]
            self._template_cache = t
        return t

    def _append_rows_locked(self, new_keys: np.ndarray, base: int,
                            n_new: int) -> None:
        """Initialize dense rows [base, base+n_new) for new_keys —
        per-shard contiguous slot ranges, so a masked dynamic-update-slice,
        not a scatter; only 4 bytes/key cross to the device (rows are
        built there from the key hash + a constant template)."""
        s = self.num_shards
        w = self.width
        seed32 = self._seed & 0xFFFFFFFF
        scale = float(self.config.init_scale)
        # New rows start at age 0 (inserted FOR a pass = just seen).
        self._unseen = np.concatenate(
            [self._unseen, np.zeros((n_new,), np.int32)])
        lo = (new_keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        if s == 1:
            cap = _pow2(n_new)
            # The pow2-padded DUS window [base, base+cap) must fit inside
            # the slot region — dynamic_update_slice CLAMPS an
            # out-of-bounds start, which would silently shift the write.
            self._ensure_capacity_locked((base + cap) * s)
            keys_pad = np.zeros((cap,), np.uint32)
            keys_pad[:n_new] = lo
            self._parts = self._settle_parts(_append_fn_local(
                self._widths, cap, self.dim, seed32, scale)(
                self._compute_parts(), jnp.asarray(keys_pad),
                jnp.asarray(self._template_row), base, n_new))
            return
        rows = np.arange(base, base + n_new)
        shard = rows % s
        counts = np.bincount(shard, minlength=s)
        cap = _pow2(int(counts.max()))
        start_min_per_shard = base // s
        self._ensure_capacity_locked((start_min_per_shard + cap + 1) * s)
        keys_pad = np.zeros((s, cap), np.uint32)
        starts = np.zeros((s,), np.int32)
        for sh in range(s):
            sel = shard == sh
            if sel.any():
                starts[sh] = rows[sel][0] // s
                keys_pad[sh, :int(counts[sh])] = lo[sel]
        kd = jax.device_put(keys_pad, self._sharding)
        tmpl = jax.device_put(
            np.broadcast_to(self._template_row, (s, w)).copy(),
            self._sharding)
        st = jax.device_put(starts, self._sharding)
        cn = jax.device_put(counts.astype(np.int32), self._sharding)
        self._parts = self._settle_parts(_append_fn_sharded(
            self.mesh, self.axis, self._widths, cap,
            self.dim, seed32, scale)(
            self._compute_parts(), kd, tmpl, st, cn))

    # -- pass build / write-back (the hot per-pass surface) ----------------

    def pull_pass_table(self, pass_keys_sorted: np.ndarray,
                        num_pass_shards: int, *, readonly: bool = False
                        ) -> Tuple[PassTable, np.ndarray]:
        """Build the per-pass device table by an on-device gather from the
        resident store (role of BuildPull + BuildGPUTask,
        ps_gpu_wrapper.cc:362,684 — zero host value traffic). Returns
        (table, dense store rows aligned to the sorted keys).

        ``readonly=True`` (eval passes, SetTestMode role): unknown keys
        are NOT inserted — their pass rows carry the deterministic init
        record via an overlay, and the store is left untouched; the
        returned rows have -1 at missing keys."""
        faults.faultpoint("device_store/pull")
        with self._lock:
            monitor.add("device_store/boundary_progs", 1)
            return self._pull_pass_table_locked(pass_keys_sorted,
                                                num_pass_shards,
                                                readonly=readonly)

    def pull_pass_table_partial(self, pass_keys_sorted: np.ndarray,
                                num_pass_shards: int, *,
                                select: np.ndarray,
                                readonly: bool = False
                                ) -> Tuple[PassTable, np.ndarray]:
        """EARLY half of the split pass build (role of the overlapped
        BuildPull threads, ps_gpu_wrapper.cc:907, on the HBM tier):
        gather only the ``select`` pass positions — the keys the active
        pass cannot dirty (it writes back only its own key set) — while
        it still trains. Non-selected positions read zero until
        :meth:`merge_pass_rows` / the fused boundary fills them in.
        Unseen keys are inserted here too (``readonly=False``): the
        append region is disjoint from the active pass's rows. Missing
        keys under ``readonly`` get their init-record overlay in this
        half (a missing key is never shared — it is not in the store at
        all, so it is always an early position)."""
        with self._lock:
            k = np.ascontiguousarray(pass_keys_sorted, np.uint64)
            if readonly:
                rows = self._index.lookup(k)
            else:
                rows = self._ensure_rows_locked(k)
            n = k.shape[0]
            rps = plan_shards(n, num_pass_shards)
            sel = np.asarray(select, bool)
            rows_eff = np.where(sel, rows, -1)
            missing = np.flatnonzero(sel & (rows < 0))
            init = (self._host_init_fused(k[missing]) if missing.size
                    else np.zeros((0, self.width), np.float32))
            table_vals = self._gather_pass_locked(rows_eff, n, rps,
                                                  num_pass_shards,
                                                  missing, init)
            table = PassTable(vals=table_vals, rows_per_shard=rps,
                              num_shards=num_pass_shards, dim=self.dim,
                              ke=self.ke, kw=self.kw)
            monitor.add("store/pass_keys", n)
            monitor.add("device_store/early_rows", int(sel.sum()))
            return table, rows

    def merge_pass_rows(self, rows: np.ndarray, table: PassTable,
                        select: np.ndarray) -> PassTable:
        """LATE half of the split build: gather the ``select`` positions
        (the shared-key remainder, post write-back) from the store into
        the early-built block. Selected rows are always present (shared
        keys live in the store by definition), so no init overlay."""
        sel_pos = np.flatnonzero(np.asarray(select, bool))
        with self._lock:
            if sel_pos.size == 0:
                return table
            monitor.add("device_store/boundary_progs", 1)
            vals = self._merge_rows_locked(table.vals, rows, sel_pos,
                                           table.rows_per_shard,
                                           table.num_shards)
        return dataclasses.replace(table, vals=vals)

    def push_and_pull_merge(self, prev_keys_sorted: np.ndarray,
                            prev_rows: np.ndarray, prev_table: PassTable,
                            next_rows: np.ndarray, next_table: PassTable,
                            next_select: np.ndarray) -> PassTable:
        """Fused pass boundary (FLAGS_pass_boundary_fuse): the previous
        pass's write-back scatter AND the next pass's shared-remainder
        gather in ONE jitted program — one dispatch crosses the host
        link per boundary instead of two, and the gather reads the
        post-scatter store so shared keys observe the write-back
        bit-exactly as the serial sequencing does."""
        faults.faultpoint("device_store/fused")
        with self._lock:
            k = np.ascontiguousarray(prev_keys_sorted, np.uint64)
            n_prev = k.shape[0]
            sel_pos = np.flatnonzero(np.asarray(next_select, bool))
            s = self.num_shards
            rps_p = prev_table.rows_per_shard
            sp_p = prev_table.num_shards
            rps_n = next_table.rows_per_shard
            sp_n = next_table.num_shards
            monitor.add("device_store/boundary_progs", 1)
            monitor.add("device_store/boundary_fused", 1)
            if s == 1 and sp_p == 1 and sp_n == 1:
                scratch = s * (self._cap + 1) - 1
                idx_p = np.full((rps_p,), scratch, np.int64)
                idx_p[:n_prev] = self._dev_idx(prev_rows)
                m = sel_pos.size
                cap_m = _pow2(max(m, 1))
                idx_n = np.full((cap_m,), scratch, np.int64)
                place = np.full((cap_m,), rps_n, np.int32)
                if m:
                    idx_n[:m] = self._dev_idx(next_rows[sel_pos])
                    place[:m] = sel_pos
                parts, merged = _fused_boundary_fn_local(
                    self._widths, rps_p, rps_n)(
                    self._compute_parts(), prev_table.vals,
                    jnp.asarray(idx_p, jnp.int32), next_table.vals,
                    jnp.asarray(idx_n, jnp.int32), jnp.asarray(place))
                self._parts = self._settle_parts(parts)
            else:
                if s != sp_p or s != sp_n:
                    raise ValueError(
                        "pass shards must equal store shards")
                slot, local, _, cap_p = self._bucket_exact(
                    prev_rows, n_prev, rps_p, sp_p)
                src = np.where(local >= 0, local, rps_p).astype(np.int32)
                dst = np.where(slot >= 0, slot, self._cap).astype(np.int32)
                req, place, cap_n = self._bucket_selected(
                    next_rows, sel_pos, rps_n, sp_n)
                src_d = jax.device_put(
                    jnp.asarray(src.reshape(sp_p, s * cap_p)),
                    self._sharding)
                dst_d = jax.device_put(
                    jnp.asarray(dst.reshape(sp_p, s * cap_p)),
                    self._sharding)
                req_d = jax.device_put(
                    jnp.asarray(req.reshape(sp_n, s * cap_n)),
                    self._sharding)
                pl_d = jax.device_put(
                    jnp.asarray(place.reshape(sp_n, s * cap_n)),
                    self._sharding)
                parts, merged = _fused_boundary_fn_sharded(
                    self.mesh, self.axis, s, cap_p, cap_n, self._widths,
                    rps_p, rps_n, self._cap)(
                    self._compute_parts(), prev_table.vals, src_d, dst_d,
                    next_table.vals, req_d, pl_d)
                self._parts = self._settle_parts(parts)
            self._dirty_parts.append(k.copy())
            self._unseen[prev_rows] = 0
            monitor.add("device_store/pushed_keys", n_prev)
        return dataclasses.replace(next_table, vals=merged)

    def _bucket_selected(self, rows: np.ndarray, sel_pos: np.ndarray,
                         rps: int, sp: int
                         ) -> Tuple[np.ndarray, np.ndarray, int]:
        """[sp, s, cap] (request slots, pass-local placements) covering
        ONLY the selected pass positions (all with valid store rows);
        pads request the scratch slot and place at the trash row. cap is
        pow2-stable like _bucket_exact's."""
        with trace.span("store/bucket", rows=int(sel_pos.size)):
            s = self.num_shards
            m = sel_pos.size
            rs = rows[sel_pos]
            store_shard = (rs % s).astype(np.int64)
            store_slot = (rs // s).astype(np.int64)
            pass_shard = (sel_pos % sp).astype(np.int64)
            pass_local = (sel_pos // sp).astype(np.int64)
            counts = np.zeros((sp, s), np.int64)
            np.add.at(counts, (pass_shard, store_shard), 1)
            cap = _pow2(max(int(counts.max()) if m else 1, 1))
            req = np.full((sp, s, cap), self._cap, np.int64)
            place = np.full((sp, s, cap), rps, np.int64)
            order = np.lexsort((store_shard, pass_shard))
            gs = pass_shard[order] * s + store_shard[order]
            starts = np.searchsorted(gs, np.arange(sp * s))
            pos = np.arange(m) - starts[gs]
            req[pass_shard[order], store_shard[order], pos] = \
                store_slot[order]
            place[pass_shard[order], store_shard[order], pos] = \
                pass_local[order]
            return req.astype(np.int32), place.astype(np.int32), cap

    def _merge_rows_locked(self, block_vals: jax.Array, rows: np.ndarray,
                           sel_pos: np.ndarray, rps: int,
                           sp: int) -> jax.Array:
        s = self.num_shards
        m = sel_pos.size
        if s == 1 and sp == 1:
            cap_m = _pow2(max(m, 1))
            scratch = s * (self._cap + 1) - 1
            idx = np.full((cap_m,), scratch, np.int64)
            place = np.full((cap_m,), rps, np.int32)
            if m:
                idx[:m] = self._dev_idx(rows[sel_pos])
                place[:m] = sel_pos
            return _merge_fn_local(self._widths, rps)(
                block_vals, self._compute_parts(),
                jnp.asarray(idx, jnp.int32), jnp.asarray(place))
        if s != sp:
            raise ValueError("pass shards must equal store shards")
        req, place, cap = self._bucket_selected(rows, sel_pos, rps, sp)
        req_d = jax.device_put(
            jnp.asarray(req.reshape(sp, s * cap)), self._sharding)
        pl_d = jax.device_put(
            jnp.asarray(place.reshape(sp, s * cap)), self._sharding)
        return _merge_fn_sharded(self.mesh, self.axis, s, cap,
                                 self._widths, rps, self._cap)(
            block_vals, self._compute_parts(), req_d, pl_d)

    def _pull_pass_table_locked(self, pass_keys_sorted: np.ndarray,
                                num_pass_shards: int, *,
                                readonly: bool = False
                                ) -> Tuple[PassTable, np.ndarray]:
        k = np.ascontiguousarray(pass_keys_sorted, np.uint64)
        if readonly:
            rows = self._index.lookup(k)
        else:
            rows = self._ensure_rows_locked(k)
        n = k.shape[0]
        rps = plan_shards(n, num_pass_shards)
        missing = np.flatnonzero(rows < 0)
        init = (self._host_init_fused(k[missing]) if missing.size
                else np.zeros((0, self.width), np.float32))
        table_vals = self._gather_pass_locked(rows, n, rps,
                                              num_pass_shards,
                                              missing, init)
        table = PassTable(vals=table_vals, rows_per_shard=rps,
                          num_shards=num_pass_shards, dim=self.dim,
                          ke=self.ke, kw=self.kw)
        monitor.add("store/pass_keys", n)
        return table, rows

    def push_pass_table(self, pass_keys_sorted: np.ndarray,
                        rows: np.ndarray, table: PassTable) -> None:
        """Write a finished pass table back into the resident store (role
        of EndPass, ps_gpu_wrapper.cc:983 — one on-device scatter)."""
        faults.faultpoint("device_store/push")
        with self._lock:
            k = np.ascontiguousarray(pass_keys_sorted, np.uint64)
            n = k.shape[0]
            if n == 0:
                return
            monitor.add("device_store/boundary_progs", 1)
            self._parts = self._scatter_pass_locked(
                table.vals, rows, n, table.rows_per_shard,
                table.num_shards)
            self._dirty_parts.append(k.copy())
            self._unseen[rows] = 0
            monitor.add("device_store/pushed_keys", n)

    def _dev_idx(self, rows: np.ndarray) -> np.ndarray:
        s = self.num_shards
        return ((rows % s) * (self._cap + 1) + rows // s).astype(np.int64)

    def _bucket_exact(self, rows: np.ndarray, n: int, rps: int, sp: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Host-exact bucketing for the sharded pass transfers.

        Pass rank p (round-robin: pass-shard p % sp, local p // sp —
        table.py layout) maps to store shard rows[p] % s at slot
        rows[p] // s; missing keys (row -1, read-only pulls) route to the
        scratch slot of shard p % s so they read zero. Returns
        (slot [sp,s,cap], local [sp,s,cap], counts, cap) with pads
        slot=-1/local=-1 to be sentineled by the caller; cap pow2-stable
        across passes.
        """
        with trace.span("store/bucket", rows=int(n)):
            s = self.num_shards
            valid = rows >= 0
            store_shard = np.where(valid, rows % s, np.arange(n) % s
                                   ).astype(np.int64)
            store_slot = np.where(valid, rows // s, self._cap).astype(np.int64)
            pass_shard = (np.arange(n) % sp).astype(np.int64)
            pass_local = (np.arange(n) // sp).astype(np.int64)
            counts = np.zeros((sp, s), np.int64)
            np.add.at(counts, (pass_shard, store_shard), 1)
            cap = _pow2(max(int(counts.max()) if n else 1, 1))
            slot = np.full((sp, s, cap), -1, np.int64)
            local = np.full((sp, s, cap), -1, np.int64)
            order = np.lexsort((store_shard, pass_shard))
            gs = pass_shard[order] * s + store_shard[order]
            starts = np.searchsorted(gs, np.arange(sp * s))
            pos = np.arange(n) - starts[gs]
            slot[pass_shard[order], store_shard[order], pos] = \
                store_slot[order]
            local[pass_shard[order], store_shard[order], pos] = \
                pass_local[order]
            return slot, local, counts, cap

    def _gather_pass_locked(self, rows: np.ndarray, n: int, rps: int,
                            sp: int, missing: Optional[np.ndarray] = None,
                            init: Optional[np.ndarray] = None) -> jax.Array:
        """missing: pass-row indices (into [0, n)) whose keys are absent
        (read-only pulls); init [len(missing), W] overlays their rows."""
        s = self.num_shards
        w = self.width
        n_miss = missing.size if missing is not None else 0
        if s == 1 and sp == 1:
            scratch = s * (self._cap + 1) - 1
            idx = np.full((rps,), scratch, np.int64)
            idx[:n] = np.where(rows >= 0, self._dev_idx(rows), scratch)
            cap_m = _pow2(max(n_miss, 1))
            init_idx = np.full((cap_m,), rps, np.int32)
            init_vals = np.zeros((cap_m, w), np.float32)
            if n_miss:
                init_idx[:n_miss] = missing
                init_vals[:n_miss] = init
            return _gather_fn_local(self._widths, rps)(
                self._compute_parts(), jnp.asarray(idx, jnp.int32),
                jnp.asarray(init_idx), jnp.asarray(init_vals))
        if s != sp:
            raise ValueError(
                f"pass shards ({sp}) must equal store shards ({s}) — both "
                f"are the size of the same table mesh axis")
        slot, local, _, cap = self._bucket_exact(rows, n, rps, sp)
        req = np.where(slot >= 0, slot, self._cap).astype(np.int32)
        place = np.where(local >= 0, local, rps).astype(np.int32)
        # Overlay init records bucketed by pass shard.
        if n_miss:
            m_shard = missing % sp
            m_local = (missing // sp).astype(np.int32)
            m_counts = np.bincount(m_shard, minlength=sp)
            cap_m = _pow2(int(m_counts.max()))
        else:
            cap_m = 1
        init_idx = np.full((sp, cap_m), rps, np.int32)
        init_vals = np.zeros((sp, cap_m, w), np.float32)
        if n_miss:
            order = np.argsort(m_shard, kind="stable")
            starts = np.searchsorted(m_shard[order], np.arange(sp))
            pos = np.arange(n_miss) - starts[m_shard[order]]
            init_idx[m_shard[order], pos] = m_local[order]
            init_vals[m_shard[order], pos] = init[order]
        req_d = jax.device_put(
            jnp.asarray(req.reshape(sp, s * cap)), self._sharding)
        place_d = jax.device_put(
            jnp.asarray(place.reshape(sp, s * cap)), self._sharding)
        init_idx_d = jax.device_put(jnp.asarray(init_idx), self._sharding)
        init_vals_d = jax.device_put(
            jnp.asarray(init_vals.reshape(sp * cap_m, w)), self._sharding)
        return _gather_fn_sharded(self.mesh, self.axis, s, cap,
                                  self._widths, rps, self._cap)(
            self._compute_parts(), req_d, place_d, init_idx_d,
            init_vals_d)

    def _scatter_pass_locked(self, block_vals: jax.Array, rows: np.ndarray,
                             n: int, rps: int, sp: int) -> Tuple:
        """Returns the new parts tuple (persistent placement)."""
        s = self.num_shards
        if s == 1 and sp == 1:
            idx = np.full((rps,), s * (self._cap + 1) - 1, np.int64)
            idx[:n] = self._dev_idx(rows)
            return self._settle_parts(_scatter_fn_local(
                self._widths, rps)(
                self._compute_parts(), block_vals,
                jnp.asarray(idx, jnp.int32)))
        if s != sp:
            raise ValueError("pass shards must equal store shards")
        slot, local, _, cap = self._bucket_exact(rows, n, rps, sp)
        src = np.where(local >= 0, local, rps).astype(np.int32)
        dst = np.where(slot >= 0, slot, self._cap).astype(np.int32)
        src_d = jax.device_put(
            jnp.asarray(src.reshape(sp, s * cap)), self._sharding)
        dst_d = jax.device_put(
            jnp.asarray(dst.reshape(sp, s * cap)), self._sharding)
        return self._settle_parts(_scatter_fn_sharded(
            self.mesh, self.axis, s, cap, self._widths)(
            self._compute_parts(), block_vals, src_d, dst_d))

    # -- FeatureStore-compatible host-dict surface -------------------------

    def contains(self, keys: np.ndarray) -> np.ndarray:
        return self._index.lookup(
            np.ascontiguousarray(keys, np.uint64)) >= 0

    def dirty_keys(self) -> np.ndarray:
        with self._lock:
            return self._dirty_compact_locked().copy()

    def _dirty_compact_locked(self) -> np.ndarray:
        if len(self._dirty_parts) > 1:
            # np.unique, not dedup_keys: key 0 is a legal dirty key here
            # (dedup_keys drops the null feasign by design).
            self._dirty_parts = [np.unique(
                np.concatenate(self._dirty_parts))]
        return (self._dirty_parts[0] if self._dirty_parts
                else np.empty((0,), np.uint64))

    def pull_for_pass(self, pass_keys_sorted: np.ndarray
                      ) -> Dict[str, np.ndarray]:
        """Host-dict compat path (tools, tier interop, tests). Values
        cross to the host — per-pass training uses pull_pass_table.
        Read-only, like the host FeatureStore contract: unseen keys are
        served their deterministic init WITHOUT being inserted (only a
        push persists them)."""
        with self._lock:
            table, _ = self._pull_pass_table_locked(pass_keys_sorted,
                                                    self.num_shards,
                                                    readonly=True)
        return extract_pass_values_host(table, pass_keys_sorted.shape[0])

    def push_from_pass(self, pass_keys_sorted: np.ndarray,
                       values: Dict[str, np.ndarray]) -> None:
        """Host-dict compat write path (delta load, tools)."""
        k = np.ascontiguousarray(pass_keys_sorted, np.uint64)
        if k.shape[0] == 0:
            return
        self._check_state_widths(values)
        with self._lock:
            rows = self._ensure_rows_locked(k)
            n = k.shape[0]
            s = self.num_shards
            rps = plan_shards(n, s)
            laid = self._place(jnp.asarray(
                lay_fused_host(fuse_values_host(values), s, rps)))
            self._parts = self._scatter_pass_locked(laid, rows, n, rps, s)
            self._dirty_parts.append(k.copy())
            self._unseen[rows] = 0

    def key_stats(self) -> Tuple[np.ndarray, np.ndarray]:
        with self._lock:
            keys = self._index.keys_by_row()
            show = self._fetch_column_locked(self.dim + 1, keys.shape[0])
        return keys, show

    def rows_by_coldness(self) -> np.ndarray:
        keys, show = self.key_stats()
        return keys[np.argsort(show, kind="stable")]

    def _fetch_column_locked(self, col: int, n: int) -> np.ndarray:
        """D2H one column for dense rows [0, n) (row order)."""
        if n == 0:
            return np.empty((0,), np.float32)
        s = self.num_shards
        cap1 = self._cap + 1
        host = np.asarray(
            jax.jit(lambda v: v[:, col])(self._parts[0])).reshape(s, cap1)
        rows = np.arange(n)
        return host[rows % s, rows // s]

    # -- maintenance / checkpoint ------------------------------------------

    def unseen_for(self, keys: np.ndarray) -> np.ndarray:
        """Unseen-days ages aligned to ``keys`` (0 where absent)."""
        k = np.ascontiguousarray(keys, np.uint64)
        with self._lock:
            rows = self._index.lookup(k)
            out = np.zeros(k.shape, np.int32)
            found = rows >= 0
            out[found] = self._unseen[rows[found]]
        return out

    def shrink(self, *, min_show: float = 0.0) -> int:
        """Day-boundary lifecycle on the HBM tier: ONE jitted scale over
        the fused record decays show/click in place, unseen_days bump +
        TTL/min-show eviction compact the store (role of ShrinkTable).
        Policy comes from :func:`lifecycle.shrink_params` like every
        other store variant."""
        decay, ttl, min_show = lifecycle.shrink_params(self.config,
                                                       min_show)
        with self._lock:
            self._shrunk_since_base = True
            self._parts = (self._place(_decay_fn(
                self.dim, float(decay))(self._parts[0])),
                ) + self._parts[1:]
            self._unseen += 1
            if min_show <= 0 and ttl <= 0:
                return 0
            n = self._index.size
            keep = np.ones((n,), bool)
            if min_show > 0:
                show = self._fetch_column_locked(self.dim + 1, n)
                keep &= show >= min_show
            if ttl > 0:
                over = self._unseen[:n] > ttl
                monitor.add("store/ttl_evicted", int((keep & over).sum()))
                keep &= ~over
            evicted = int((~keep).sum())
            if evicted:
                self._compact_locked(np.flatnonzero(keep))
            return evicted

    def _compact_locked(self, keep_rows: np.ndarray) -> None:
        """Rebuild with only keep_rows (ascending dense row ids)."""
        keys = self._index.keys_by_row()[keep_rows]
        # keep_rows is ascending and upsert below reassigns dense ids
        # 0..n-1 in that same order, so the age array just filters.
        ages = self._unseen[keep_rows]
        n = keys.shape[0]
        s = self.num_shards
        rps = plan_shards(max(n, 1), s)
        survivors = self._gather_pass_locked(keep_rows, n, rps, s)
        self._index.close()
        self._index = native_store.KeyIndex()
        self._index.reserve(n)
        self._cap = _pow2(max(1 << 10, -(-max(n, 1) // s)))
        self._parts = self._place_parts(tuple(
            jnp.zeros((s * (self._cap + 1), w), jnp.float32)
            for w in self._widths))
        self._unseen = ages
        if n:
            rows2, n_new = self._index.upsert(keys)
            assert n_new == n
            # Rows are fresh appends 0..n-1; values come from the gathered
            # block, not init — scatter them in directly.
            self._parts = self._scatter_pass_locked(survivors, rows2, n,
                                                    rps, s)
        log.vlog(0, "device store compacted: %d rows kept", n)

    def _snapshot_sorted_locked(self, keys_sorted: np.ndarray
                                ) -> Dict[str, np.ndarray]:
        table, _ = self._pull_pass_table_locked(keys_sorted,
                                                self.num_shards,
                                                readonly=True)
        return extract_pass_values_host(table, keys_sorted.shape[0])

    def _empty_vals(self) -> Dict[str, np.ndarray]:
        d = self.dim
        return {"emb": np.empty((0, d), np.float32),
                "emb_state": np.empty((0, self.ke), np.float32),
                "w": np.empty((0,), np.float32),
                "w_state": np.empty((0, self.kw), np.float32),
                "show": np.empty((0,), np.float32),
                "click": np.empty((0,), np.float32)}

    def reset(self) -> None:
        """Drop everything (pass-retry rollback — see FeatureStore.reset):
        fresh key index, zeroed HBM block, clean delta set."""
        self.set_all(np.empty((0,), np.uint64), self._empty_vals())

    def _save_arrays(self, path: str, keys, vals, kind: str,
                     unseen=None) -> None:
        os.makedirs(path, exist_ok=True)
        final = os.path.join(path, f"{self.config.name}.{kind}.npz")
        tmp = os.path.join(path, f".{self.config.name}.{kind}.tmp")
        with open(tmp, "wb") as f:
            np.savez_compressed(f, keys=keys, **vals)
        os.replace(tmp, final)
        if unseen is not None:
            # Unseen-days TTL sidecar aligned to the npz's key order —
            # same format as FeatureStore's (ONLINE.md), so the six
            # store variants' checkpoints stay mutually loadable.
            ages_final = os.path.join(
                path, f"{self.config.name}.{kind}.ages.npz")
            ages_tmp = os.path.join(
                path, f".{self.config.name}.{kind}.ages.tmp")
            with open(ages_tmp, "wb") as f:
                np.savez_compressed(
                    f, unseen=np.ascontiguousarray(unseen, np.int32))
            os.replace(ages_tmp, ages_final)
        meta = {"kind": kind, "num_features": int(keys.shape[0]),
                "dim": self.config.dim, "table": self.config.name}
        with open(os.path.join(path,
                               f"{self.config.name}.{kind}.meta.json"),
                  "w") as f:
            json.dump(meta, f)

    def _ages_for_locked(self, keys: np.ndarray) -> np.ndarray:
        rows = self._index.lookup(keys)
        out = np.zeros(keys.shape, np.int32)
        found = rows >= 0
        out[found] = self._unseen[rows[found]]
        return out

    def save_base(self, path: str) -> None:
        with self._lock:
            keys = np.sort(self._index.keys_by_row())
            vals = (self._snapshot_sorted_locked(keys) if keys.size
                    else self._empty_vals())
            unseen = self._ages_for_locked(keys)
            self._dirty_parts = []
            self._shrunk_since_base = False
        self._save_arrays(path, keys, vals, "base", unseen=unseen)
        log.vlog(0, "device store save_base: %d features -> %s",
                 keys.shape[0], path)

    def save_delta(self, path: str) -> None:
        with self._lock:
            if self._shrunk_since_base:
                raise RuntimeError(
                    "save_delta after shrink(): decay/eviction cannot be "
                    "expressed as a delta — save_base first (the "
                    "reference's day boundary does the same: shrink, then "
                    "base dump)")
            dirty = self._dirty_compact_locked()
            present = self._index.lookup(dirty) >= 0
            dirty = dirty[present]
            vals = (self._snapshot_sorted_locked(dirty) if dirty.size
                    else self._empty_vals())
            unseen = self._ages_for_locked(dirty)
        self._save_arrays(path, dirty, vals, "delta", unseen=unseen)
        log.vlog(0, "device store save_delta: %d features -> %s",
                 dirty.shape[0], path)

    def save_xbox(self, path: str) -> int:
        from paddlebox_tpu.embedding.store import quantize_xbox_vals
        with self._lock:
            keys = np.sort(self._index.keys_by_row())
            vals = (self._snapshot_sorted_locked(keys) if keys.size
                    else self._empty_vals())
        self._save_arrays(path, keys,
                          quantize_xbox_vals({"emb": vals["emb"],
                                              "w": vals["w"]}), "xbox")
        log.vlog(0, "device store save_xbox: %d features -> %s",
                 keys.shape[0], path)
        return int(keys.shape[0])

    def _check_state_widths(self, vals: Dict[str, np.ndarray]) -> None:
        for f, want in (("emb_state", self.ke), ("w_state", self.kw)):
            got = vals[f].shape[-1] if vals[f].ndim > 1 else 1
            if got != want:
                raise ValueError(
                    f"{f} width {got} != {want} expected by optimizer "
                    f"{self.config.optimizer!r} — checkpoint/table was "
                    f"written with a different sparse optimizer")

    def set_all(self, keys_sorted: np.ndarray,
                vals: Dict[str, np.ndarray]) -> None:
        """Replace contents (base-load semantics: delta cleared, shrink
        guard reset). Keys must be sorted unique."""
        self._check_state_widths(vals)
        with self._lock:
            s = self.num_shards
            n = int(keys_sorted.shape[0])
            self._index.close()
            self._index = native_store.KeyIndex()
            self._index.reserve(n)
            self._cap = _pow2(max(1 << 10, -(-max(n, 1) // s)))
            self._parts = self._place_parts(tuple(
                jnp.zeros((s * (self._cap + 1), w), jnp.float32)
                for w in self._widths))
            self._dirty_parts = []
            self._shrunk_since_base = False
            self._unseen = np.zeros((n,), np.int32)
            if n == 0:
                return
            rows, _ = self._index.upsert(
                np.ascontiguousarray(keys_sorted, np.uint64))
            rps = plan_shards(n, s)
            laid = self._place(jnp.asarray(
                lay_fused_host(fuse_values_host(vals), s, rps)))
            self._parts = self._scatter_pass_locked(laid, rows, n, rps, s)

    def load(self, path: str, kind: str = "base") -> None:
        data = np.load(os.path.join(path,
                                    f"{self.config.name}.{kind}.npz"))
        keys = data["keys"].astype(np.uint64)
        vals = {f: data[f] for f in _FIELDS if f in data}
        if kind == "base":
            self.set_all(keys, vals)
        else:
            self._check_state_widths(vals)
            self.push_from_pass(keys, vals)
        # Restore the unseen-days TTL sidecar (when present — see
        # FeatureStore.load): the push/set path above reset the loaded
        # keys' ages, which is correct only for genuinely-new training
        # writes, not a restart reload.
        ages_f = os.path.join(path,
                              f"{self.config.name}.{kind}.ages.npz")
        if os.path.exists(ages_f):
            ages = np.load(ages_f)["unseen"].astype(np.int32)
            if ages.shape[0] == keys.shape[0]:
                with self._lock:
                    rows = self._index.lookup(keys)
                    found = rows >= 0
                    self._unseen[rows[found]] = ages[found]
            else:
                log.warning("ages sidecar %s has %d rows, checkpoint "
                            "has %d — ignoring it", ages_f,
                            ages.shape[0], keys.shape[0])
