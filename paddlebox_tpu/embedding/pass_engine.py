"""Pass lifecycle orchestration: feed_pass → begin_pass → train → end_pass.

Role of the BoxWrapper/BoxHelper pass driver (``box_wrapper.h:449-453,
1034-1301``): per-pass key registration (``FeedPass``), staged build of the
device table (``BeginFeedPass``/``EndFeedPass``; HeterPS ``PreBuildTask`` →
``BuildPull`` → ``BuildGPUTask``, ps_gpu_wrapper.cc:114,337,684), training
window between ``BeginPass``/``EndPass``, and write-back on ``EndPass``.

Double-buffering: ``feed_pass`` may run in a background thread while the
previous pass trains (role of PreLoadIntoMemory/WaitFeedPassDone overlap,
box_wrapper.h:1140,1161).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.core import faults, flags, log, monitor, timers, trace
from paddlebox_tpu.embedding.store import FeatureStore
from paddlebox_tpu.embedding.table import (PassTable, TableConfig,
                                           build_pass_table_host,
                                           extract_pass_values_host,
                                           map_keys_to_rows,
                                           shared_key_mask)


class PassBuildCancelled(RuntimeError):
    """A pending async build was cancelled (cancel_pending) while it was
    parked waiting for the active pass's boundary."""


class _PendingPass:
    def __init__(self):
        self.keys: Optional[np.ndarray] = None
        self.table: Optional[PassTable] = None
        self.keymap = None
        self.rows: Optional[np.ndarray] = None   # device-store dense rows
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        # Split-build handshake (device tier): the builder publishes its
        # early state and parks; end_pass may consume it into the fused
        # boundary program and hand back the finished table.
        self.early_table: Optional[PassTable] = None
        self.early_shared: Optional[np.ndarray] = None
        self.early_ready = threading.Event()
        self.fused_table: Optional[PassTable] = None
        # Boundary wake-up: set by end_pass/abort_pass (after
        # _no_active_pass) and by cancel_pending, so a parked builder
        # never needs to poll the shared event.
        self.resume = threading.Event()
        self.cancel = threading.Event()


class PassEngine:
    """Owns the FeatureStore + the live per-pass device table."""

    def __init__(self, config: TableConfig, store: Optional[FeatureStore] = None,
                 *, mesh: Optional[Mesh] = None, table_axis: str = "dp"):
        self.config = config
        self.store = store or FeatureStore(config)
        self.mesh = mesh
        self.table_axis = table_axis
        self.num_shards = (
            int(mesh.shape[table_axis]) if mesh is not None else 1)
        self.timers = timers.TimerGroup()

        self._current_keys: Optional[np.ndarray] = None
        self._table: Optional[PassTable] = None
        self._keymap = None
        self._current_rows: Optional[np.ndarray] = None
        self._pending: Optional[_PendingPass] = None
        self._pass_id = -1
        # Sequencing for async builds: the store pull must happen AFTER the
        # previous pass's end_pass write-back, or updates to keys shared
        # between passes would be read stale and then overwritten (the
        # reference sequences BuildPull after EndPass the same way).
        self._no_active_pass = threading.Event()
        self._no_active_pass.set()
        # One pending-build slot: a feed_pass issued while an earlier
        # build is still waiting to be begun (pipelined day loops feeding
        # pass k+1 from a loader thread) blocks until begin_pass consumes
        # the earlier one. A semaphore (not an Event) so concurrent
        # feed_pass callers serialize atomically instead of both passing
        # a wait()+clear() window.
        self._pending_sem = threading.Semaphore(1)

    # -- build -------------------------------------------------------------

    def _build(self, pass_keys: np.ndarray, pending: _PendingPass,
               readonly: bool = False) -> None:
        try:
            faults.faultpoint("pass_engine/build")
            with self.timers.scope("feed_pass"), \
                    trace.span("build/pass_table",
                               pass_id=self._pass_id + 1):
                # Key dedup can overlap the active pass... (native
                # multi-threaded dedup, role of PreBuildTask,
                # ps_gpu_wrapper.cc:114; numpy fallback inside). Keys
                # arriving already sorted-unique-nonzero — the sorted-run
                # collector's merge (Dataset.pass_keys, round 13) — skip
                # the redundant re-sort: one O(n) vectorized check
                # replaces an O(n log n) dedup on the build path.
                from paddlebox_tpu.native.keymap_py import KeyMap, dedup_keys
                from paddlebox_tpu.native.store_py import \
                    is_sorted_unique_nonzero
                keys = np.asarray(pass_keys, np.uint64)
                if not is_sorted_unique_nonzero(keys):
                    keys = dedup_keys(keys)
                if hasattr(self.store, "pull_pass_table"):
                    # Device-resident store tier: the build is an on-device
                    # gather — values never cross the host boundary. Only
                    # rows the active pass will write back (its own keys)
                    # must wait for end_pass; everything else — unseen-key
                    # insertion (append region disjoint from the active
                    # rows), the NOT-shared gather, and the keymap build —
                    # overlaps the active pass's training (split-key early
                    # build, role of the overlapped BuildPull threads,
                    # ps_gpu_wrapper.cc:907).
                    active = self._current_keys  # snapshot; sorted or None
                    split = (bool(flags.flag("pass_split_build"))
                             and hasattr(self.store,
                                         "pull_pass_table_partial")
                             and active is not None and active.size
                             and keys.size
                             and not self._no_active_pass.is_set())
                    shared = (shared_key_mask(active, keys) if split
                              else None)
                    if shared is not None:
                        # Even a fully-shared pass goes through the
                        # split path: the early half is then just the
                        # (overlapped) keymap build + a zero-filled
                        # block, but the whole-table remainder gather
                        # can ride the fused boundary program — one
                        # dispatch at the boundary instead of two.
                        table, rows = self.store.pull_pass_table_partial(
                            keys, self.num_shards, select=~shared,
                            readonly=readonly)
                        pending.keys = keys
                        pending.rows = rows
                        # Keymap built during the overlap window; hung on
                        # the pending NOW so every discard path
                        # (cancel_pending, a begin_pass error) closes it.
                        pending.keymap = KeyMap(keys, table.rows_per_shard,
                                                self.num_shards)
                        monitor.add("pass/split_builds", 1)
                        if shared.any():
                            # Publish early state, then park: end_pass
                            # either fuses its scatter with our remainder
                            # gather (ONE dispatch) or just releases us
                            # to merge ourselves.
                            pending.early_table = table
                            pending.early_shared = shared
                            pending.early_ready.set()
                            self._wait_boundary(pending)
                            if pending.fused_table is not None:
                                table = pending.fused_table
                            else:
                                table = self.store.merge_pass_rows(
                                    rows, table, shared)
                        pending.table = table
                        monitor.add("pass/built", 1)
                        return
                    # Serial build (no active pass, all keys shared, or
                    # split disabled): the whole gather observes the
                    # write-back.
                    self._wait_boundary(pending)
                    table, rows = self.store.pull_pass_table(
                        keys, self.num_shards, readonly=readonly)
                    pending.keys = keys
                    pending.table = table
                    pending.rows = rows
                    pending.keymap = KeyMap(keys, table.rows_per_shard,
                                            self.num_shards)
                    monitor.add("pass/built", 1)
                    return
                # Split pull (role of the double-buffered build threads,
                # ps_gpu_wrapper.cc:907): the active pass's end_pass only
                # writes back ITS OWN keys, so values for keys NOT in the
                # active set can be pulled while it trains; only the
                # intersection must wait for write-back. Consecutive
                # online passes typically share a minority of keys, so
                # most of the pull overlaps training.
                active = self._current_keys  # snapshot; sorted or None
                vals = None
                shared = None
                # Multi-host tier: plan-aware partial pulls slice ONE
                # cached owner plan (keyed by this pass's id) instead of
                # re-deriving an argsort per sub-pull, and the key set
                # publishes EARLY so the active pass's end_pass can
                # split its push into the priority slice (rows this
                # pass pulls back at the boundary) + an overlapped bulk
                # remainder on the exchange worker.
                mh = hasattr(self.store, "push_from_pass_async")
                pid = self._pass_id + 1 if mh else None
                if mh:
                    pending.keys = keys
                if (active is not None and active.size and keys.size
                        and not self._no_active_pass.is_set()):
                    shared = shared_key_mask(active, keys)
                    if shared.any() and not shared.all():
                        part = (self.store.pull_for_pass(
                                    keys, ~shared, pass_id=pid) if mh
                                else self.store.pull_for_pass(
                                    keys[~shared]))
                        n = keys.shape[0]
                        vals = {f: np.empty((n,) + v.shape[1:], v.dtype)
                                for f, v in part.items()}
                        for f, v in part.items():
                            vals[f][~shared] = v
                    elif not shared.any():
                        vals = (self.store.pull_for_pass(
                                    keys, pass_id=pid) if mh
                                else self.store.pull_for_pass(keys))
                        shared = None
                self._wait_boundary(pending)
                if vals is None:
                    vals = (self.store.pull_for_pass(keys, pass_id=pid)
                            if mh else self.store.pull_for_pass(keys))
                elif shared is not None:
                    # The ONE coalesced boundary pull: only the shared
                    # remainder waits here. barrier=False is safe — the
                    # shared rows were pushed synchronously as the
                    # priority slice of end_pass's write-back, and any
                    # still-queued bulk push holds only keys NOT in
                    # this pass.
                    part = (self.store.pull_for_pass(
                                keys, shared, pass_id=pid,
                                barrier=False, boundary=True) if mh
                            else self.store.pull_for_pass(keys[shared]))
                    for f, v in part.items():
                        vals[f][shared] = v
                table = build_pass_table_host(
                    vals, self.num_shards, self.config)
                if self.mesh is not None:
                    sharding = NamedSharding(self.mesh, P(self.table_axis))
                    table = jax.tree.map(
                        lambda x: jax.device_put(x, sharding), table)
                pending.keys = keys
                pending.table = table
                pending.keymap = KeyMap(keys, table.rows_per_shard,
                                        self.num_shards)
                monitor.add("pass/built", 1)
        except BaseException as e:  # propagate to the waiting begin_pass
            pending.error = e

    def _wait_boundary(self, pending: _PendingPass) -> None:
        """Park the builder until the active pass releases the store
        (end_pass/abort_pass), the fused boundary already produced our
        table, or the build is cancelled. The normal wake-up is the
        per-pending ``resume`` event (set by the boundary with the
        pending visible — feed_pass publishes ``_pending`` before the
        builder starts); the ``_no_active_pass`` check is both the
        no-active fast path and a poll-rate safety net."""
        faults.faultpoint("pass_engine/boundary")
        # The builder parks here while the active pass owns the store.
        with self.timers.scope("feed_wait"), \
                trace.span("build/boundary_wait"):
            while True:
                if pending.cancel.is_set():
                    raise PassBuildCancelled(
                        "pending pass build cancelled at the boundary "
                        "wait (cancel_pending)")
                if (pending.resume.is_set()
                        or self._no_active_pass.is_set()):
                    return
                pending.resume.wait(timeout=0.2)

    def feed_pass(self, pass_keys: np.ndarray, *, async_build: bool = False,
                  readonly: bool = False) -> None:
        """Register the next pass's key set and build its device table.

        ``async_build=True`` overlaps the build with current-pass training
        (role of PreLoadIntoMemory + WaitFeedPassDone). ``readonly=True``
        marks an eval-pass build: a device-tier store must not insert the
        pass's unseen keys (host-tier pulls never insert, so it is a no-op
        there).
        """
        self._pending_sem.acquire()
        pending = _PendingPass()
        # Publish BEFORE the builder runs: end_pass/cancel_pending find
        # the pending through self._pending to wake its boundary wait —
        # an invisible parked builder would sleep a poll interval (or,
        # pre-r08, deadlock against a failed pass).
        self._pending = pending
        if async_build:
            t = threading.Thread(target=self._build,
                                 args=(pass_keys, pending, readonly),
                                 daemon=True)
            pending.thread = t
            t.start()
        else:
            self._build(pass_keys, pending, readonly)

    def wait_feed_pass_done(self) -> None:
        p = self._pending
        if p is not None and p.thread is not None:
            p.thread.join()
        if p is not None and p.error is not None:
            raise p.error

    def cancel_pending(self) -> None:
        """Discard an un-begun pending build (error-path cleanup: a
        pipelined runner that fails mid-pass must not leave an orphaned
        build whose keymap a later retry would silently consume).

        Safe against a builder parked at the boundary: a pass that
        failed MID-training never runs end_pass, so the builder's wait
        would otherwise never release — the cancel event breaks it out
        (pre-r08 this join deadlocked)."""
        p = self._pending
        if p is None:
            return
        p.cancel.set()
        p.resume.set()
        if p.thread is not None:
            p.thread.join()
        if p.keymap is not None:
            p.keymap.close()
        self._pending = None
        self._pending_sem.release()

    # -- pass window -------------------------------------------------------

    def begin_pass(self) -> PassTable:
        """Swap in the pending pass's table (role of BeginPass)."""
        if self._table is not None:
            raise RuntimeError(
                "begin_pass while a pass is active — end_pass first "
                "(an async feed_pass build would deadlock waiting for it)")
        try:
            self.wait_feed_pass_done()
        except BaseException:
            # Failed build: release the pending slot so the caller can
            # retry with a fresh feed_pass instead of deadlocking.
            p = self._pending
            if p is not None and p.keymap is not None:
                p.keymap.close()
            self._pending = None
            self._pending_sem.release()
            raise
        if self._pending is None or self._pending.table is None:
            raise RuntimeError("begin_pass without a successful feed_pass")
        self._current_keys = self._pending.keys
        self._table = self._pending.table
        self._keymap = self._pending.keymap
        self._current_rows = self._pending.rows
        self._pending = None
        self._pass_id += 1
        # Order matters: mark the pass ACTIVE before releasing the
        # pending slot, or a queued async build could observe
        # no-active-pass in the gap, skip the split-pull sequencing, and
        # pull shared keys before this pass's write-back.
        self._no_active_pass.clear()
        self._pending_sem.release()
        log.vlog(1, "begin_pass %d: %d keys, %d shards", self._pass_id,
                 self._current_keys.shape[0], self.num_shards)
        return self._table

    @property
    def table(self) -> PassTable:
        if self._table is None:
            raise RuntimeError("no active pass")
        return self._table

    def update_table(self, table: PassTable) -> None:
        """Trainer hands back the latest device table after push steps."""
        self._table = table

    def lookup_rows(self, batch_keys: np.ndarray) -> np.ndarray:
        """Host map: batch feasigns → device row ids for the active pass
        (native hash lookup, role of CopyKeys' host side; numpy fallback)."""
        if self._current_keys is None or self._table is None:
            raise RuntimeError("no active pass")
        if self._keymap is not None:
            return self._keymap.lookup(batch_keys)
        return map_keys_to_rows(self._current_keys, batch_keys,
                                self._table.rows_per_shard, self.num_shards)

    def abort_if_active(self) -> None:
        """Error-path twin of :meth:`abort_pass`: drop the active pass if
        there is one, no-op otherwise — the pass-retry rollback cannot
        know whether the failure hit before or after begin_pass."""
        if self._table is not None:
            self.abort_pass()

    def abort_pass(self) -> None:
        """Drop the active pass WITHOUT writing back (role of the test
        mode, SetTestMode: eval passes must not dirty or grow the store)."""
        if self._table is None:
            raise RuntimeError("abort_pass without begin_pass")
        self._table = None
        self._current_keys = None
        self._current_rows = None
        if self._keymap is not None:
            self._keymap.close()
            self._keymap = None
        self._release_boundary()

    def _release_boundary(self) -> None:
        """Mark no-active and wake a parked pending builder (its
        ``resume`` event spares it the poll interval). Order matters:
        the shared event first, so a builder woken by either signal sees
        a consistent no-active state."""
        self._no_active_pass.set()
        p = self._pending
        if p is not None:
            p.resume.set()

    def _fuse_boundary(self) -> bool:
        """True when end_pass should run the fused scatter+gather
        program for a split build that is parked awaiting its shared
        remainder."""
        mode = str(flags.flag("pass_boundary_fuse")).lower()
        if mode == "off":
            return False
        p = self._pending
        return (p is not None and p.early_ready.is_set()
                and p.error is None and not p.cancel.is_set()
                and p.fused_table is None
                and hasattr(self.store, "push_and_pull_merge"))

    def end_pass(self) -> None:
        """Write the pass table back to the store (role of EndPass).

        When a split-built next pass is parked awaiting its shared-key
        remainder, the write-back scatter and that remainder gather run
        as ONE fused device program (FLAGS_pass_boundary_fuse): the
        boundary costs one dispatch over the host link instead of two,
        with identical sequencing (the gather reads the post-scatter
        store inside the program)."""
        if self._table is None or self._current_keys is None:
            raise RuntimeError("end_pass without begin_pass")
        faults.faultpoint("pass_engine/write_back")
        with self.timers.scope("end_pass"):
            if self._current_rows is not None and hasattr(
                    self.store, "push_pass_table"):
                # Device tier: one on-device scatter; nothing crosses to
                # the host (the r02 93s D2H+merge wall, VERDICT task 1).
                fused = False
                if self._fuse_boundary():
                    p = self._pending
                    p.fused_table = self.store.push_and_pull_merge(
                        self._current_keys, self._current_rows,
                        self._table, p.rows, p.early_table,
                        p.early_shared)
                    fused = True
                if not fused:
                    self.store.push_pass_table(self._current_keys,
                                               self._current_rows,
                                               self._table)
            else:
                vals = extract_pass_values_host(
                    self._table, self._current_keys.shape[0])
                if hasattr(self.store, "push_from_pass_async"):
                    # Priority split: rows the PENDING pass pulls back
                    # at its boundary push synchronously; the disjoint
                    # bulk remainder overlaps the next pass's training
                    # on the exchange worker. No pending keys yet (or
                    # overlap off) degrades to the serial push inside
                    # push_from_pass_async.
                    p = self._pending
                    nxt = p.keys if p is not None else None
                    pri = (shared_key_mask(nxt, self._current_keys)
                           if nxt is not None and nxt.size else None)
                    self.store.push_from_pass_async(
                        self._current_keys, vals, priority_select=pri,
                        pass_id=self._pass_id)
                else:
                    self.store.push_from_pass(self._current_keys, vals)
        self._table = None
        self._current_keys = None
        self._current_rows = None
        if self._keymap is not None:
            self._keymap.close()
            self._keymap = None
        self._release_boundary()
        monitor.add("pass/ended", 1)

    # -- boundary observability --------------------------------------------

    def boundary_ms(self) -> Dict[str, float]:
        """Cumulative boundary stage ms (delta them per pass): ``end_ms``
        the end_pass write-back (incl. a fused boundary program),
        ``build_ms`` the whole feed_pass build, ``feed_wait_ms`` the
        serial fraction of it — the time the builder sat blocked on the
        active pass. overlap_frac = 1 - feed_wait/build is computed by
        the per-pass reporter from these deltas.

        A store with a background exchange worker (MultiHostStore)
        contributes ``exchange_busy_ms``/``exchange_wait_ms`` — the
        reporter derives boundary.exchange_overlap_frac (1 -
        wait/busy) from their per-pass deltas."""
        snap = self.timers.snapshot_ms()
        out = {"end_ms": snap.get("end_pass", 0.0),
               "build_ms": snap.get("feed_pass", 0.0),
               "feed_wait_ms": snap.get("feed_wait", 0.0)}
        if hasattr(self.store, "exchange_stats"):
            out.update(self.store.exchange_stats())
        return out
