"""Host-sharded parameter service: one shard server per host.

Role of the reference's multi-node sparse tier (the brpc PS cluster the
GPU pass build pulls from, ``ps_gpu_wrapper.cc:362``) re-keyed by the
elastic :class:`~paddlebox_tpu.multihost.keyrange.ShardRangeTable`: each
host runs ONE :class:`ShardServer` owning the keys whose placement hash
lands in its contiguous range, so no host ever holds the full 50M+ key
table. The server speaks the repo's framed typed-wire protocol
(``distributed/wire.py`` — no pickle) through the shared
:class:`~paddlebox_tpu.distributed.rpc.FramedRPCServer` loop, and
clients ride :class:`~paddlebox_tpu.distributed.rpc.FramedRPCConn`'s
reconnect + idempotent-retry machinery (PR 5), so a shard blip on a pure
read costs latency, not the pass.

Replication (``FLAGS_multihost_replicas``, MULTIHOST.md "replicated
tier"): with R > 1 every range SLOT has one primary and R-1 backups on
distinct hosts (:class:`~paddlebox_tpu.multihost.replication.ReplicaMap`).
A server may replicate several slots — each slot's rows live in their
OWN FeatureStore, so promotion is a role flip, not a data move. Writes
(push / apply_rows / shrink) apply on the primary, take the next
sequence number in that slot's
:class:`~paddlebox_tpu.multihost.replication.DeltaJournal`, and forward
synchronously to the backups; a briefly-unreachable backup is marked
lagged and caught up on the next mutation (or an explicit
``sync_replicas``) — journal replay when the gap fits the retained
window, full range snapshot otherwise. Pure reads (pull / pull_serving /
contains) are served by ANY replica of the keys' slot, which is what
lets clients fail over a read to a backup without coordination. A write
reaching a non-primary replica raises a LOUD
:class:`~paddlebox_tpu.multihost.replication.StalePrimaryError`
(transient — the client re-resolves the replica set and retries).
``R == 1`` (the default) never builds a map and every path is
bit-identical to the pre-replication tier.

Wire format (``FLAGS_multihost_wire_dtype``): the ``emb`` field — the
dominant payload — crosses the DCN as f32 (exact, default), f16, or
int8 with per-block f32 scales (``multihost/quant.py``,
``FLAGS_embedding_quant_block``); every other field (w, optimizer
state, show/click) stays f32, and the receiver widens BEFORE anything
accumulates or persists. Reshard row moves and replica
forwards/snapshots always travel f32: they relocate training state,
which must arrive bit-identical.

Checkpoint layout: ``<path>/hostshard-<slot>/<table>.<kind>.npz`` per
PRIMARY slot (backups never save — their primary does), plus the
``.ages.npz`` sidecar carrying per-row unseen-days TTL ages (ONLINE.md).
``load`` is WORLD-AGNOSTIC: every server scans all hostshard dirs (and
a flat single-host dump — migration), keeping only rows in the ranges
of the slots it currently replicates — so a checkpoint written at world
W recovers cleanly into world W', which is what makes a crashed reshard
rollback safe (MULTIHOST.md, "reshard state machine").
"""

from __future__ import annotations

import glob
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from paddlebox_tpu.core import (faults, flags, incident, log, monitor,
                                timeseries, trace)
from paddlebox_tpu.distributed import rpc, wire
from paddlebox_tpu.embedding.store import _FIELDS, FeatureStore
from paddlebox_tpu.embedding.table import TableConfig
from paddlebox_tpu.multihost import quant
from paddlebox_tpu.multihost.keyrange import ShardRangeTable
from paddlebox_tpu.multihost.replication import (DeltaJournal, ReplicaMap,
                                                 StalePrimaryError)

_SPAN = 1 << 64

# Backup-slot epoch while a CHUNKED replica snapshot is mid-stream.
# Never equals a real journal epoch, so a crash between chunks makes the
# next catch-up negotiation fall back to a fresh full snapshot.
_SNAPSHOT_PARTIAL = "~snapshot-partial~"


def wire_mode() -> str:
    mode = flags.flag("multihost_wire_dtype")
    if mode not in ("f32", "f16", "int8"):
        raise ValueError(
            f"unknown multihost_wire_dtype {mode!r} "
            "(want 'f32'/'f16'/'int8')")
    return mode


def encode_emb(emb: np.ndarray, mode: str) -> Dict[str, np.ndarray]:
    """Encode the emb payload for the DCN wire. f32 passes the array
    through UNTOUCHED (the exact path must stay bit-identical)."""
    if mode == "f32":
        return {"emb": emb}
    if mode == "f16":
        return {"emb_f16": np.asarray(emb, np.float32).astype(np.float16)}
    q, scales = quant.quantize_blocked_np(
        emb, int(flags.flag("embedding_quant_block")))
    return {"emb_q": q, "emb_scale": scales,
            "emb_width": np.asarray([emb.shape[1]], np.int64)}


def decode_emb(payload: Dict[str, np.ndarray]) -> np.ndarray:
    """Widen a wire emb payload back to f32 (the only dtype anything
    downstream accumulates or persists in)."""
    if "emb" in payload:
        return payload["emb"]
    if "emb_f16" in payload:
        return payload["emb_f16"].astype(np.float32)
    width = int(payload["emb_width"][0])
    return quant.dequantize_blocked_np(
        payload["emb_q"], payload["emb_scale"], width,
        int(flags.flag("embedding_quant_block")))


def payload_nbytes(payload: Dict[str, np.ndarray]) -> int:
    return int(sum(np.asarray(v).nbytes for v in payload.values()))


class _CoalesceEntry:
    __slots__ = ("keys", "rows", "err", "done")

    def __init__(self, keys: np.ndarray):
        self.keys = keys
        self.rows: Optional[Dict[str, np.ndarray]] = None
        self.err: Optional[Exception] = None
        self.done = False


class _PullCoalescer:
    """Server-side read coalescing: concurrent ``pull`` /
    ``pull_serving`` requests hitting one shard fold into ONE store
    lookup (the ``serving/batcher.py`` window pattern applied to the
    shard tier). Under trainer fan-in the per-slot FeatureStore lock is
    the hot resource; N worker threads queueing on it serially pay N
    lock acquisitions and N gather passes over overlapping keys.

    Protocol: the first request of a round becomes the LEADER — it
    optionally sleeps ``FLAGS_multihost_coalesce_window_ms`` (0 =
    opportunistic: no sleep, riders are whatever piled up while the
    previous round held the store), drains the queue, unions the key
    sets (all sorted unique per the pull contract, so ``np.union1d``
    stays exact), runs the raw lookup ONCE, and scatters each rider's
    slice back via ``np.searchsorted``. Riders block on a timed
    Condition wait (lock-discipline rule: no untimed waits); a rider
    that arrives after the leader's drain claims the NEXT round when
    the busy flag drops. Bit-identity: init rows and ``contains`` are
    per-key deterministic, so a coalesced slice equals the direct
    call's bytes. A leader error fails the whole round loudly — the
    clients' idempotent-retry machinery re-issues.

    Per (server, kind) rounds; ``multihost_coalesce_window_ms < 0``
    disables coalescing entirely (every request takes the direct
    path)."""

    _KINDS = ("pull", "pull_serving")

    def __init__(self, server: "ShardServer"):
        self._srv = server
        self._cv = threading.Condition()
        self._queues: Dict[str, List[_CoalesceEntry]] = {
            k: [] for k in self._KINDS}
        self._busy: Dict[str, bool] = {k: False for k in self._KINDS}

    def rows(self, kind: str, keys: np.ndarray,
             fn: Callable[[np.ndarray], Dict[str, np.ndarray]]
             ) -> Dict[str, np.ndarray]:
        window = float(flags.flag("multihost_coalesce_window_ms"))
        if window < 0 or keys.size == 0:
            return fn(keys)
        ent = _CoalesceEntry(keys)
        with self._cv:
            self._queues[kind].append(ent)
            while not ent.done and self._busy[kind]:
                self._cv.wait(timeout=0.05)
            if not ent.done:
                # Claim leadership of the next round (our entry is
                # still queued — the round serves it with the riders).
                self._busy[kind] = True
        if not ent.done:
            try:
                if window > 0:
                    time.sleep(window / 1e3)
                with self._cv:
                    batch = self._queues[kind]
                    self._queues[kind] = []
                self._serve(batch, fn)
            finally:
                with self._cv:
                    self._busy[kind] = False
                    self._cv.notify_all()
        if ent.err is not None:
            raise ent.err
        assert ent.rows is not None
        return ent.rows

    def _serve(self, batch: List[_CoalesceEntry],
               fn: Callable[[np.ndarray], Dict[str, np.ndarray]]
               ) -> None:
        try:
            if len(batch) == 1:
                batch[0].rows = fn(batch[0].keys)
            else:
                union = batch[0].keys
                for b in batch[1:]:
                    union = np.union1d(union, b.keys)
                rows = fn(union)
                for b in batch:
                    idx = np.searchsorted(union, b.keys)
                    b.rows = {f: v[idx] for f, v in rows.items()}
                self._srv._bump("multihost/coalesced_pulls",
                                len(batch) - 1)
            self._srv._bump("multihost/coalesce_rounds", 1)
        except Exception as e:
            for b in batch:
                b.err = e
        with self._cv:
            for b in batch:
                b.done = True
            self._cv.notify_all()


class ShardServer(rpc.FramedRPCServer):
    """One host's shard(s) of the multi-host embedding tier."""

    def __init__(self, endpoint: str, index: int,
                 ranges: ShardRangeTable,
                 config: TableConfig, *, seed: int = 0,
                 store: Optional[FeatureStore] = None):
        self.index = index
        self.ranges = ranges
        self.config = config
        self._seed = seed
        # Per-slot stores: a replicated server holds one FeatureStore
        # PER slot it participates in (primary or backup), so promotion
        # is a role flip and drop-slot is a dict pop — never a row scan.
        # Unreplicated servers have exactly {index: store}: the legacy
        # single-store layout, byte-identical behavior.
        self._slot_stores: Dict[int, FeatureStore] = {
            index: store if store is not None else FeatureStore(
                config, seed=seed)}
        self._roles: Dict[int, str] = {index: "primary"}
        self._map: Optional[ReplicaMap] = None
        self._journals: Dict[int, DeltaJournal] = {}
        self._applied_seq: Dict[int, int] = {}
        # Per-slot BASELINE EPOCH: names the history a slot store's seq
        # numbers count over ("" = the empty/deterministic-init
        # baseline; hash-chained over checkpoint loads). A seq is only
        # comparable within one epoch — a freshly-loaded primary and a
        # fresh-empty backup both sit at seq 0 with different bytes,
        # and journal replay across that mismatch would silently
        # diverge. Epoch mismatch always forces a full snapshot.
        self._slot_epoch: Dict[int, str] = {index: ""}
        # (slot, backup endpoint) -> {"seq": last acked (None = unknown),
        # "lagged": forward failed, catch up before the next send}.
        self._backup_state: Dict[Tuple[int, str], Dict] = {}
        # Peer conns for replica forwarding; guarded by _peers_lock
        # (forwards for different slots run on different slot locks,
        # and stop() clears the dict from the teardown thread).
        self._peers: Dict[str, "ShardClient"] = {}
        self._peers_lock = threading.Lock()
        # One writer lock over range-mutating sequences (reshard moves /
        # set_range / load): the FeatureStore lock covers single calls,
        # but a pull_range -> drop_range commit must not interleave with
        # a concurrent load's set_all.
        self._mut_lock = threading.Lock()
        # PER-SLOT replication locks serialize apply + journal append +
        # backup forward so backups observe each slot's mutations in
        # seq order. Slot-granular ON PURPOSE: two primaries forwarding
        # to each other concurrently (host A pushes slot 0 -> B while B
        # pushes slot 1 -> A) would deadlock on one server-wide lock,
        # but a slot's primary->backup chain has length 1 and one
        # primary — no cycle is constructible. RLock: shrink/sync paths
        # nest. Ordered AFTER _mut_lock wherever both are held; multi-
        # slot sections acquire slots in sorted order, never during an
        # RPC they initiated.
        self._slot_locks: Dict[int, threading.RLock] = {}
        self._locks_guard = threading.Lock()
        # Per-SERVER registry beside the process-global one (the
        # PredictServer instance-Monitor pattern): in-process multi-
        # server drills run N ShardServers in one interpreter, and
        # per-host assertions (served keys, forward errors, journal
        # lag) need each server's own numbers — the global keeps its
        # process-wide meaning. handle_metrics_snapshot serves this
        # registry to the fleet_top / telemetry_scrape collectors.
        self.metrics = monitor.Monitor()
        # Per-host trend ring (core/timeseries.py) behind the
        # metrics_history RPC; idle until the sampler is armed.
        self.history = timeseries.history_for(self.metrics,
                                              label=f"shard:{index}")
        self._coalescer = _PullCoalescer(self)
        self.service_name = f"shard[{index}]"
        rpc.FramedRPCServer.__init__(self, endpoint, backlog=64)

    def _bump(self, name: str, delta: int = 1) -> None:
        monitor.add(name, delta)
        self.metrics.add(name, delta)

    def _set_gauge(self, name: str, value: float) -> None:
        monitor.set_gauge(name, value)
        self.metrics.set_gauge(name, value)

    def _slot_lock(self, slot: int) -> "threading.RLock":
        with self._locks_guard:
            lk = self._slot_locks.get(slot)
            if lk is None:
                lk = self._slot_locks[slot] = threading.RLock()
            return lk

    def _hold_all_slots(self):
        """Acquire every known slot lock in sorted order (topology /
        load / reset sections — no RPC runs while held)."""
        import contextlib
        stack = contextlib.ExitStack()
        with self._locks_guard:
            slots = sorted(set(self._slot_locks)
                           | set(self._slot_stores) | set(self._roles))
        for slot in slots:
            stack.enter_context(self._slot_lock(slot))
        return stack

    @staticmethod
    def _chain_epoch(prev: str, kind: str, path: str) -> str:
        """Deterministic epoch transition for a checkpoint load: every
        server that applied the same load sequence onto the same prior
        baseline lands on the same epoch string, so post-load journal
        replay needs no snapshot."""
        import hashlib
        h = hashlib.sha1(f"{prev}|{kind}:{path}".encode()).hexdigest()
        return h[:16]

    @property
    def store(self) -> FeatureStore:
        """The store of this server's (first) primary slot — the legacy
        single-slot surface tests and the R=1 paths use."""
        return self._slot_stores[self.index]

    def _after_reply(self) -> bool:
        if not self._running:
            self.stop()
            return True
        return False

    # -- slot routing ------------------------------------------------------

    def _primary_slots(self) -> List[int]:
        return sorted(s for s, r in self._roles.items() if r == "primary")

    def _slot_groups(self, keys: np.ndarray, *, write: bool
                     ) -> List[Tuple[int, Optional[np.ndarray]]]:
        """Group request keys by owning slot; every slot must be locally
        replicated (reads) / locally PRIMARY (writes). ``None`` index =
        the whole (single-slot) request — the common case, since clients
        slice per slot. Subset indices are ascending, so sorted inputs
        stay sorted per group."""
        if keys.size == 0:
            return [(self.index, None)]
        owner = self.ranges.owner_of(keys)
        slots = np.unique(owner)
        for s in slots.tolist():
            role = self._roles.get(int(s))
            if role is None:
                bad = int(s)
                raise ValueError(
                    f"keys not owned by shard {self.index} "
                    f"(first stray owner {bad}) — client range table is "
                    f"stale; re-apply the rank table")
            if write and role != "primary":
                self._bump("multihost/stale_primary_errors", 1)
                incident.note_stale_primary()
                raise StalePrimaryError(
                    f"STALE_PRIMARY: shard {self.index} is {role} for "
                    f"slot {int(s)} — the client's replica map predates "
                    "a promotion/repair; re-resolve and retry")
        if slots.size == 1:
            return [(int(slots[0]), None)]
        return [(int(s), np.flatnonzero(owner == s)) for s in slots]

    def _sub(self, arr: np.ndarray, idx: Optional[np.ndarray]
             ) -> np.ndarray:
        return arr if idx is None else arr[idx]

    # -- replication plumbing ----------------------------------------------

    def _peer(self, endpoint: str) -> "ShardClient":
        with self._peers_lock:
            c = self._peers.get(endpoint)
            if c is None:
                c = self._peers[endpoint] = ShardClient(endpoint)
            return c

    def _replicated(self, slot: int) -> Tuple[str, ...]:
        """Backup endpoints of a slot this server leads (empty when
        unreplicated — the R=1 fast path)."""
        if self._map is None:
            return ()
        return self._map.replicas_of(slot)[1:]

    def _mutate(self, slot: int, op: str, payload: dict, apply_fn) -> None:
        """One slot mutation: apply locally, journal, forward to the
        slot's backups SYNCHRONOUSLY (an unreachable backup is marked
        lagged and caught up later — availability over lockstep; the
        client's push still succeeded on the primary)."""
        backups = self._replicated(slot)
        if not backups and self._map is None:
            apply_fn()      # R=1: nothing else, bit-identical
            return
        with self._slot_lock(slot):
            apply_fn()
            j = self._journals.get(slot)
            if j is None:
                j = self._journals[slot] = DeltaJournal(
                    int(flags.flag("multihost_journal_entries")),
                    epoch=self._slot_epoch.get(slot, ""))
            faults.faultpoint("multihost/journal_append")
            seq = j.append(op, payload)
            if backups:
                faults.faultpoint("multihost/replica_forward")
                self._forward_locked(slot, seq, op, payload)

    def _forward_locked(self, slot: int, seq: int, op: str,
                        payload: dict) -> None:
        # In-sync backups get their replica_apply PIPELINED on the
        # mux'd peer conns (PR 16): all sends go out back-to-back, then
        # the acks are collected — R=3 pays one backup RTT, not two.
        # Out-of-sync backups fall to the sequential catch-up path; a
        # failed pipelined apply falls there too (the peer conn
        # reconnects lazily and journal/snapshot replay is idempotent).
        eps = self._replicated(slot)
        states = {ep: self._backup_state.setdefault(
            (slot, ep), {"seq": None, "lagged": True}) for ep in eps}
        futs: Dict[str, "_ShardFuture"] = {}
        for ep in eps:
            if states[ep]["seq"] == seq - 1:
                try:
                    futs[ep] = self._peer(ep).call_async(
                        "replica_apply", slot=slot, seq=seq, op=op,
                        epoch=self._journals[slot].epoch, **payload)
                except (OSError, ConnectionError, wire.WireError):
                    pass    # send failed: the collect loop catches up
        for ep in eps:
            st = states[ep]
            try:
                try:
                    if ep in futs:
                        futs[ep].result()
                        st["seq"] = seq
                    else:
                        self._catch_up_locked(slot, ep, st)
                        if st["seq"] == seq - 1:
                            self._peer(ep).call(
                                "replica_apply", slot=slot, seq=seq,
                                op=op,
                                epoch=self._journals[slot].epoch,
                                **payload)
                            st["seq"] = seq
                except (OSError, ConnectionError, RuntimeError,
                        wire.WireError):
                    # Direct send bounced (stale conn after a backup
                    # restart, a seq race, a mid-stream drop): one
                    # catch-up attempt — the peer conn reconnects lazily
                    # and the journal/snapshot replay is idempotent. A
                    # backup that is genuinely DOWN fails here too and
                    # stays lagged.
                    self._catch_up_locked(slot, ep, st)
                if st["seq"] < seq:
                    raise ConnectionError(
                        f"backup {ep} slot {slot} at seq {st['seq']}, "
                        f"want {seq}")
                st["lagged"] = False
            except (OSError, ConnectionError, RuntimeError,
                    wire.WireError) as e:
                st["lagged"] = True
                self._bump("multihost/replica_forward_errors", 1)
                log.warning("%s: forward %s seq %d slot %d -> %s failed "
                            "(%r) — backup marked lagged",
                            self.service_name, op, seq, slot, ep, e)

    def _catch_up_locked(self, slot: int, ep: str, st: Dict) -> None:
        """Bring one backup to the journal head: delta replay when the
        journal still covers its gap, full range snapshot otherwise
        (the bounded-re-replication fallback)."""
        peer = self._peer(ep)
        bstate = peer.call("replica_seq", slot=slot)
        bseq, bepoch = int(bstate["seq"]), str(bstate["epoch"])
        j = self._journals[slot]
        # Journal replay is only sound within ONE epoch (same baseline
        # under the seq numbers); anything else snapshots.
        entries = j.since(bseq) if bepoch == j.epoch else None
        if entries is None:
            store = self._slot_stores[slot]
            keys, _ = store.key_stats()
            unseen = store.unseen_for(keys)
            chunk = int(flags.flag("reshard_chunk_rows"))
            n = int(keys.size)
            if chunk <= 0 or n <= chunk:
                peer.call("replica_snapshot", slot=slot, seq=j.seq,
                          epoch=j.epoch, keys=keys,
                          values=store.pull_for_pass(keys),
                          unseen=unseen)
            else:
                # Bounded-memory re-replication: stream the snapshot in
                # FLAGS_reshard_chunk_rows windows so neither side ever
                # materializes the whole slot in one RPC. Chunks are
                # synchronous (strictly ordered); the backup holds the
                # mid-snapshot sentinel epoch until 'last' commits, so
                # a kill -9 between chunks forces a clean re-snapshot.
                for i0 in range(0, n, chunk):
                    i1 = min(i0 + chunk, n)
                    sub = keys[i0:i1]
                    peer.call("replica_snapshot", slot=slot, seq=j.seq,
                              epoch=j.epoch, keys=sub,
                              values=store.pull_for_pass(sub),
                              unseen=unseen[i0:i1],
                              part=("first" if i0 == 0 else
                                    "last" if i1 == n else "mid"))
                    self._bump("multihost/replica_snapshot_chunks", 1)
            self._bump("multihost/replica_snapshots", 1)
            self._bump("multihost/replica_snapshot_rows",
                       int(keys.size))
            log.vlog(0, "%s: slot %d snapshot -> %s (%d rows, seq %d; "
                     "backup was at %d)", self.service_name, slot, ep,
                     keys.size, j.seq, bseq)
        else:
            for e in entries:
                peer.call("replica_apply", slot=slot, seq=e.seq,
                          op=e.op, epoch=j.epoch, **e.payload)
            self._bump("multihost/replica_catchup_entries",
                       len(entries))
            if entries:
                log.vlog(0, "%s: slot %d journal catch-up -> %s "
                         "(%d entries, seq %d -> %d)", self.service_name,
                         slot, ep, len(entries), bseq, j.seq)
        st["seq"] = j.seq

    def adopt_replica_map(self, rmap: ReplicaMap) -> Dict[int, str]:
        """ADOPT a replica-map generation: derive this server's roles
        from its own endpoint, create empty stores for newly assigned
        slots, flip roles (backup→primary = PROMOTION: the slot's store
        already holds the rows, a fresh journal seeds at the applied
        seq), and drop slots no longer replicated here (COMMIT).
        Idempotent — re-adopting the same map is a no-op."""
        with self._mut_lock, self._hold_all_slots():
            new_roles = rmap.slots_of(self.endpoint)
            if not new_roles:
                raise ValueError(
                    f"endpoint {self.endpoint} appears in no slot of "
                    "the replica map — wrong map or drained host")
            cap = int(flags.flag("multihost_journal_entries"))
            for slot, role in new_roles.items():
                old = self._roles.get(slot)
                if slot not in self._slot_stores:
                    self._slot_stores[slot] = FeatureStore(
                        self.config, seed=self._seed)
                    self._slot_epoch.setdefault(slot, "")
                if role == "primary" and old != "primary":
                    faults.faultpoint("multihost/replica_promote")
                    start = self._applied_seq.pop(slot, 0)
                    # The promoted store's (epoch, seq) carries over:
                    # its bytes ARE baseline+seq mutations, and an R=3
                    # sibling backup in the same epoch can keep its
                    # state (same-epoch gap still snapshots, since the
                    # fresh journal holds no entries).
                    self._journals[slot] = DeltaJournal(
                        cap, start_seq=start,
                        epoch=self._slot_epoch.get(slot, ""))
                    if old == "backup":
                        self._bump("multihost/replica_promotes", 1)
                        log.vlog(0, "%s: PROMOTED to primary of slot %d "
                                 "(seq %d)", self.service_name, slot,
                                 start)
                elif role == "backup" and old != "backup":
                    j = self._journals.pop(slot, None)
                    self._applied_seq[slot] = j.seq if j else 0
            for slot in list(self._slot_stores):
                if slot not in new_roles:
                    self._slot_stores.pop(slot)
                    self._journals.pop(slot, None)
                    self._applied_seq.pop(slot, None)
                    self._slot_epoch.pop(slot, None)
            self._roles = new_roles
            self._map = rmap
            self.ranges = rmap.table
            prim = self._primary_slots()
            self.index = prim[0] if prim else sorted(new_roles)[0]
            self._backup_state = {
                (slot, ep): self._backup_state.get(
                    (slot, ep), {"seq": None, "lagged": True})
                for slot in prim
                for ep in rmap.replicas_of(slot)[1:]}
            self.service_name = f"shard[{self.index}]"
            self._set_gauge("multihost/replication",
                            float(rmap.replication))
            return dict(self._roles)

    # -- pull / push (the DCN halves of the lookup exchange) ---------------

    def _pull_rows(self, keys: np.ndarray) -> Dict[str, np.ndarray]:
        """Raw full-row lookup for sorted unique keys (pull_for_pass
        semantics; ``emb`` stays f32 — wire encoding is per-request, on
        top). This is the coalescable unit: one call per coalescing
        round, holding each touched slot store once."""
        groups = self._slot_groups(keys, write=False)
        rows: Optional[Dict[str, np.ndarray]] = None
        for slot, idx in groups:
            part = self._slot_stores[slot].pull_for_pass(
                self._sub(keys, idx))
            if idx is None:
                rows = part
            else:
                if rows is None:
                    rows = {f: np.empty((keys.shape[0],) + v.shape[1:],
                                        v.dtype) for f, v in part.items()}
                for f, v in part.items():
                    rows[f][idx] = v
        return rows

    def handle_pull(self, req) -> Dict[str, np.ndarray]:
        """Full value rows for sorted unique keys in a locally
        replicated slot (pull_for_pass semantics: unseen keys return
        deterministic per-key init rows and are NOT inserted — a pure
        read, declared idempotent by the client, served by primary OR
        backup). ``wire`` selects the emb encoding. Concurrent pulls
        coalesce into one store lookup (``_PullCoalescer``); the wire
        encode and served-keys counter stay per-request."""
        keys = np.asarray(req["keys"], np.uint64)
        rows = self._coalescer.rows("pull", keys, self._pull_rows)
        out: Dict[str, np.ndarray] = {
            f: v for f, v in rows.items() if f != "emb"}
        out.update(encode_emb(rows["emb"], req.get("wire", "f32")))
        self._bump("multihost/served_pull_keys", int(keys.size))
        return out

    def _pull_serving_rows(self, keys: np.ndarray
                           ) -> Dict[str, np.ndarray]:
        """Raw serving lookup: found mask + w + f32 emb (zeros for
        missing keys), per-key deterministic — the coalescable unit
        behind ``handle_pull_serving``."""
        groups = self._slot_groups(keys, write=False)
        n = keys.shape[0]
        found = np.zeros((n,), bool)
        emb: Optional[np.ndarray] = None
        w = np.zeros((n,), np.float32)
        for slot, idx in groups:
            store = self._slot_stores[slot]
            sub = self._sub(keys, idx)
            f = store.contains(sub)
            rows = store.pull_for_pass(sub)
            e = np.ascontiguousarray(rows["emb"], np.float32)
            ww = np.ascontiguousarray(rows["w"], np.float32)
            if not f.all():
                # Masked rows ship zeros (cheap to compress, and the
                # client must not see init values for keys it will
                # serve as unknown anyway).
                e[~f] = 0.0
                ww[~f] = 0.0
            if idx is None:
                found, emb, w = f, e, ww
            else:
                if emb is None:
                    emb = np.zeros((n, e.shape[1]), np.float32)
                found[idx] = f
                emb[idx] = e
                w[idx] = ww
        return {"found": found, "w": w, "emb": emb}

    def handle_pull_serving(self, req) -> Dict[str, np.ndarray]:
        """Serving-tier miss resolution: (found mask, w, wire-encoded
        emb) for sorted unique keys in a locally replicated slot. A PURE
        read like ``pull`` — unseen keys are NOT inserted — but it also
        reports which keys exist (serving must answer zeros for a
        feasign training never saw, not the trainer's init row) and
        ships ONLY the serving fields (emb + w), never optimizer state:
        a replica's miss path reads a fraction of the bytes a trainer
        pull moves. Concurrent calls coalesce like ``pull``."""
        keys = np.asarray(req["keys"], np.uint64)
        rows = self._coalescer.rows("pull_serving", keys,
                                    self._pull_serving_rows)
        out: Dict[str, np.ndarray] = {"found": rows["found"],
                                      "w": rows["w"]}
        out.update(encode_emb(rows["emb"], req.get("wire", "f32")))
        self._bump("multihost/served_serving_keys", int(keys.size))
        return out

    def handle_push(self, req) -> int:
        """EndPass write-back of full rows (emb decoded from the wire
        encoding to f32 BEFORE the store write). Primary-only; the
        decoded f32 rows are what forwards to backups, so replicas stay
        bit-identical to the primary regardless of the client wire."""
        keys = np.asarray(req["keys"], np.uint64)
        groups = self._slot_groups(keys, write=True)
        values = dict(req["values"])
        values["emb"] = decode_emb(values)
        for k in ("emb_f16", "emb_q", "emb_scale", "emb_width"):
            values.pop(k, None)
        for slot, idx in groups:
            sub_k = self._sub(keys, idx)
            sub_v = {f: self._sub(v, idx) for f, v in values.items()}
            self._mutate(
                slot, "push", {"keys": sub_k, "values": sub_v},
                lambda s=slot, k=sub_k, v=sub_v:
                    self._slot_stores[s].push_from_pass(k, v))
        self._bump("multihost/served_push_keys", int(keys.size))
        return int(keys.size)

    # -- replica protocol --------------------------------------------------

    def _require_backup(self, slot: int) -> FeatureStore:
        role = self._roles.get(slot)
        if role != "backup":
            self._bump("multihost/stale_primary_errors", 1)
            incident.note_stale_primary()
            raise StalePrimaryError(
                f"STALE_PRIMARY: shard {self.index} is "
                f"{role or 'no replica'} for slot {slot} — the sender's "
                "replica map predates a promotion/repair")
        return self._slot_stores[slot]

    def handle_replica_apply(self, req) -> int:
        """Backup-side mutation install, strictly in journal order: a
        seq gap raises loudly so the primary falls back to catch-up
        (never a silent divergence)."""
        slot, seq = int(req["slot"]), int(req["seq"])
        with self._slot_lock(slot):
            store = self._require_backup(slot)
            cur = self._applied_seq.get(slot, 0)
            epoch = self._slot_epoch.get(slot, "")
            if str(req.get("epoch", "")) != epoch:
                raise RuntimeError(
                    f"REPLICA_GAP: backup slot {slot} is on epoch "
                    f"{epoch!r}, entry is {req.get('epoch')!r} — "
                    "snapshot required")
            if seq != cur + 1:
                raise RuntimeError(
                    f"REPLICA_GAP: backup slot {slot} at seq {cur}, "
                    f"got {seq} — journal catch-up required")
            op = req["op"]
            if op == "push" or op == "apply":
                store.push_from_pass(
                    np.asarray(req["keys"], np.uint64),
                    dict(req["values"]),
                    unseen=(np.asarray(req["unseen"], np.int32)
                            if "unseen" in req else None))
            elif op == "shrink":
                store.shrink(resolved=(float(req["decay"]),
                                       int(req["ttl"]),
                                       float(req["min_show"])))
            else:
                raise ValueError(f"unknown replica op {op!r}")
            self._applied_seq[slot] = seq
        return seq

    def handle_replica_snapshot(self, req) -> int:
        """Full-slot overwrite install (catch-up past the journal
        window, or initial re-replication COPY). Idempotent.

        Chunked form (bounded-memory re-replication): the primary
        streams the snapshot in FLAGS_reshard_chunk_rows windows —
        ``part='first'`` REPLACES the slot store and stamps the
        mid-snapshot sentinel epoch, ``part='mid'`` appends,
        ``part='last'`` appends then commits the real (seq, epoch).
        A kill -9 between chunks leaves the sentinel epoch, which can
        never equal a primary's epoch, so the next catch-up negotiation
        re-snapshots from scratch instead of trusting a torn store."""
        slot, seq = int(req["slot"]), int(req["seq"])
        part = str(req.get("part", "all"))
        with self._slot_lock(slot):
            store = self._require_backup(slot)
            keys = np.asarray(req["keys"], np.uint64)
            vals = {f: np.asarray(req["values"][f]) for f in _FIELDS}
            unseen = np.asarray(req["unseen"], np.int32)
            if part in ("all", "first"):
                store.set_all(keys, vals, unseen=unseen)
            elif part in ("mid", "last"):
                if self._slot_epoch.get(slot) != _SNAPSHOT_PARTIAL:
                    raise RuntimeError(
                        f"SNAPSHOT_GAP: slot {slot} got snapshot chunk "
                        f"part={part!r} without an open first chunk — "
                        "restart the snapshot")
                if keys.size:
                    store.push_from_pass(keys, vals, unseen=unseen)
            else:
                raise ValueError(f"unknown snapshot part {part!r}")
            if part in ("all", "last"):
                self._applied_seq[slot] = seq
                self._slot_epoch[slot] = str(req.get("epoch", ""))
            else:
                self._slot_epoch[slot] = _SNAPSHOT_PARTIAL
        return int(keys.size)

    def handle_replica_seq(self, req) -> Dict:
        """This backup's applied (seq, epoch) for one slot (pure
        read) — the catch-up negotiation state."""
        slot = int(req["slot"])
        with self._slot_lock(slot):
            self._require_backup(slot)
            return {"seq": int(self._applied_seq.get(slot, 0)),
                    "epoch": self._slot_epoch.get(slot, "")}

    def handle_sync_replicas(self, req) -> Dict[str, int]:
        """Force catch-up of every backup of one primary slot NOW (the
        repair controller's re-replication step and the drills' quiesce
        point). Returns backup endpoint -> acked seq; a still-dead
        backup keeps its lag mark and reports -1."""
        slot = int(req["slot"])
        out: Dict[str, int] = {}
        with self._slot_lock(slot):
            if self._roles.get(slot) != "primary":
                raise StalePrimaryError(
                    f"STALE_PRIMARY: shard {self.index} is not primary "
                    f"of slot {slot}")
            j = self._journals.get(slot)
            if j is None:
                j = self._journals[slot] = DeltaJournal(
                    int(flags.flag("multihost_journal_entries")))
            for ep in self._replicated(slot):
                st = self._backup_state.setdefault(
                    (slot, ep), {"seq": None, "lagged": True})
                try:
                    if st["seq"] != j.seq:
                        self._catch_up_locked(slot, ep, st)
                    st["lagged"] = False
                    out[ep] = int(st["seq"])
                except (OSError, ConnectionError, RuntimeError,
                        wire.WireError) as e:
                    st["lagged"] = True
                    log.warning("%s: sync_replicas slot %d -> %s failed "
                                "(%r)", self.service_name, slot, ep, e)
                    out[ep] = -1
        return out

    def handle_set_replication(self, req) -> Dict:
        roles = self.adopt_replica_map(ReplicaMap.from_dict(req["map"]))
        return {str(s): r for s, r in roles.items()}

    def handle_replica_status(self, req) -> Dict:
        """Introspection for drills/tests: per-slot role, rows, journal
        seq / applied seq, and backup ack state."""
        with self._hold_all_slots():
            slots = {}
            for slot, role in sorted(self._roles.items()):
                j = self._journals.get(slot)
                slots[str(slot)] = {
                    "role": role,
                    "rows": int(self._slot_stores[slot].num_features),
                    "epoch": self._slot_epoch.get(slot, ""),
                    "seq": int(j.seq if j is not None
                               else self._applied_seq.get(slot, 0)),
                    "backups": {
                        ep: int(-1 if st["seq"] is None else st["seq"])
                        for (s, ep), st in self._backup_state.items()
                        if s == slot},
                }
            return {"endpoint": self.endpoint, "index": int(self.index),
                    "slots": slots,
                    "replication": int(self._map.replication
                                       if self._map else 1)}

    # -- reshard protocol --------------------------------------------------

    def handle_pull_range(self, req) -> Dict[str, np.ndarray]:
        """Copy (NOT pop) of every resident row whose placement hash is
        in [lo, hi) — the read-only COPY phase of a reshard move, so a
        crash mid-move loses nothing. Scans every locally replicated
        slot store (one store in the R=1 layout).

        Cursor paging (``after``/``limit``): with ``limit > 0`` the
        reply holds at most ``limit`` rows in global key order starting
        strictly AFTER the ``after`` key, plus ``more``/``next_after``
        so the caller can walk the range in bounded windows
        (FLAGS_reshard_chunk_rows) instead of materializing the whole
        range in one RPC. Pure read — re-pulling any window is free."""
        lo, hi = int(req["lo"]), int(req["hi"])
        after = int(req.get("after", 0) or 0)
        limit = int(req.get("limit", 0) or 0)
        slot_sel: List[Tuple[int, np.ndarray]] = []
        for slot in sorted(self._slot_stores):
            store = self._slot_stores[slot]
            keys, _ = store.key_stats()
            mask = self.ranges.mask_in_range(keys, lo, hi)
            if after:
                mask &= keys > np.uint64(after)
            sel = keys[mask]
            if sel.size:
                slot_sel.append((slot, sel))
        more = False
        next_after = 0
        total = sum(int(s.size) for _, s in slot_sel)
        if limit > 0 and total > limit:
            # The page is the `limit` smallest candidate keys (slot
            # ranges are disjoint, so keys are unique across stores and
            # a <=-cut reproduces the global order exactly).
            cut = np.sort(
                np.concatenate([s for _, s in slot_sel]))[limit - 1]
            slot_sel = [(slot, s[s <= cut]) for slot, s in slot_sel]
            slot_sel = [(slot, s) for slot, s in slot_sel if s.size]
            more = True
            next_after = int(cut)
        parts_k: List[np.ndarray] = []
        parts_v: List[Dict[str, np.ndarray]] = []
        for slot, sel in slot_sel:
            parts_k.append(sel)
            parts_v.append(self._slot_stores[slot].pull_for_pass(sel))
        if not parts_k:
            empty = self._slot_stores[self.index].pull_for_pass(
                np.empty((0,), np.uint64))
            return {"keys": np.empty((0,), np.uint64), "values": empty,
                    "more": False, "next_after": "0"}
        keys = np.concatenate(parts_k)
        vals = {f: np.concatenate([p[f] for p in parts_v])
                for f in parts_v[0]}
        order = np.argsort(keys, kind="stable")
        return {"keys": keys[order],
                "values": {f: v[order] for f, v in vals.items()},
                "more": more, "next_after": str(next_after)}

    def handle_apply_rows(self, req) -> int:
        """Install moved rows (full-row OVERWRITE — naturally idempotent,
        so a replayed move after a crash cannot double-apply). Forwards
        to backups like any other mutation."""
        keys = np.asarray(req["keys"], np.uint64)
        values = dict(req["values"])
        unseen = (np.asarray(req["unseen"], np.int32)
                  if "unseen" in req else None)
        with self._mut_lock:
            if self._map is None:
                # Reshard COPY window: rows land on the DST before the
                # ADOPT re-draws its table, so ownership is checked by
                # the reshard plan, not the (still-old) range table.
                self.store.push_from_pass(keys, values, unseen=unseen)
                return int(keys.size)
            groups = self._slot_groups(keys, write=True)
            for slot, idx in groups:
                sub_k = self._sub(keys, idx)
                sub_v = {f: self._sub(v, idx) for f, v in values.items()}
                payload = {"keys": sub_k, "values": sub_v}
                sub_u = None
                if unseen is not None:
                    sub_u = self._sub(unseen, idx)
                    payload["unseen"] = sub_u
                self._mutate(
                    slot, "apply", payload,
                    lambda s=slot, k=sub_k, v=sub_v, u=sub_u:
                        self._slot_stores[s].push_from_pass(k, v,
                                                            unseen=u))
        return int(keys.size)

    def handle_drop_range(self, req) -> int:
        """COMMIT phase: discard rows in [lo, hi) after every dest has
        acknowledged its copy. Idempotent (an empty range drops 0)."""
        lo, hi = int(req["lo"]), int(req["hi"])
        dropped = 0
        with self._mut_lock:
            for slot in sorted(self._slot_stores):
                store = self._slot_stores[slot]
                keys, _ = store.key_stats()
                mask = self.ranges.mask_in_range(keys, lo, hi)
                sel = keys[mask]
                if sel.size:
                    store.pop_rows(sel)
                    dropped += int(sel.size)
        return dropped

    def handle_set_range(self, req) -> bool:
        """Adopt a new range table (+ this server's index in it) — the
        last step before the drop phase of a reshard. The R=1 elastic
        RESIZE path; a replicated cluster adopts topology through
        ``set_replication`` instead (fixed slot count, endpoints move)."""
        with self._mut_lock:
            if self._map is not None and self._map.replication > 1:
                raise RuntimeError(
                    "set_range on a replicated shard server — elastic "
                    "world resizing runs at replicas=1; use "
                    "set_replication for failover repair (MULTIHOST.md)")
            new_index = int(req["index"])
            if new_index != self.index:
                self._slot_stores[new_index] = self._slot_stores.pop(
                    self.index)
                self._roles = {new_index: "primary"}
                self._slot_epoch[new_index] = self._slot_epoch.pop(
                    self.index, "")
                j = self._journals.pop(self.index, None)
                if j is not None:
                    self._journals[new_index] = j
            self.ranges = ShardRangeTable.from_dict(req["table"])
            self.index = new_index
            self._map = None
            self.service_name = f"shard[{self.index}]"
        return True

    # -- checkpoint / lifecycle --------------------------------------------

    def _shard_dir(self, path: str, slot: Optional[int] = None) -> str:
        d = os.path.join(
            path, f"hostshard-{self.index if slot is None else slot:04d}")
        os.makedirs(d, exist_ok=True)
        return d

    def handle_save(self, req) -> bool:
        """Save every PRIMARY slot to its own hostshard dir (backups
        never save: their primary's dump covers the range, and two
        replicas dumping the same rows would double them on load)."""
        mode = req.get("mode", "base")
        with self._mut_lock:
            for slot in self._primary_slots():
                store = self._slot_stores[slot]
                d = self._shard_dir(req["path"], slot)
                if mode == "base":
                    store.save_base(d)
                elif mode == "delta":
                    store.save_delta(d)
                else:
                    store.save_xbox(d)
        return True

    def _checkpoint_parts(self, path: str, kind: str, lo: int, hi: int
                          ) -> List[Tuple[np.ndarray, Dict,
                                          Optional[np.ndarray]]]:
        """Every (keys, values, ages) part of a checkpoint FILTERED to
        [lo, hi) — hostshard dirs from any world size, plus a flat
        single-host dump (migration path). ``ages`` is the unseen-days
        sidecar (None for pre-sidecar checkpoints — those rows restart
        their TTL lease, the documented legacy behavior)."""
        name = self.config.name
        files = sorted(glob.glob(os.path.join(
            path, "hostshard-*", f"{name}.{kind}.npz")))
        flat = os.path.join(path, f"{name}.{kind}.npz")
        if os.path.exists(flat):
            files.append(flat)
        if not files:
            raise FileNotFoundError(
                f"no {kind} dump for table {name!r} under {path}")
        parts = []
        for f in files:
            data = np.load(f)
            keys = data["keys"].astype(np.uint64)
            mask = self.ranges.mask_in_range(keys, lo, hi)
            if not mask.any():
                continue
            ages = None
            ages_f = f[:-len(".npz")] + ".ages.npz"
            if os.path.exists(ages_f):
                a = np.load(ages_f)["unseen"]
                if a.shape[0] == keys.shape[0]:
                    ages = a[mask].astype(np.int32)
            parts.append((keys[mask],
                          {fld: data[fld][mask] for fld in _FIELDS},
                          ages))
        return parts

    def handle_load(self, req) -> int:
        """World-agnostic load: each locally replicated slot (primary
        AND backup — a recovered cluster comes back fully replicated
        from the checkpoint alone) keeps only rows in its range.
        ``base`` REPLACES contents (set_all semantics, like
        FeatureStore.load); ``delta`` applies on top. Journals reset:
        every replica now holds the same bytes."""
        path, kind = req["path"], req.get("kind", "base")
        total = 0
        with self._mut_lock, self._hold_all_slots():
            for slot in sorted(self._roles):
                store = self._slot_stores[slot]
                lo, hi = self.ranges.range_of(slot)
                parts = self._checkpoint_parts(path, kind, lo, hi)
                if kind == "base":
                    if parts:
                        keys = np.concatenate([k for k, _, _ in parts])
                        vals = {f: np.concatenate(
                            [v[f] for _, v, _ in parts])
                            for f in _FIELDS}
                        ages = np.concatenate(
                            [(a if a is not None
                              else np.zeros(k.shape, np.int32))
                             for k, _, a in parts])
                        order = np.argsort(keys, kind="stable")
                        store.set_all(keys[order],
                                      {f: v[order]
                                       for f, v in vals.items()},
                                      unseen=ages[order])
                    else:
                        store.reset()
                else:
                    for keys, vals, ages in parts:
                        store.push_from_pass(keys, vals, unseen=ages)
                new_epoch = self._chain_epoch(
                    self._slot_epoch.get(slot, ""), kind, path)
                self._slot_epoch[slot] = new_epoch
                j = self._journals.get(slot)
                if j is not None:
                    j.reset(epoch=new_epoch)
                if slot in self._applied_seq:
                    self._applied_seq[slot] = 0
                total += int(store.num_features)
            for st in self._backup_state.values():
                st["seq"] = None
                st["lagged"] = True
        return total

    def handle_reset(self, req) -> bool:
        with self._mut_lock, self._hold_all_slots():
            for slot, store in self._slot_stores.items():
                store.reset()
                self._slot_epoch[slot] = ""
                j = self._journals.get(slot)
                if j is not None:
                    j.reset(epoch="")
                if slot in self._applied_seq:
                    self._applied_seq[slot] = 0
            for st in self._backup_state.values():
                st["seq"] = None
                st["lagged"] = True
        return True

    def handle_shrink(self, req) -> int:
        """Day-boundary lifecycle on this server's PRIMARY slots (the
        FeatureStore resolves FLAGS_table_* decay/TTL/min-show in THIS
        process, and forwards the RESOLVED numbers to backups so a
        backup host with different flags cannot diverge); the
        post-shrink row count is republished as this server's gauge so
        the bounded-store story is observable per host too."""
        from paddlebox_tpu.embedding import lifecycle
        evicted = 0
        with self._mut_lock:
            for slot in self._primary_slots():
                store = self._slot_stores[slot]
                if self._replicated(slot):
                    params = lifecycle.shrink_params(
                        self.config, req.get("min_show", 0.0))
                    box: List[int] = []
                    self._mutate(
                        slot, "shrink",
                        {"decay": float(params[0]), "ttl": int(params[1]),
                         "min_show": float(params[2])},
                        lambda s=store, p=params, b=box:
                            b.append(s.shrink(resolved=p)))
                    evicted += box[0]
                else:
                    evicted += store.shrink(
                        min_show=req.get("min_show", 0.0))
        self._set_gauge(
            "multihost/shard_rows",
            float(sum(self._slot_stores[s].num_features
                      for s in self._primary_slots())))
        return evicted

    def handle_contains(self, req) -> np.ndarray:
        """Membership mask for keys in locally replicated slots (pure
        read — the FeatureStore.contains surface across the wire)."""
        keys = np.asarray(req["keys"], np.uint64)
        groups = self._slot_groups(keys, write=False)
        out = np.zeros(keys.shape, bool)
        for slot, idx in groups:
            got = self._slot_stores[slot].contains(self._sub(keys, idx))
            if idx is None:
                out = got
            else:
                out[idx] = got
        return out

    def handle_unseen_for(self, req) -> np.ndarray:
        """Unseen-days TTL ages for keys in locally replicated slots
        (pure read — the FeatureStore.unseen_for surface across the
        wire; the ages sidecar makes these restart-durable)."""
        keys = np.asarray(req["keys"], np.uint64)
        groups = self._slot_groups(keys, write=False)
        out = np.zeros(keys.shape, np.int32)
        for slot, idx in groups:
            got = self._slot_stores[slot].unseen_for(
                self._sub(keys, idx))
            if idx is None:
                out = got
            else:
                out[idx] = got
        return out

    def handle_key_stats(self, req) -> Dict[str, np.ndarray]:
        """(keys, show) of this server's PRIMARY slots (pure read) —
        the cluster-wide key_stats fan-in's per-server share."""
        ks, shows = [], []
        for slot in self._primary_slots():
            k, sh = self._slot_stores[slot].key_stats()
            ks.append(k)
            shows.append(sh)
        keys = (np.concatenate(ks) if ks
                else np.empty((0,), np.uint64))
        show = (np.concatenate(shows) if shows
                else np.empty((0,), np.float32))
        return {"keys": keys, "show": show}

    def replication_lag(self) -> Dict[str, float]:
        """Per-slot journal lag of this server's primary slots: for
        every (slot, backup) pair, primary seq minus the backup's last
        acked seq (an unacked/never-synced backup counts the full
        journal seq). Returns the worst and the p99 across slots — the
        fleet-wide freshness-of-replicas gauges a scrape reads. An
        approximate stat: read without slot locks (a torn read is off
        by at most the in-flight mutation)."""
        lags: List[int] = []
        journals = dict(self._journals)
        for (slot, _ep), st in list(self._backup_state.items()):
            j = journals.get(slot)
            if j is None:
                continue
            acked = st.get("seq")
            lags.append(max(0, j.seq - (acked if acked is not None
                                        else 0)))
        if not lags:
            return {"worst": 0.0, "p99": 0.0, "pairs": 0.0}
        lags.sort()
        p99 = lags[min(len(lags) - 1,
                       max(0, int(round(0.99 * (len(lags) - 1)))))]
        return {"worst": float(lags[-1]), "p99": float(p99),
                "pairs": float(len(lags))}

    def handle_metrics_snapshot(self, req) -> dict:
        """This server's labeled instance-registry snapshot, with the
        replication-lag gauges computed AT SCRAPE TIME (they are a
        derived view of journal/ack state, not an event counter) — the
        per-host share of the one-scrape cluster snapshot
        (core/telemetry_scrape.py, tools/fleet_top.py)."""
        lag = self.replication_lag()
        self._set_gauge("multihost/replica_lag_worst", lag["worst"])
        self._set_gauge("multihost/replica_lag_p99", lag["p99"])
        return self.metrics.snapshot_all(
            labels={"service": self.service_name,
                    "endpoint": self.endpoint,
                    "shard": int(self.index)})

    def handle_metrics_history(self, req) -> dict:
        """This shard host's trend ring (instance registry: served
        volume, journal lag gauges as of the last scrape) for the
        fleet_top sparkline pane."""
        return self.history.to_dict(window_s=req.get("window_s"),
                                    last_n=req.get("last_n"))

    def handle_stats(self, req) -> Dict[str, int]:
        snap = monitor.snapshot()
        return {"num_features": int(sum(
                    self._slot_stores[s].num_features
                    for s in self._primary_slots())),
                "index": int(self.index),
                "world": int(self.ranges.world),
                "replication": int(self._map.replication
                                   if self._map else 1),
                # Process-level conn health: the failover drills assert
                # the retry budget actually consumed.
                "rpc_reconnects": int(snap.get("rpc/reconnects", 0)),
                "rpc_retries": int(snap.get("rpc/retries", 0))}

    def handle_stop(self, req) -> bool:
        self._running = False
        return True

    def stop(self) -> None:
        """Graceful stop: close the listener; established conns drain
        their in-flight replies (the PS stop-RPC discipline)."""
        with self._peers_lock:
            peers, self._peers = dict(self._peers), {}
        for c in peers.values():
            c.close()
        rpc.FramedRPCServer.stop(self)

    def kill(self) -> None:
        """Host-death simulation for in-process tests/drills: stop AND
        sever every established connection, the way a SIGKILL'd host
        drops its sockets — a lingering persistent client conn must not
        receive one more reply from a corpse."""
        self.stop()
        self.close_connections()


class ShardClient:
    """One client handle to a shard slot's servers: a thin FramedRPCConn
    wrapper declaring the idempotent methods. ``replicas_fn`` wires the
    conn's reconnect-time ``resolve`` hook to the slot's CURRENT
    replica set — the conn always re-points at the set's PRIMARY, so a
    retried pull/push after a primary death (and the repair
    controller's promotion) lands on the live primary instead of
    burning ``FLAGS_rpc_retry_deadline_s`` on the dead endpoint — the
    same fix PR 11 gave PredictClient.

    Pure READS additionally fail over across the slot's backups when
    the primary stays unreachable (any replica serves them — a shard
    host kill -9 under serving traffic costs a reconnect, not an
    error); the failover conn sticks until the next failure or a
    topology refresh rebuilds the client. Writes never fail over: a
    backup answers them with the loud transient STALE_PRIMARY contract.

    ``push`` IS declared idempotent: a shard push is a full-row
    overwrite keyed by feasign (replaying it writes the same bytes), so
    retry-after-reconnect can never double-apply."""

    #: Methods any replica may answer (pure reads).
    READS = frozenset(("pull", "pull_serving", "pull_range", "stats",
                       "contains", "unseen_for", "key_stats",
                       "replica_seq", "replica_status"))

    def __init__(self, endpoint: str, *, timeout: float = 60.0,
                 replicas_fn=None):
        self.endpoint = endpoint
        self._timeout = timeout
        self._replicas_fn = replicas_fn
        try:
            self._conn = self._make_conn(endpoint)
        except (OSError, ConnectionError):
            if replicas_fn is None:
                raise
            # Replicated slot with a dead primary AT CLIENT BUILD TIME
            # — a replica joining mid-failover (the autopilot spawns
            # joiners precisely while hosts are dying). Defer: the
            # first call builds the conn, and its read failover walks
            # the replica set if the primary is still down.
            self._conn = None

    def _make_conn(self, endpoint: str) -> rpc.FramedRPCConn:
        return rpc.FramedRPCConn(
            endpoint, timeout=self._timeout, service_name="shard",
            idempotent=("pull", "pull_serving", "pull_range", "stats",
                        "contains", "unseen_for", "key_stats",
                        "replica_seq", "replica_status", "push"),
            resolve=(self._resolve if self._replicas_fn is not None
                     else None))

    def _resolve(self, current: str) -> str:
        """Reconnect target: the slot's CURRENT primary (after a
        promotion/repair refreshed the map, that is the live one)."""
        eps = tuple(self._replicas_fn() or ())
        return eps[0] if eps else current

    def call(self, method: str, **kw):
        try:
            conn = self._conn
            if conn is None:
                conn = self._conn = self._make_conn(self.endpoint)
            return conn.call(method, **kw)
        except (OSError, ConnectionError, wire.WireError):
            if self._replicas_fn is None or method not in self.READS:
                raise
            # Try every replica in map order, PRIMARY FIRST, on a fresh
            # conn — the failed conn may have been swapped/closed under
            # us by a concurrently failing thread, so its endpoint says
            # nothing about who is dead.
            eps = tuple(self._replicas_fn() or ())
            for ep in eps:
                try:
                    conn = self._make_conn(ep)
                    out = conn.call(method, **kw)
                except (OSError, ConnectionError, wire.WireError):
                    continue
                # Stick to the live replica (swap BEFORE closing the
                # old conn: another thread mid-call on it will fail and
                # re-enter this loop against the full candidate list).
                old, self._conn = self._conn, conn
                try:
                    if old is not None:
                        old.close()
                except OSError:
                    pass
                monitor.add("multihost/replica_failovers", 1)
                # The failover HOP is part of the request's story: the
                # instant carries the active trace id (when traced), so
                # a merged trace shows which replica answered after the
                # primary died.
                trace.instant("multihost/replica_failover",
                              method=method, endpoint=ep)
                log.warning("shard client: read %s failed over to "
                            "replica %s", method, ep)
                return out
            raise

    def call_async(self, method: str, **kw) -> "_ShardFuture":
        """Pipelined call on the underlying mux conn (PR 16): N
        ``call_async`` results share one round trip instead of N.
        ``result()`` applies the same fallback as :meth:`call` — a
        transport failure on a method :meth:`call` would retry/fail
        over re-issues it synchronously through :meth:`call`; anything
        else re-raises (the caller owns catch-up, exactly as with the
        blocking path)."""
        conn = self._conn
        if conn is None:
            try:
                conn = self._conn = self._make_conn(self.endpoint)
            except (OSError, ConnectionError):
                if method not in _ShardFuture._REISSUE:
                    raise
                # Dead primary on a deferred conn: resolve through the
                # synchronous failover path at result() time.
                return _ShardFuture(self, None, method, kw)
        return _ShardFuture(self, conn.call_async(method, **kw),
                            method, kw)

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()


class _ShardFuture:
    """Future returned by :meth:`ShardClient.call_async`: resolves the
    pipelined reply, falling back to the client's synchronous
    retry/failover path when the transport died and the method is safe
    to re-issue (a read, or the idempotent-by-contract ``push``)."""

    _REISSUE = ShardClient.READS | frozenset(("push",))

    def __init__(self, client: ShardClient, fut, method: str, kw: dict):
        self._client = client
        self._fut = fut
        self._method = method
        self._kw = kw

    def result(self, timeout: Optional[float] = None):
        if self._fut is None:
            # call_async could not even build a conn to the primary
            # (deferred-conn client, primary dead): straight to the
            # synchronous failover path.
            return self._client.call(self._method, **self._kw)
        try:
            return self._fut.result(timeout)
        except (OSError, ConnectionError, wire.WireError):
            if self._method not in self._REISSUE:
                raise
            return self._client.call(self._method, **self._kw)


def start_local_shards(world: int, config: TableConfig, *, seed: int = 0,
                       replicas: int = 1
                       ) -> Tuple[List[ShardServer], List[str]]:
    """Loopback cluster on 127.0.0.1 ephemeral ports (tests, drills and
    examples). ``replicas`` > 1 wires the
    ring replica map across the started servers."""
    ranges = ShardRangeTable.for_world(world)
    servers = [ShardServer("127.0.0.1:0", i, ranges, config, seed=seed)
               for i in range(world)]
    eps = [s.endpoint for s in servers]
    if replicas > 1:
        rmap = ReplicaMap.ring(eps, replicas, ranges)
        for s in servers:
            s.adopt_replica_map(rmap)
    return servers, eps


def stop_shards(servers: List[ShardServer]) -> None:
    for s in servers:
        try:
            s.stop()
        except Exception as e:  # best-effort teardown
            log.vlog(1, "shard stop failed: %s", e)
