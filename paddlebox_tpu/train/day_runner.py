"""DayRunner: the production day/pass training loop.

Role of the reference's outer CTR main loop (SURVEY.md §3.1 + FleetUtil,
``fleet_util.py:368-1196``): per day — for each online pass, load that
pass's data splits, shuffle, ``begin_pass → train → end_pass``, publish a
pass-level delta; at day end — shrink the table, dump the day-level base,
and publish both through the atomic done-file index. On restart, resume
from the done-file recovery chain (last base + following deltas), which
is exactly what the elastic manager's membership-change callback needs.

TPU-first: the runner is a thin host orchestration shell — all heavy
work is already in Dataset (threaded columnar load), PassEngine (table
build), and CTRTrainer's single jitted step. File layout convention:
``<data_root>/<day>/<split>/part-*`` with pass groups from
``get_online_pass_interval``.
"""

from __future__ import annotations

import glob
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from paddlebox_tpu.checkpoint.protocol import (CheckpointProtocol,
                                               get_online_pass_interval)
from paddlebox_tpu.core import (faults, flags, log, monitor, quality,
                                report, timers, trace, watchdog)
from paddlebox_tpu.data.dataset import Dataset


class DayRunner:
    """Drives a CTRTrainer through days of pass-addressed data."""

    def __init__(self, trainer, feed_config, output_root: str, *,
                 data_root: str = "",
                 split_interval: int = 60, split_per_pass: int = 1,
                 hours: Sequence[int] = tuple(range(24)),
                 is_data_hourly_placed: bool = True,
                 shuffle: bool = True,
                 num_reader_threads: int = 4,
                 filelist_fn: Optional[Callable[[str, List[str]],
                                                List[str]]] = None,
                 min_show_shrink: float = 0.0,
                 save_xbox: bool = False,
                 pipeline_passes: bool = True,
                 is_rank0: bool = True,
                 pass_boundary_hook: Optional[Callable[[str, int],
                                                       None]] = None,
                 pass_retry_hook: Optional[Callable[[str, int,
                                                     BaseException],
                                                    None]] = None):
        self.trainer = trainer
        self.feed_config = feed_config
        self.data_root = data_root
        self.ckpt = CheckpointProtocol(output_root, is_rank0=is_rank0)
        self.pass_splits = get_online_pass_interval(
            list(hours), split_interval, split_per_pass,
            is_data_hourly_placed)
        self.shuffle = shuffle
        self.num_reader_threads = num_reader_threads
        self.filelist_fn = filelist_fn or self._default_filelist
        self.min_show_shrink = min_show_shrink
        self.save_xbox = save_xbox  # serving export per pass (xbox role)
        # Overlap pass k+1's data load + table build with pass k's
        # training (role of PreLoadIntoMemory/WaitFeedPassDone,
        # box_wrapper.h:1140,1161, and the double-buffered build threads,
        # ps_gpu_wrapper.cc:907).
        self.pipeline_passes = pipeline_passes
        self.is_rank0 = is_rank0
        # Called after each pass's delta is PUBLISHED — the checkpointed
        # boundary where cluster-topology events (the multihost elastic
        # reshard, multihost/reshard.py) are safe: the hook's state
        # transition is covered by recovery_chain(), and the hook owns
        # its own rollback (a leaked transient here would re-enter the
        # pass retry loop and replay an already-published pass).
        self.pass_boundary_hook = pass_boundary_hook
        # Called on a TRANSIENT pass failure BEFORE the rollback reload:
        # the seam where a replicated multihost tier repairs its
        # topology (promote a surviving backup off a dead shard host,
        # multihost/reshard.py ElasticReshardController.repair) so the
        # reset + recovery-chain reload that follows reaches only live
        # servers. Hook errors are logged, never raised — a broken
        # repair hook must not turn a retryable failure fatal.
        self.pass_retry_hook = pass_retry_hook
        self.timers = timers.TimerGroup()
        # Pipelined next-pass preload in flight (train_day): the pass
        # retry path must be able to join + invalidate it, so the handle
        # lives on self, not in train_day's locals.
        self._inflight_preload = None

    # -- data addressing ---------------------------------------------------

    def _default_filelist(self, day: str, splits: List[str]) -> List[str]:
        files: List[str] = []
        for s in splits:
            files.extend(sorted(glob.glob(
                os.path.join(self.data_root, day, s, "part-*"))))
        return files

    # -- recovery ----------------------------------------------------------

    def _save_dense(self, model_dir: str) -> None:
        """Dense params + optimizer state beside the sparse checkpoint
        (written BEFORE the done-file publish, so a published record
        always implies a complete model)."""
        from paddlebox_tpu.checkpoint.dense import save_pytree
        save_pytree({"params": self.trainer.params,
                     "opt_state": self.trainer.opt_state},
                    os.path.join(model_dir, "dense.npz"))

    def _load_dense(self, model_dir: str) -> bool:
        import zipfile

        from paddlebox_tpu.checkpoint.dense import (CheckpointCorruptError,
                                                    load_pytree)
        path = os.path.join(model_dir, "dense.npz")
        if not os.path.exists(path):
            return False
        template = {"params": self.trainer.params,
                    "opt_state": self.trainer.opt_state}
        try:
            state, _step = load_pytree(template, path)
        except (CheckpointCorruptError, zipfile.BadZipFile, EOFError,
                ValueError, OSError) as e:
            # Torn/corrupt dense.npz (crash mid-write before the fsync
            # discipline existed, disk corruption): one more warned
            # skip-to-older-record case — the restart this checkpoint
            # exists to serve must not die on it.
            log.warning("day_runner: dense checkpoint %s is corrupt "
                        "(%s) — skipping it", path, e)
            return False
        except KeyError as e:
            # Structure mismatch — e.g. the optimizer config changed
            # (grad_clip_norm re-nests opt_state under optax.chain) since
            # the checkpoint was written. Recovery falls back to an older
            # record or a warned fresh-dense resume rather than aborting.
            log.warning("day_runner: dense checkpoint %s does not match "
                        "the current optimizer/model structure (%s) — "
                        "skipping it", path, e)
            return False
        # Same key paths can still carry different SHAPES (model config
        # changed): restoring them would train garbage or crash later in
        # the jitted step — reject here with the same warned fallback.
        import jax
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(template)):
            if np.shape(a) != np.shape(b):
                log.warning(
                    "day_runner: dense checkpoint %s leaf shape %s != "
                    "current model's %s — skipping it", path,
                    np.shape(a), np.shape(b))
                return False
        # load_pytree returns HOST-format leaves; re-place them into the
        # trainer's live layout (replicated, ZeRO-sharded, or host-
        # pinned per FLAGS_dense_zero) — checkpoints are layout- and
        # world-agnostic, exactly like the sparse shard loads.
        self.trainer.params, self.trainer.opt_state = (
            self.trainer.place_dense(state["params"], state["opt_state"]))
        return True

    def recover(self) -> Optional[Dict[str, object]]:
        """Load last base + subsequent deltas from the done-file (role of
        the elastic restart consumers). Returns the resume point
        ``{"day": str, "pass_id": int}`` — the last day/pass whose state
        is already in the store — or None when starting fresh. The point
        is also remembered so a direct ``train_day`` call (the elastic
        worker pattern) skips already-published passes; pass_id 0 means
        the day completed through its base dump.

        A chain with deltas but NO base (crash during the first day)
        loads the deltas onto the fresh store — resuming costs at most
        the in-flight pass even before the first day-end base exists."""
        base, deltas = self.ckpt.recovery_chain()
        if base is None and not deltas:
            log.vlog(0, "day_runner: no published model, fresh start")
            self._recover_point = None
            return None
        store = self.trainer.engine.store
        if base is not None:
            store.load(base.path, "base")
        for d in deltas:
            store.load(d.path, "delta")
        # Dense state from the NEWEST record that carries it (sparse
        # deltas are cumulative; dense checkpoints are full snapshots).
        for rec in [*reversed(deltas)] + ([base] if base else []):
            if self._load_dense(rec.path):
                log.vlog(0, "day_runner: dense state from %s", rec.path)
                break
        else:
            log.warning("day_runner: no dense checkpoint in the recovery "
                        "chain — dense towers resume from current "
                        "(likely fresh) init")
        log.vlog(0, "day_runner: recovered base %s + %d deltas (day %s)",
                 base.path if base else "<none>", len(deltas),
                 base.day if base else (deltas[-1].day if deltas else "?"))
        if deltas:
            last = deltas[-1]
            point = {"day": last.day, "pass_id": last.pass_id}
        else:
            point = {"day": base.day, "pass_id": 0}
        self._recover_point = point
        return point

    # -- day loop ----------------------------------------------------------

    def _load_dataset(self, day: str, pass_id: int, files: List[str], *,
                      feed: bool) -> Dataset:
        """Load a pass's files, hand the engine its keys (``feed``),
        then shuffle: the one order every pass takes, preloaded or not.
        A local shuffle permutes the rows of one key set, so the keys
        are whole when the last chunk is in (the load unites them as it
        goes) and the pass table builds while the rows are permuted."""
        faults.faultpoint("day_runner/load")
        ds = Dataset(self.feed_config,
                     num_reader_threads=self.num_reader_threads)
        ds.set_filelist(files)
        with trace.span("ingest/load", day=day, pass_id=pass_id):
            ds.load_into_memory()
        if feed:
            self._feed_keys(ds, day, pass_id)
        if self.shuffle:
            # Deterministic digest — hash(str) is randomized per
            # process, which would make recovery replays and per-rank
            # batch orders irreproducible.
            import zlib
            with trace.span("ingest/shuffle", day=day, pass_id=pass_id):
                faults.faultpoint("day_runner/shuffle")
                ds.local_shuffle(
                    seed=zlib.crc32(f"{day}:{pass_id}".encode()))
        return ds

    def _feed_keys(self, ds: Dataset, day: str, pass_id: int) -> None:
        """Register an online pass's keys with the async build: with the
        split-key early build the engine overlaps everything it legally
        can with the active pass, and with the shuffle of THIS thread;
        begin_pass joins the build and raises its errors."""
        eng = self.trainer.engine
        with trace.span("ingest/pass_keys", day=day, pass_id=pass_id):
            keys = [ds.pass_keys(slots=g.slots) for g in eng.groups]
        # Parked on the engine's pending slot until the active pass's
        # begin_pass frees it.
        with trace.span("ingest/feed_pass", day=day, pass_id=pass_id):
            eng.feed_pass(keys, async_build=True)

    def _start_preload(self, day: str, pass_id: int, files: List[str]):
        """Background: load pass k+1's data, kick its table build and
        shuffle while pass k trains. feed_pass blocks until pass k's
        begin_pass frees the pending slot, and the build's store pull is
        internally sequenced after pass k's end_pass write-back (split
        pull: only the shared-key intersection waits)."""
        import threading

        out = {"ds": None, "error": None}

        def body():
            try:
                faults.faultpoint("day_runner/preload")
                out["ds"] = self._load_dataset(day, pass_id, files,
                                               feed=True)
            except BaseException as e:
                out["error"] = e

        t = threading.Thread(target=body, daemon=True)
        t.start()
        out["thread"] = t
        self._inflight_preload = out
        return out

    def train_pass(self, day: str, pass_id: int, files: List[str], *,
                   dataset: Optional[Dataset] = None,
                   feed_keys: bool = True) -> Dict[str, float]:
        """One online pass: load → shuffle → train → delta checkpoint.
        ``dataset``/``feed_keys`` let the pipelined day loop hand in a
        preloaded dataset whose table build is already in flight.

        Self-healing (``FLAGS_pass_max_retries``): a TRANSIENT failure
        (IO/connection/timeout, an injected drill fault, a watchdog
        stall) costs one pass retry, not the day — each retry drops the
        pending build, rolls the sparse store + dense state back to the
        last published record, reloads the pass's data with its
        deterministic shuffle, and replays; the retried pass is
        bit-identical to an unfailed run. Fatal errors (bad data, NaN
        loss, code bugs) raise immediately."""
        max_retries = max(0, int(flags.flag("pass_max_retries")))
        # Dense pre-pass snapshot (HOST copies — the train step donates
        # the device buffers, so by failure time the originals are
        # deleted): the rollback source when NO published record carries
        # dense state yet (a first-day first-pass failure — self.params
        # is only committed at train_pass success, so this equals the
        # last published dense whenever one exists).
        dense_snap = None
        if max_retries:
            import jax
            dense_snap = jax.tree.map(
                lambda x: np.array(x),
                (self.trainer.params, self.trainer.opt_state))
        attempt = 0
        while True:
            wd_armed = watchdog.arm_from_flags(
                phase=f"day {day} pass {pass_id}")
            try:
                return self._train_pass_inner(day, pass_id, files,
                                              dataset=dataset,
                                              feed_keys=feed_keys)
            except BaseException as e:
                # EVERY failure path drops the pending build (load error,
                # train-step error, checkpoint error): an exception
                # between feed_pass and begin_pass would otherwise orphan
                # a build holding the one-slot semaphore — a retry (or
                # the elastic restart's next pass) would deadlock in
                # feed_pass or silently consume the wrong pass's
                # table/keymap. The engine's cancellable boundary wait
                # makes this safe even when the failed pass never ran
                # end_pass.
                self.trainer.engine.cancel_pending()
                if attempt >= max_retries or not faults.is_transient(e):
                    raise
                attempt += 1
                monitor.add("pass/retries", 1)
                log.warning(
                    "day %s pass %d failed with transient %s: %r — "
                    "rolling back and retrying (%d/%d)", day, pass_id,
                    type(e).__name__, e, attempt, max_retries)
                trace.instant("pass/retry", day=day, pass_id=pass_id,
                              attempt=attempt, error=repr(e))
                if self.pass_retry_hook is not None:
                    try:
                        self.pass_retry_hook(day, pass_id, e)
                    except Exception as he:
                        log.warning("pass_retry_hook failed (%r) — "
                                    "continuing with the rollback", he)
                self._rollback_for_retry(dense_snap)
                backoff = min(
                    float(flags.flag("pass_retry_backoff_s"))
                    * (2.0 ** (attempt - 1)),
                    float(flags.flag("pass_retry_backoff_max_s")))
                if backoff > 0:
                    time.sleep(backoff)
                # Replay from scratch: the handed-in dataset/build may be
                # partially consumed or mid-flight — a fresh load with
                # the deterministic day:pass shuffle seed reproduces the
                # exact batch order of an unfailed run.
                dataset, feed_keys = None, True
            finally:
                if wd_armed:
                    watchdog.disarm()

    def _rollback_for_retry(self, dense_snap) -> None:
        """Restore the model to the last published state so the retry
        replays the pass against exactly the inputs an unfailed run
        would have seen.

        - Active pass dropped WITHOUT write-back (it may be mid-train).
        - Sparse store reset and rebuilt from ``recovery_chain()`` (the
          failed attempt may have inserted the pass's unseen keys, or —
          when the failure hit AFTER end_pass, in save/publish — already
          written the pass's updates back; replaying on top would
          double-apply them).
        - Dense state from the newest published record carrying it,
          falling back to the pre-pass in-memory snapshot (identical
          whenever a published record exists; the only source before the
          first publish).
        """
        eng = self.trainer.engine
        # An in-flight NEXT-pass preload (pipelined day loop) may still
        # be loading data or building its table: join it so its
        # feed_pass has happened, then cancel that build too — its
        # boundary state is stale after the rollback. The slot it would
        # wait on is already free (the caller's cancel_pending ran).
        pre = getattr(self, "_inflight_preload", None)
        if pre is not None and pre.get("thread") is not None:
            pre["thread"].join()
            pre["cancelled"] = True
        eng.cancel_pending()
        eng.abort_if_active()
        store = eng.store
        base, deltas = self.ckpt.recovery_chain()
        if hasattr(store, "reset"):
            store.reset()
        elif base is None:
            log.warning("day_runner: store %s has no reset(); rollback "
                        "without a base may leave the failed attempt's "
                        "writes in place", type(store).__name__)
        if base is not None:
            store.load(base.path, "base")
        for d in deltas:
            store.load(d.path, "delta")
        for rec in [*reversed(deltas)] + ([base] if base else []):
            if self._load_dense(rec.path):
                log.vlog(0, "day_runner: rollback dense from %s", rec.path)
                break
        else:
            params, opt = dense_snap
            self.trainer.params, self.trainer.opt_state = (
                self.trainer.place_dense(params, opt))
        monitor.add("pass/rollbacks", 1)

    def _train_pass_inner(self, day: str, pass_id: int, files: List[str],
                          *, dataset: Optional[Dataset],
                          feed_keys: bool) -> Dict[str, float]:
        report.init_telemetry_from_flags()
        faults.init_from_flags()
        # Stamp the quality tracker with this pass's identity (non-
        # override: a stream manifest's richer context wins) so the
        # quality_report line names day/pass beside the pass_report.
        quality.GLOBAL.set_pass_context(day, pass_id, override=False)
        with self.timers.scope("load"), \
                trace.span("day/load", day=day, pass_id=pass_id):
            if dataset is None:
                # Not preloaded (an unpipelined day, a retry's replay):
                # the same load -> keys -> feed -> shuffle, on this thread.
                ds = self._load_dataset(day, pass_id, files, feed=feed_keys)
                feed_keys = False
            else:
                ds = dataset
        self.trainer.reset_metrics()
        with self.timers.scope("train"), \
                trace.span("day/train", day=day, pass_id=pass_id):
            stats = self.trainer.train_pass(ds, feed_keys=feed_keys)
        if "pass_report" in stats:
            # How far the pass's keys were united under its load.
            runs, merged = ds.key_run_counts()
            stats["pass_report"].update(
                ingest_key_runs=runs, ingest_key_runs_merged_in_load=merged)
        if self.is_rank0:
            # Only rank 0 writes model files — N ranks racing
            # savez on one shared path would corrupt the npz.
            with self.timers.scope("save_delta"), \
                    trace.span("day/save_delta", day=day,
                               pass_id=pass_id):
                faults.faultpoint("day_runner/save")
                mdir = self.ckpt.model_dir(day, pass_id)
                self.trainer.engine.store.save_delta(mdir)
                # Dense state rides with every sparse checkpoint (role
                # of save_persistables beside the table dumps): a
                # recovery that reloads the table but restarts the
                # dense towers from init would resume an inconsistent
                # model. data_norm stats live in params and ride too.
                self._save_dense(mdir)
                faults.faultpoint("day_runner/publish")
                self.ckpt.publish(day, pass_id)
            if self.save_xbox and hasattr(self.trainer.engine.store,
                                          "save_xbox"):
                with self.timers.scope("save_xbox"), \
                        trace.span("day/save_xbox", day=day,
                                   pass_id=pass_id):
                    self.trainer.engine.store.save_xbox(
                        self.ckpt.model_dir(day, pass_id))
                    self.ckpt.publish_xbox(day, pass_id)
        if self.pass_boundary_hook is not None:
            with trace.span("day/pass_boundary_hook", day=day,
                            pass_id=pass_id):
                self.pass_boundary_hook(day, pass_id)
        ds.clear()
        monitor.add("day_runner/passes", 1)
        # One report path: the day-loop timers land in the registry
        # (and thus the metrics JSONL) beside the trainer's pass stages.
        self.timers.publish("day_runner")
        log.vlog(0, "day %s pass %d: %s | %s", day, pass_id, stats,
                 self.timers.report())
        return stats

    def train_day(self, day: str,
                  start_pass: Optional[int] = None
                  ) -> List[Dict[str, float]]:
        """All passes of one day, then shrink + base dump (the day
        boundary sequence the reference runs: shrink → SaveBase →
        write_model_donefile).

        ``start_pass=None`` derives the start from the last ``recover()``
        point: a recovered pass of THIS day resumes after it, and a
        recovered day BASE (pass 0 — the day finished) skips the day
        outright — an elastic restart landing after the day completed
        must not retrain it and republish its passes (observed: a
        post-completion join regenerated deltas 1..6 over a finished
        day before this guard)."""
        # Arm fault injection before the FIRST dataset load/preload —
        # waiting for train_pass would leave the early load sites
        # un-drillable (and racy from the preload thread).
        faults.init_from_flags()
        if start_pass is None:
            p = getattr(self, "_recover_point", None)
            if p is not None and p["day"] == str(day):
                if p["pass_id"] == 0:
                    log.vlog(0, "day %s already complete in the recovery "
                             "chain: skipping", day)
                    return []
                start_pass = int(p["pass_id"]) + 1
            else:
                start_pass = 1
        all_stats = []
        resumed_past = 0  # passes skipped because recovery already holds them
        jobs: List = []
        for pass_id, splits in enumerate(self.pass_splits, start=1):
            files = self.filelist_fn(day, splits)
            if pass_id < start_pass:
                resumed_past += bool(files)
                continue
            if not files:
                log.warning("day %s pass %d: no files for splits %s, "
                            "skipping", day, pass_id, splits)
                continue
            jobs.append((pass_id, files))

        preloaded = None
        try:
            for i, (pass_id, files) in enumerate(jobs):
                if preloaded is not None:
                    with trace.span("day/preload_join", day=day,
                                    pass_id=pass_id):
                        preloaded["thread"].join()
                    self._inflight_preload = None
                    if preloaded["error"] is not None:
                        raise preloaded["error"]
                    ds, feed_keys = preloaded["ds"], False
                    if preloaded.get("cancelled"):
                        # The previous pass's retry rollback cancelled
                        # this preload's table build — re-feed from the
                        # (still loaded) dataset so begin_pass has a
                        # fresh build against the rolled-back store.
                        self._feed_keys(ds, day, pass_id)
                elif self.pipeline_passes:
                    # First pass of the day: load + feed here so training
                    # can begin while the NEXT pass preloads. Async build
                    # (the default): begin_pass joins it; a build error
                    # surfaces there, inside the same try as every other
                    # pass failure.
                    ds = self._load_dataset(day, pass_id, files, feed=True)
                    feed_keys = False
                else:
                    ds, feed_keys = None, True
                preloaded = None
                if self.pipeline_passes and i + 1 < len(jobs):
                    preloaded = self._start_preload(day, *jobs[i + 1])
                all_stats.append(self.train_pass(day, pass_id, files,
                                                 dataset=ds,
                                                 feed_keys=feed_keys))
        except BaseException:
            # A failed pass must not leave the NEXT pass's in-flight
            # preload occupying the engine's pending slot — a retry
            # would consume the orphaned (wrong-pass) table/keymap.
            if preloaded is not None:
                preloaded["thread"].join()
            self._inflight_preload = None
            self.trainer.engine.cancel_pending()
            raise
        if not all_stats and not resumed_past:
            # A day that trained nothing (data outage) must not decay the
            # model or publish a base marking the day done — the data may
            # arrive late and the day must remain trainable. Resuming
            # after the day's LAST delta is different: those passes are
            # already in the store, so day-end below must still run or
            # the day would never get its shrink + base.
            log.warning("day %s: no trainable passes; skipping day-end "
                        "shrink/base", day)
            return all_stats
        evicted = self.day_end(day)
        log.vlog(0, "day %s done: %d passes, %d evicted", day,
                 len(all_stats), evicted)
        return all_stats

    def day_end(self, day: str) -> int:
        """The day-boundary sequence the reference runs: table lifecycle
        shrink (show/click decay + unseen-days TTL + min-show eviction,
        FLAGS_table_*) → SaveBase → donefile publish. Shared between
        ``train_day`` and the streaming runner's day rollover
        (stream/runner.py) — both close a day the exact same way.
        Returns rows evicted by the shrink."""
        store = self.trainer.engine.store
        if self.is_rank0:
            with self.timers.scope("day_end"), \
                    trace.span("day/day_end", day=day):
                evicted = store.shrink(min_show=self.min_show_shrink)
                faults.faultpoint("day_runner/day_end_save")
                bdir = self.ckpt.model_dir(day, pass_id=-1)
                store.save_base(bdir)
                self._save_dense(bdir)
                faults.faultpoint("day_runner/publish")
                self.ckpt.publish(day, pass_id=-1)
        elif getattr(store, "shared", False):
            # Shared backing tier (e.g. PSBackedStore): rank 0 already
            # shrank the one store — running it again would apply
            # show/click decay and eviction world_size times per day
            # (the reference's day-end ShrinkTable runs once).
            evicted = 0
        else:
            evicted = store.shrink(min_show=self.min_show_shrink)
        monitor.add("day_runner/days", 1)
        monitor.add("day_runner/evicted_keys", int(evicted))
        # The per-day key window slides at the boundary by design —
        # the NEXT pass's churn alarm is suppressed, not a drift.
        quality.GLOBAL.note_day_rollover()
        return evicted

    def run_days(self, days: Sequence[str],
                 resume: bool = True) -> Dict[str, List[Dict[str, float]]]:
        """Multi-day loop with recovery. The resume point covers both the
        base day AND any trailing deltas already loaded into the store —
        the delta day's completed passes are skipped via ``start_pass``
        (re-training them would double-apply their updates)."""
        point = self.recover() if resume else None
        out = {}
        for day in days:
            day = str(day)
            if point is not None:
                if day < point["day"] or (day == point["day"]
                                          and point["pass_id"] == 0):
                    log.vlog(0, "day %s already covered by recovery: skip",
                             day)
                    continue
                if day == point["day"]:
                    # resume mid-day after the last published delta pass
                    out[day] = self.train_day(
                        day, start_pass=point["pass_id"] + 1)
                    continue
            out[day] = self.train_day(day)
        return out
