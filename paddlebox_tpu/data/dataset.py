"""Dataset: file list → threaded load → shuffle → static-shape batches.

Role of the reference's dataset hierarchy (``data_set.{h,cc}``, SURVEY.md
§2.4): ``PadBoxSlotDataset::LoadIntoMemory`` (reader thread pool feeding a
channel + pass-key merge, ``data_set.cc:2283-2289``), preload/wait
(``box_wrapper.h:1140,1161``), local & cross-node shuffle
(``ShuffleData``/``ReceiveSuffleData``, ``data_set.cc:2436,2544``), and the
python ``BoxPSDataset`` API (``python/paddle/fluid/dataset.py:1225``).

TPU-first shape: records live as columnar CSR chunks
(:class:`ColumnarChunk`) parsed by the native C++ parser when available
(``native/parser.cc``) — every downstream operation (shuffle, partition,
batch pack) is a vectorized numpy gather, no per-record python objects.
Batches are packed host-side to STATIC shapes (:class:`SlotBatch`) so the
jitted train step never recompiles; per-pass unique keys are collected
during load (role of ``MergeInsKeys`` → ``PSAgent::AddKey``).
"""

from __future__ import annotations

import builtins
import os
import queue
import subprocess
import threading
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from paddlebox_tpu.core import faults, flags, log, monitor, trace
from paddlebox_tpu.data.channel import Channel, ClosedChannelError
from paddlebox_tpu.data.columnar import ColumnarChunk, instances_to_chunk
from paddlebox_tpu.data.parser import parse_lines
from paddlebox_tpu.data.slots import DataFeedConfig, SlotBatch

_READ_BLOCK = 4 << 20  # bytes per parse chunk

# File-list entries may name a BYTE RANGE of a file still being appended
# (the streaming tier's tail-consume cursor, stream/source.py):
# "<path>@@<start>-<end>" reads [start, end) — always cut at a newline
# boundary by the producer, so the slice parses like a whole file.
BYTE_RANGE_SEP = "@@"


def split_byte_range(spec: str):
    """``'p@@100-200'`` -> ``('p', 100, 200)``; plain path ->
    ``(path, None, None)``. A malformed suffix is treated as a literal
    path (``@@`` is no legal byte in this repo's day layouts)."""
    if BYTE_RANGE_SEP not in spec:
        return spec, None, None
    path, _, rng = spec.rpartition(BYTE_RANGE_SEP)
    a, dash, b = rng.partition("-")
    try:
        start, end = int(a), int(b)
    except ValueError:
        return spec, None, None
    if not dash or start < 0 or end < start:
        return spec, None, None
    return path, start, end


class _ByteSlice:
    """Read-only [start, end) window of an open binary file."""

    def __init__(self, f, start: int, end: int):
        f.seek(start)
        self._f = f
        self._left = end - start

    def read(self, n: int = -1) -> bytes:
        if self._left <= 0:
            return b""
        n = self._left if n is None or n < 0 else min(n, self._left)
        b = self._f.read(n)
        self._left -= len(b)
        return b

    def close(self) -> None:
        self._f.close()


def _open_stream(path: str, pipe_command: str):
    """Open a byte stream, optionally through a shell filter (role of
    pipe_command in data_feed.proto:47 / shell_popen io/fs.cc:69).
    Byte-range specs open the base file windowed to [start, end)."""
    base, start, end = split_byte_range(path)
    if start is not None:
        if pipe_command:
            # A shell filter consumes the raw stream start-to-finish —
            # a mid-file window through it would re-decompress the
            # whole prefix per range (and gzip members don't align to
            # carve cuts). Loud, not silent-wrong.
            raise ValueError(
                f"byte-range spec {path!r} cannot combine with "
                f"pipe_command {pipe_command!r} — tail-consume plain "
                "text logs only (ONLINE.md)")
        return None, _ByteSlice(open(base, "rb"), start, end)
    if pipe_command:
        f = open(path, "rb")
        proc = subprocess.Popen(pipe_command, shell=True, stdin=f,
                                stdout=subprocess.PIPE, bufsize=1 << 20)
        return proc, proc.stdout
    return None, open(path, "rb")


def _read_blocks(path: str, pipe_command: str) -> Iterator[bytes]:
    """Yield newline-aligned byte blocks of ~_READ_BLOCK size."""
    proc, stream = _open_stream(path, pipe_command)
    try:
        carry = b""
        while True:
            block = stream.read(_READ_BLOCK)
            if not block:
                break
            block = carry + block
            cut = block.rfind(b"\n")
            if cut < 0:
                carry = block
                continue
            carry = block[cut + 1:]
            yield block[:cut + 1]
        if carry:
            yield carry
    finally:
        stream.close()
        if proc is not None:
            ret = proc.wait()
            if ret != 0:
                # A failing filter (typo'd decompressor, truncated file)
                # must not silently produce an empty pass.
                raise RuntimeError(
                    f"pipe_command {pipe_command!r} exited {ret} on {path}")


def _parse_block(block: bytes, config: DataFeedConfig,
                 parser_fn=None) -> ColumnarChunk:
    """Native C++ parse when available, python fallback otherwise.
    ``parser_fn`` overrides the registry lookup (instance-scoped custom
    parser — the DLManager plugin role without global registration)."""
    if parser_fn is None and config.parser == "svm":
        from paddlebox_tpu.native.parser_py import parse_chunk_native
        chunk = parse_chunk_native(block, config)
        if chunk is not None:
            return chunk
        # No native library: the vectorized numpy bulk parse (C-level
        # S→numeric casts over the whole block) before the per-line
        # loop; it returns None on any input it cannot prove it handles
        # bit-identically, so semantics never change.
        from paddlebox_tpu.data.parser import parse_block_numpy
        chunk = parse_block_numpy(block, config)
        if chunk is not None:
            return chunk
    # Split on '\n' only — matching the block framing and the native
    # parser; str.splitlines would also break on NEL/FF/LS etc. and make
    # the two parser paths disagree on exotic bytes.
    lines = block.decode("utf-8", "replace").split("\n")
    instances = (parser_fn(lines, config) if parser_fn is not None
                 else parse_lines(lines, config))
    return instances_to_chunk(instances, config)


class Dataset:
    """In-memory columnar slot dataset with pass lifecycle.

    Typical CTR pass loop (mirrors BoxPSDataset usage, dataset.py:1225):

        ds = Dataset(config, num_reader_threads=8)
        ds.set_filelist(shards)
        ds.load_into_memory()          # or preload_into_memory + wait
        ds.local_shuffle(seed)
        for batch in ds.batches_sharded(ndev):
            ...
        ds.clear()
    """

    def __init__(self, config: DataFeedConfig, *, num_reader_threads: int = 4,
                 channel_capacity: int = 64, parser_fn=None):
        self.config = config
        # Instance-scoped parser override (DLManager custom-parser role
        # without mutating the global registry): fn(lines, config) ->
        # List[Instance].
        self.parser_fn = parser_fn
        self.num_reader_threads = max(1, num_reader_threads)
        self._channel_capacity = channel_capacity
        self._filelist: List[str] = []
        self._chunks: List[ColumnarChunk] = []
        self._merged: Optional[ColumnarChunk] = None
        self._preload_threads: List[threading.Thread] = []
        self._reader_errors: List[BaseException] = []
        self._lock = threading.Lock()
        # Sorted-run pass-key collection: each loaded chunk's slots are
        # deduped into sorted runs and united into their slot's merger
        # WHILE the load runs (a helper thread of _drain), so when the
        # last chunk is in, every slot holds its key set and pass_keys()
        # only unites the slots asked for. Valid only while every loaded
        # chunk passed through _drain and no key-set-changing op ran.
        self._key_mergers: Dict[str, "SortedRunMerger"] = {}
        self._key_zero: Dict[str, bool] = {}
        self._key_runs_valid = True
        # [runs handed to the mergers, runs folded into another by the
        # time their load returned]: the merger's engagement (monitor
        # ingest/key_runs, ingest/key_runs_merged_in_load).
        self._key_run_counts = [0, 0]
        # Live ingest worker processes (multi-process path) — exposed so
        # tests/drills can kill one mid-load.
        self._ingest_procs: List = []
        # Hook invoked with each loaded chunk's keys at load time — wired
        # to the embedding engine's pass-key collector (role of
        # PSAgent::AddKey threading in MergeInsKeys, data_set.cc:2289).
        self.key_sink: Optional[Callable[[np.ndarray], None]] = None
        # Per-load data-health collector (FLAGS_quality_collect, core/
        # quality.py): fed each chunk in _drain; the trainer reads the
        # finalized per-slot health at pass time (quality_health()).
        self._quality = None

    # -- file list ---------------------------------------------------------

    def set_filelist(self, files: Sequence[str]) -> None:
        missing = [f for f in files
                   if not os.path.exists(split_byte_range(f)[0])]
        if missing:
            raise FileNotFoundError(f"dataset files missing: {missing[:3]}")
        # The pipelined day loop calls this from its preload thread while
        # the training thread may inspect filelist — swap under the lock.
        with self._lock:
            self._filelist = list(files)

    @property
    def filelist(self) -> List[str]:
        with self._lock:
            return list(self._filelist)

    # -- load --------------------------------------------------------------

    def _reader_worker(self, file_q: "queue.Queue[str]", out: Channel) -> None:
        try:
            while True:
                try:
                    path = file_q.get_nowait()
                except queue.Empty:
                    return
                n = 0
                for block in _read_blocks(path, self.config.pipe_command):
                    chunk = _parse_block(block, self.config, self.parser_fn)
                    n += chunk.num_rows
                    out.put(chunk)
                monitor.add("dataset/ins_loaded", n)
                log.vlog(1, "loaded %d instances from %s", n, path)
        except BaseException as e:  # surfaced by load_into_memory/wait
            with self._lock:
                self._reader_errors.append(e)

    def _start_load(self) -> Channel:
        if int(flags.flag("ingest_workers")) > 0 and self.parser_fn is None:
            return self._start_load_mp(int(flags.flag("ingest_workers")))
        file_q: "queue.Queue[str]" = queue.Queue()
        for f in self._filelist:
            file_q.put(f)
        out: Channel = Channel(self._channel_capacity)
        threads = []
        nthreads = min(self.num_reader_threads, max(1, len(self._filelist)))
        for _ in range(nthreads):
            t = threading.Thread(target=self._reader_worker,
                                 args=(file_q, out), daemon=True)
            t.start()
            threads.append(t)

        def closer():
            for t in threads:
                t.join()
            out.close()

        threading.Thread(target=closer, daemon=True).start()
        return out

    def _start_load_mp(self, num_workers: int) -> Channel:
        """Multi-process columnar ingest (FLAGS_ingest_workers; role of
        the reference's multithreaded LoadIntoMemory, data_set.cc:2283,
        which parallelizes for real because it is C++ — here the python
        parse escapes the GIL by running in worker PROCESSES that hand
        chunks back through zero-copy shared-memory frames).

        Same Channel contract as the thread path, so load/preload/dump
        and ``_drain`` (key_sink included) are unchanged. A worker death
        mid-file is detected by the pump, its staged frames are
        discarded (commit happens only on ``file_done``, so no partial
        rows), the file is requeued up to ``FLAGS_ingest_file_retries``
        times on a fresh worker, and an exhausted retry budget surfaces
        through ``_reader_errors`` like any reader failure."""
        import multiprocessing as mp

        from paddlebox_tpu.data import shm_channel
        from paddlebox_tpu.data.ingest_worker import worker_main

        # spawn, not fork: the parent holds jax state and live threads
        # (preload/trainer); forking either is undefined behavior.
        ctx = mp.get_context("spawn")
        with self._lock:
            files = list(self._filelist)
        out: Channel = Channel(self._channel_capacity)
        parent_pid = os.getpid()
        load_id = shm_channel.next_load_id()
        task_q = ctx.Queue()
        for f in files:
            task_q.put(f)
        msg_q = ctx.Queue()
        n_workers = min(num_workers, max(1, len(files)))
        max_file_retries = int(flags.flag("ingest_file_retries"))
        # Runaway-respawn backstop (a replacement that itself keeps
        # dying must converge to an error, not a spawn loop).
        respawn_budget = [n_workers + len(files) * max(1, max_file_retries)]

        def pump():
            procs: Dict[int, object] = {}
            current: Dict[int, Optional[str]] = {}
            staged: Dict[int, list] = {}
            committed: Dict[int, set] = {}
            finished: set = set()
            settled: set = set()   # paths that reached done/error
            file_retries: Dict[str, int] = {}
            next_wid = [0]

            def new_worker():
                faults.faultpoint("ingest/worker_spawn")
                if respawn_budget[0] <= 0:
                    raise RuntimeError(
                        "ingest worker respawn budget exhausted")
                respawn_budget[0] -= 1
                wid = next_wid[0]
                next_wid[0] += 1
                p = ctx.Process(target=worker_main,
                                args=(wid, parent_pid, load_id, task_q,
                                      msg_q, self.config),
                                daemon=True)
                p.start()
                procs[wid] = p
                current[wid] = None
                staged[wid] = []
                committed[wid] = set()
                self._ingest_procs.append(p)
                monitor.add("ingest/workers_spawned", 1)

            def discard_staged(wid):
                for _name, _chunk, release in staged[wid]:
                    release()
                staged[wid] = []

            def record_error(exc: BaseException):
                with self._lock:
                    self._reader_errors.append(exc)

            def handle(msg):
                kind, wid = msg[0], msg[1]
                if kind == "file_start":
                    current[wid] = msg[2]
                elif kind in ("file_done", "file_error"):
                    settled.add(msg[2])
                if kind == "file_done":
                    current[wid] = None
                    frames, staged[wid] = staged[wid], []
                    n = 0
                    for name, chunk, _release in frames:
                        committed[wid].add(name)
                        n += chunk.num_rows
                        out.put(chunk)
                    monitor.add("dataset/ins_loaded", n)
                    monitor.add("ingest/chunks", len(frames))
                    monitor.add("ingest/rows", n)
                    log.vlog(1, "ingest: loaded %d instances from %s",
                             n, msg[2])
                elif kind == "chunk":
                    _k, _w, _path, name, _n, _nb = msg
                    faults.faultpoint("ingest/shm_attach")
                    chunk, release = shm_channel.read_chunk(name)
                    staged[wid].append((name, chunk, release))
                elif kind == "file_error":
                    _k, _w, path, ename, emsg = msg
                    current[wid] = None
                    discard_staged(wid)
                    t = getattr(builtins, ename, None)
                    if isinstance(t, type) and issubclass(t, BaseException):
                        record_error(t(emsg))
                    else:
                        record_error(RuntimeError(f"{ename}: {emsg}"))
                elif kind == "exit":
                    finished.add(wid)

            def check_dead():
                dead = [wid for wid, p in procs.items()
                        if wid not in finished and not p.is_alive()]
                if not dead:
                    return
                # Final drain first: messages the worker flushed before
                # dying (possibly its file_done/exit) must win over the
                # death verdict, or a COMPLETED file would be requeued
                # and its rows duplicated.
                while True:
                    try:
                        handle(msg_q.get_nowait())
                    except queue.Empty:
                        break
                for wid in dead:
                    if wid in finished:
                        continue  # the drain found its exit after all
                    faults.faultpoint("ingest/worker_exit")
                    p = procs[wid]
                    finished.add(wid)
                    discard_staged(wid)
                    shm_channel.sweep_orphans(parent_pid, load_id,
                                              worker_id=wid,
                                              exclude=committed[wid])
                    path = current.get(wid)
                    current[wid] = None
                    monitor.add("ingest/worker_deaths", 1)
                    if path is not None:
                        n = file_retries.get(path, 0)
                        if n < max_file_retries:
                            file_retries[path] = n + 1
                            monitor.add("ingest/worker_restarts", 1)
                            log.warning(
                                "ingest worker %d died (exitcode %s) "
                                "parsing %s — retry %d/%d on a fresh "
                                "worker", wid, p.exitcode, path, n + 1,
                                max_file_retries)
                            task_q.put(path)
                            new_worker()
                        else:
                            settled.add(path)
                            record_error(RuntimeError(
                                f"ingest worker died (exitcode "
                                f"{p.exitcode}) parsing {path!r}; "
                                f"{max_file_retries} retries exhausted"))
                    elif (not any(procs[w].is_alive() for w in procs)
                            and not task_q.empty()):
                        # Died idle with files still queued and no
                        # sibling left to drain them.
                        new_worker()

            try:
                for _ in range(n_workers):
                    new_worker()
                while len(finished) < len(procs):
                    try:
                        msg = msg_q.get(timeout=0.25)
                    except queue.Empty:
                        check_dead()
                        continue
                    handle(msg)
                missing = [f for f in files if f not in settled]
                with self._lock:
                    have_errors = bool(self._reader_errors)
                if missing and not have_errors:
                    # Closes the kill window between a worker's task_q
                    # pop and its file_start announcement: a file that
                    # never settled must fail the load, not silently
                    # shrink the pass.
                    record_error(RuntimeError(
                        f"ingest ended with {len(missing)} unparsed "
                        f"file(s): {missing[:3]}"))
            except ClosedChannelError:
                pass  # consumer bailed early (dump error path)
            except BaseException as e:
                record_error(e)
            finally:
                # SIGKILL, not SIGTERM: workers are stateless daemons
                # (any staged shm is discarded below) and a teardown
                # must never wait on a wedged parse.
                for p in procs.values():
                    if p.is_alive():
                        p.kill()
                for wid, p in procs.items():
                    p.join(timeout=5)
                    discard_staged(wid)
                    shm_channel.sweep_orphans(parent_pid, load_id,
                                              worker_id=wid,
                                              exclude=committed[wid])
                with self._lock:
                    self._ingest_procs = [
                        p for p in self._ingest_procs if p.is_alive()]
                out.close()

        threading.Thread(target=pump, daemon=True,
                         name="pbx-ingest-pump").start()
        return out

    def _raise_reader_errors(self) -> None:
        with self._lock:
            errs, self._reader_errors = self._reader_errors, []
        if errs:
            raise errs[0]

    def load_into_memory(self) -> None:
        """Blocking load of the whole filelist (role of LoadIntoMemory)."""
        ch = self._start_load()
        self._drain(ch)
        self._raise_reader_errors()

    def preload_into_memory(self) -> None:
        """Start background load (role of PreLoadIntoMemory — overlaps the
        previous pass's training with the next pass's read)."""
        ch = self._start_load()
        t = threading.Thread(target=self._drain, args=(ch,), daemon=True)
        t.start()
        self._preload_threads = [t]

    def wait_preload_done(self) -> None:
        """Role of WaitPreLoadDone/WaitFeedPassDone."""
        for t in self._preload_threads:
            t.join()
        self._preload_threads = []
        self._raise_reader_errors()

    def _collect_key_runs(self, chunk: ColumnarChunk) -> int:
        """Dedup the chunk's per-slot keys into sorted runs and unite
        each into its slot's merger, so that pass_keys() has no merge
        over every loaded id left to do. Returns the runs handed in.
        Bit-parity: merge(runs) == np.unique(concat) — dedup_keys drops
        the 0 sentinel, so a seen-zero flag restores it for the slots
        where the exact path would have reported it."""
        from paddlebox_tpu.native.keymap_py import dedup_keys
        from paddlebox_tpu.native.store_py import SortedRunMerger
        added = 0
        for s, ids in chunk.sparse_ids.items():
            if not ids.size:
                continue
            run = dedup_keys(ids)
            zero = run.size < ids.size and bool((ids == 0).any())
            with self._lock:
                if not self._key_runs_valid:
                    return added
                merger = self._key_mergers.get(s)
                if merger is None:
                    merger = self._key_mergers[s] = SortedRunMerger()
                if zero:
                    self._key_zero[s] = True
            # Outside the lock: one load's helper is the mergers' only
            # writer, and pass_keys() reads them after the load.
            merger.add_run(run)
            added += bool(run.size)
        return added

    def _merge_key_runs(self, chunks: "queue.SimpleQueue") -> None:
        """The load's key helper (a thread of _drain's, gone when _drain
        returns): takes each chunk as it is drained, folds its runs in,
        and when the channel has closed unites what each slot still
        holds. The dedup and the merges are native calls that release
        the GIL, so they run beside the parse and the drain."""
        added = 0
        try:
            while True:
                chunk = chunks.get()
                if chunk is None:       # _drain's last word
                    break
                with trace.span("ingest/key_merge", rows=chunk.num_rows):
                    added += self._collect_key_runs(chunk)
            with self._lock:
                mergers = list(self._key_mergers.values())
            with trace.span("ingest/key_merge", slots=len(mergers)):
                for m in mergers:
                    m.merge()
            merged = max(0, added - sum(m.num_runs for m in mergers))
            with self._lock:
                self._key_run_counts[0] += added
                self._key_run_counts[1] += merged
            monitor.add("ingest/key_runs", added)
            monitor.add("ingest/key_runs_merged_in_load", merged)
        except BaseException as e:  # surfaced by load_into_memory/wait
            self._invalidate_key_runs()
            with self._lock:
                self._reader_errors.append(e)

    def _invalidate_key_runs(self) -> None:
        with self._lock:
            self._key_runs_valid = False
            self._key_mergers = {}
            self._key_zero = {}

    def key_run_counts(self) -> Tuple[int, int]:
        """(key runs the loads handed to the mergers, runs already folded
        into another when their load returned) since the last clear()."""
        with self._lock:
            return tuple(self._key_run_counts)

    def _drain(self, ch: Channel) -> None:
        sink = self.key_sink
        collect = bool(flags.flag("ingest_key_runs"))
        qc = None
        if flags.flag("quality_collect"):
            from paddlebox_tpu.core import quality
            with self._lock:
                if self._quality is None:
                    self._quality = quality.SlotHealthCollector()
                qc = self._quality
        local: List[ColumnarChunk] = []
        helper = None
        if collect:
            keyq: "queue.SimpleQueue" = queue.SimpleQueue()
            helper = threading.Thread(target=self._merge_key_runs,
                                      args=(keyq,), daemon=True,
                                      name="pbx-ingest-keys")
            helper.start()
        try:
            while True:
                chunk = ch.get()
                local.append(chunk)
                if collect:
                    keyq.put(chunk)
                if qc is not None:
                    qc.observe_chunk(chunk)
                if sink is not None:
                    keys = chunk.all_keys()
                    if keys.size:
                        sink(keys)
        except ClosedChannelError:
            pass
        finally:
            if helper is not None:
                keyq.put(None)
                helper.join()
        with self._lock:
            self._chunks.extend(local)
            self._merged = None
        if local and not collect:
            # Runs no longer cover every loaded chunk — pass_keys falls
            # back to the exact merged-sort path.
            self._invalidate_key_runs()

    def _merge(self) -> ColumnarChunk:
        with self._lock:
            if self._merged is None:
                chunks = self._chunks or [ColumnarChunk.empty(self.config)]
                self._merged = ColumnarChunk.concat(chunks)
                self._chunks = [self._merged]
            return self._merged

    # -- shuffle -----------------------------------------------------------

    def _check_no_preload(self, op: str) -> None:
        # Shuffles snapshot-then-replace the chunk list; a concurrent
        # preload _drain appending chunks would be silently discarded.
        if any(t.is_alive() for t in self._preload_threads):
            raise RuntimeError(
                f"{op} while preload_into_memory is running — call "
                f"wait_preload_done() first")

    def local_shuffle(self, seed: Optional[int] = None) -> None:
        self._check_no_preload("local_shuffle")
        merged = self._merge()
        rng = np.random.default_rng(seed)
        perm = rng.permutation(merged.num_rows)
        shuffled = merged.take(perm)
        with self._lock:
            self._chunks = [shuffled]
            self._merged = shuffled

    def global_shuffle(self, *, num_ranks: int = 1, rank: int = 0,
                       exchange: Optional[Callable[[List[ColumnarChunk]],
                                                   ColumnarChunk]] = None,
                       seed: Optional[int] = None,
                       allow_partition: bool = False) -> None:
        """Cross-node record shuffle (role of PadBoxSlotDataset::ShuffleData
        → boxps::PaddleShuffler → ReceiveSuffleData, data_set.cc:2436,2544).

        Records are hashed into ``num_ranks`` bucket chunks; ``exchange``
        ships them to their owner ranks and returns the chunk this rank
        receives. With ``num_ranks > 1`` a transport is REQUIRED unless
        ``allow_partition=True`` explicitly opts into keeping only this
        rank's bucket (simulating one rank — other buckets are dropped).
        """
        if num_ranks > 1 and exchange is None and not allow_partition:
            raise ValueError(
                "global_shuffle with num_ranks>1 needs an exchange transport "
                "(or allow_partition=True to keep only this rank's bucket, "
                "dropping the rest)")
        self._check_no_preload("global_shuffle")
        merged = self._merge()
        rng = np.random.default_rng(seed)
        assign = rng.integers(num_ranks, size=merged.num_rows)
        buckets = [merged.take(np.flatnonzero(assign == r))
                   for r in range(num_ranks)]
        if exchange is None:
            received = buckets[rank]
            dropped = merged.num_rows - received.num_rows
            if dropped:
                monitor.add("dataset/shuffle_partition_dropped", dropped)
        else:
            received = exchange(buckets)
        # The key SET changed (rows left/arrived) — ingest-time runs no
        # longer describe what is loaded.
        self._invalidate_key_runs()
        with self._lock:
            self._chunks = [received]
            self._merged = received
        self.local_shuffle(seed)

    # -- access ------------------------------------------------------------

    @property
    def num_instances(self) -> int:
        with self._lock:
            return sum(c.num_rows for c in self._chunks)

    def batches(self, *, drop_last: bool = False,
                batch_size: Optional[int] = None) -> Iterator[SlotBatch]:
        """Yield static-shape SlotBatches; the short final batch is padded
        with invalid rows unless drop_last."""
        bs = batch_size or self.config.batch_size
        merged = self._merge()
        n = merged.num_rows
        for lo in range(0, n, bs):
            hi = min(lo + bs, n)
            if hi - lo < bs and drop_last:
                return
            yield merged.pack_batch(lo, hi, self.config, bs)

    def batches_sharded(self, num_shards: int, *,
                        batch_size: Optional[int] = None
                        ) -> Iterator[SlotBatch]:
        """Yield batches in the per-device sharded layout (see
        SlotBatch.pack_sharded) — what a dp-sharded train step consumes."""
        bs = batch_size or self.config.batch_size
        merged = self._merge()
        n = merged.num_rows
        for lo in range(0, n, bs):
            hi = min(lo + bs, n)
            yield merged.pack_batch_sharded(lo, hi, self.config, num_shards,
                                            bs)

    def slots_shuffle(self, slots: Sequence[str],
                      seed: Optional[int] = None) -> None:
        """AUC-runner eval mode: decorrelate the given slots from labels by
        shuffling their values across records (role of
        BoxPSDataset.slots_shuffle, dataset.py:1288)."""
        self._check_no_preload("slots_shuffle")
        merged = self._merge()
        rng = np.random.default_rng(seed)
        for s in slots:
            merged = merged.shuffle_slot(s, rng)
        with self._lock:
            self._chunks = [merged]
            self._merged = merged

    def snapshot_chunks(self):
        """Cheap state snapshot (chunk refs; chunks are treated as
        immutable — transforms like shuffle_slot return new ones) so
        AUC-runner eval can shuffle a slot and restore afterwards."""
        with self._lock:
            return (list(self._chunks), self._merged)

    def restore_chunks(self, snap) -> None:
        chunks, merged = snap
        self._invalidate_key_runs()  # snapshot may predate current load
        with self._lock:
            self._chunks = list(chunks)
            self._merged = merged

    # -- disk spill (role of PreLoadIntoDisk/DumpIntoDisk + LoadDiskData,
    # data_set.cc:2088,2167) ----------------------------------------------

    def dump_into_disk(self, spill_dir: str) -> int:
        """Stream-parse the filelist straight to disk chunk archives
        without holding records in RAM (role of PreLoadIntoDisk: datasets
        larger than host memory spill between load and train). Returns
        the number of chunks written."""
        os.makedirs(spill_dir, exist_ok=True)
        # A re-dump producing fewer chunks must not leave stale chunks
        # from a previous run to be silently mixed in at load time.
        for old in self._disk_chunk_files(spill_dir):
            os.unlink(old)
        ch = self._start_load()
        n = 0
        try:
            while True:
                chunk = ch.get()
                chunk.save(os.path.join(spill_dir, f"chunk-{n:06d}.npz"))
                if self.key_sink is not None:
                    keys = chunk.all_keys()
                    if keys.size:
                        self.key_sink(keys)
                n += 1
        except ClosedChannelError:
            pass
        except BaseException:
            # e.g. disk-full in save(): readers are blocked on the bounded
            # channel — close it so their put() raises and threads exit
            # instead of leaking.
            ch.close()
            raise
        self._raise_reader_errors()
        log.vlog(0, "dump_into_disk: %d chunks -> %s", n, spill_dir)
        return n

    @staticmethod
    def _disk_chunk_files(spill_dir: str) -> List[str]:
        import glob
        return sorted(glob.glob(os.path.join(spill_dir, "chunk-*.npz")))

    def load_from_disk(self, spill_dir: str) -> None:
        """Load previously spilled chunks back into memory."""
        files = self._disk_chunk_files(spill_dir)
        if not files:
            # Same convention as set_filelist's missing-file error: a
            # misconfigured path must not silently yield an empty pass.
            raise FileNotFoundError(f"no chunk-*.npz under {spill_dir!r}")
        chunks = [ColumnarChunk.load(p) for p in files]
        self._invalidate_key_runs()  # spilled chunks carry no runs
        with self._lock:
            self._chunks = chunks
            self._merged = None

    def batches_from_disk(self, spill_dir: str, *,
                          batch_size: Optional[int] = None,
                          drop_last: bool = False) -> Iterator[SlotBatch]:
        """Stream batches chunk-by-chunk from a spill dir, holding at most
        one chunk (+remainder rows) in RAM — training directly from the
        disk tier."""
        bs = batch_size or self.config.batch_size
        rest: Optional[ColumnarChunk] = None
        for path in self._disk_chunk_files(spill_dir):
            cur = ColumnarChunk.load(path)
            if rest is not None and rest.num_rows:
                cur = ColumnarChunk.concat([rest, cur])
            n = cur.num_rows
            lo = 0
            while lo + bs <= n:
                yield cur.pack_batch(lo, lo + bs, self.config, bs)
                lo += bs
            rest = cur.take(np.arange(lo, n)) if lo < n else None
        if rest is not None and rest.num_rows and not drop_last:
            yield rest.pack_batch(0, rest.num_rows, self.config, bs)

    # -- pv/ins grouped batching (role of PaddleBoxDataFeed pv mode,
    # data_feed.h:1701: group instances by search id; a batch holds whole
    # pvs) ------------------------------------------------------------------

    def batches_grouped(self, group_slot: str, *,
                        batch_size: Optional[int] = None,
                        ) -> Iterator[Tuple[SlotBatch, np.ndarray]]:
        """Yield (SlotBatch, group_ids[bs]) where rows of the same group
        (e.g. search id / pv) are contiguous and never split across
        batches; group_ids carries the per-row group key (0 on padding
        rows). Groups larger than batch_size are truncated with a monitor
        tick (the reference drops such pvs)."""
        bs = batch_size or self.config.batch_size
        merged = self._merge()
        keys, has = merged.group_keys(group_slot)
        n = merged.num_rows
        if n == 0:
            return
        # Group rank = first-occurrence order (NOT sorted key order: that
        # would make every epoch's batch composition identical and nullify
        # local_shuffle between pvs). Keyless rows are singleton groups in
        # encounter order.
        gid = np.empty((n,), np.int64)
        num_keyed = 0
        if has.any():
            uniq, inv = np.unique(keys[has], return_inverse=True)
            num_keyed = uniq.size
            gid[has] = inv
        gid[~has] = num_keyed + np.arange(int((~has).sum()))
        first_seen = np.full(num_keyed + int((~has).sum()), n, np.int64)
        np.minimum.at(first_seen, gid, np.arange(n))
        rank_of_gid = np.argsort(np.argsort(first_seen))
        order = np.argsort(rank_of_gid[gid], kind="stable")
        merged = merged.take(order)
        # Boundaries come from the reordered group ids — NOT the key array
        # with keyless rows zeroed, which would merge adjacent keyless
        # singletons (and any real group whose key happens to be 0) into
        # one pseudo-group.
        gid_ord = gid[order]
        keys = np.where(has, keys, 0)[order]
        starts = np.concatenate(
            [[0], np.flatnonzero(gid_ord[1:] != gid_ord[:-1]) + 1, [n]])
        lo = 0
        g = 0  # index into starts of the first group of this batch
        while g < starts.size - 1:
            lo = starts[g]
            # extend until next group would overflow the batch
            h = g + 1
            while h < starts.size - 1 and starts[h + 1] - lo <= bs:
                h += 1
            hi = min(starts[h], lo + bs)
            if starts[h] - lo > bs and h == g + 1:
                monitor.add("dataset/pv_truncated", int(starts[h] - lo - bs))
            batch = merged.pack_batch(lo, hi, self.config, bs)
            gids = np.zeros((bs,), np.uint64)
            gids[:hi - lo] = keys[lo:hi]
            yield batch, gids
            g = h

    def pass_keys(self, slots: Optional[Sequence[str]] = None) -> np.ndarray:
        """Unique feasigns currently loaded (role of the per-pass key set
        registered via FeedPass, box_wrapper.h:1239): sorted unique, in
        the shape feed_pass's dedup bypass recognizes.

        ``slots`` restricts to the given sparse slots — used by dim-grouped
        embedding engines that feed each width group its own key set.

        Fast path: while the runs united during the load still cover
        everything loaded, every slot holds its finished key set and
        this unites the slots asked for — log2(slots) levels over
        distinct keys, not a merge over every loaded id. Any operation
        that changed the key set (global shuffle, chunk restore, disk
        reload) falls back to the exact merged-sort path."""
        with self._lock:
            runs_ok = self._key_runs_valid
            if runs_ok:
                names = (list(self._key_mergers) if slots is None
                         else [s for s in slots if s in self._key_mergers])
                mergers = [self._key_mergers[s] for s in names]
                seen_zero = any(self._key_zero.get(s, False)
                                for s in (self._key_zero if slots is None
                                          else slots))
        if runs_ok:
            from paddlebox_tpu.native.store_py import SortedRunMerger
            union = SortedRunMerger()
            for m in mergers:
                union.add_run(m.merge())
            keys = union.merge()
            if seen_zero:
                keys = np.concatenate(
                    [np.zeros((1,), np.uint64), keys])
            monitor.add("ingest/pass_keys_from_runs", 1)
            return keys
        merged = self._merge()
        if slots is None:
            keys = merged.all_keys()
        else:
            parts = [merged.sparse_ids[s] for s in slots
                     if merged.sparse_ids.get(s) is not None
                     and merged.sparse_ids[s].size]
            keys = (np.concatenate(parts) if parts
                    else np.empty((0,), np.uint64))
        if keys.size == 0:
            return keys
        return np.unique(keys)

    def quality_health(self):
        """Finalized per-slot data-health of everything this dataset
        loaded (core/quality.py SlotHealthCollector.finalize()); None
        when FLAGS_quality_collect was off during the load. The
        trainer attaches this to the pass's quality report — load-time
        collection keeps the per-chunk work off the pass critical path
        and attributes a pipelined preload's chunks to the dataset
        (and so the pass) that actually consumes them."""
        with self._lock:
            qc = self._quality
        return qc.finalize() if qc is not None else None

    def clear(self) -> None:
        with self._lock:
            self._chunks.clear()
            self._merged = None
            self._key_mergers = {}
            self._key_zero = {}
            self._key_runs_valid = True
            self._key_run_counts = [0, 0]
            self._quality = None
        # Chunk finalizers unlink their shm segments as the refs die;
        # nothing else to do here (gc-immediate under CPython).
