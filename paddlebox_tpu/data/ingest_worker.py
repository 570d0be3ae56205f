"""Ingest worker process: parse file blocks → ColumnarChunk → shm frames.

The child half of the multi-process columnar ingest
(``FLAGS_ingest_workers``; role of the reference's reader thread pool,
``data_set.cc:2283``, moved across a process boundary so the parse runs
on real cores instead of GIL turns). Each worker pulls whole files from
a shared task queue, parses them block-by-block with the SAME
``_parse_block`` the thread path uses (native C++ → vectorized numpy →
exact per-line fallback), writes each chunk into a shared-memory frame
(``data/shm_channel.py``) and reports frames/progress over the message
queue. The parent commits a file's frames only after ``file_done`` — a
worker dying mid-file leaves no partial rows behind.

Message protocol (every tuple starts with the kind and worker id)::

    ("file_start", wid, path)
    ("chunk",      wid, path, seg_name, num_rows, nbytes)
    ("file_done",  wid, path, num_rows)
    ("file_error", wid, path, exc_type_name, exc_msg)
    ("exit",       wid)

Errors mirror the thread path: one failing file ends the worker (its
remaining queue files are drained by siblings), and the error surfaces
through ``Dataset._reader_errors``.
"""

from __future__ import annotations

import os
import queue
import sys

from paddlebox_tpu.data import shm_channel
from paddlebox_tpu.data.slots import DataFeedConfig


def _pin_cpu() -> None:
    """One process per chip: the parent may hold the accelerator, and a
    second process that initialises it fails or hangs. Nothing on the
    parse path imports jax (tests/test_ingest.py pins that); this covers
    a parent main module, re-imported here by spawn, that does."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def worker_main(worker_id: int, parent_pid: int, load_id: int, task_q,
                msg_q, config: DataFeedConfig) -> None:
    """Process entry point (spawn-safe: module-level, picklable args)."""
    _pin_cpu()
    # Imported here, not at module top: the spawn child pays the package
    # import either way, but keeping the entry's import surface explicit
    # documents what the worker actually needs.
    from paddlebox_tpu.data.dataset import _parse_block, _read_blocks
    serial = 0
    try:
        while True:
            try:
                path = task_q.get_nowait()
            except queue.Empty:
                return
            msg_q.put(("file_start", worker_id, path))
            n_rows = 0
            try:
                for block in _read_blocks(path, config.pipe_command):
                    chunk = _parse_block(block, config, None)
                    name = shm_channel.seg_name(parent_pid, load_id,
                                                worker_id, serial)
                    serial += 1
                    nbytes = shm_channel.write_chunk(chunk, name)
                    msg_q.put(("chunk", worker_id, path, name,
                               chunk.num_rows, nbytes))
                    n_rows += chunk.num_rows
            except BaseException as e:
                # Send (type name, message); the parent rebuilds the
                # closest builtin exception — pickling arbitrary
                # exception objects across the queue is not reliable.
                msg_q.put(("file_error", worker_id, path,
                           type(e).__name__, str(e)))
                return
            msg_q.put(("file_done", worker_id, path, n_rows))
    finally:
        try:
            msg_q.put(("exit", worker_id))
            msg_q.close()
            msg_q.join_thread()  # flush the feeder before the process dies
        except Exception:
            pass
