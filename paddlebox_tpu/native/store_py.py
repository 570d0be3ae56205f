"""ctypes wrappers for the native store engine (native/store.cc).

Two surfaces:

- :class:`KeyIndex` — incremental key→row hash index (host half of the
  device-resident feature store, embedding/device_store.py). Rows are
  assigned in first-insertion order and never move.
- Module functions ``ss_locate`` / ``gather_rows`` / ``scatter_rows`` /
  ``merge_sorted`` / ``init_uniform`` — threaded primitives for the
  host-RAM store tier (embedding/store.py hot loops; role of the
  reference's multithreaded PreBuildTask/BuildPull walk,
  ps_gpu_wrapper.cc:114,362). Each has an exact numpy fallback when the
  native library is unavailable.
"""

from __future__ import annotations

import ctypes
from typing import Optional, Tuple

import numpy as np

from paddlebox_tpu.native.build import load_library

_u64p = ctypes.POINTER(ctypes.c_uint64)
_i64p = ctypes.POINTER(ctypes.c_int64)
_u8p = ctypes.POINTER(ctypes.c_uint8)
_f32p = ctypes.POINTER(ctypes.c_float)


def _p(a: np.ndarray, t):
    return a.ctypes.data_as(t)


def is_sorted_unique_nonzero(keys: np.ndarray) -> bool:
    """True when ``keys`` is strictly ascending with no 0 (the shape
    dedup_keys produces) — the precondition for the bulk-build bypasses.
    One vectorized O(n) pass, cheap next to any build it guards."""
    k = keys
    if k.size == 0:
        return True
    return bool(k[0] != 0) and (k.size == 1 or bool(np.all(k[1:] > k[:-1])))


def merge_unique(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two SORTED UNIQUE key arrays, O(n + m): one threaded
    two-pointer pass (native ``pbx_union_sorted``); ``np.union1d``
    without the library."""
    a = np.ascontiguousarray(a, np.uint64)
    b = np.ascontiguousarray(b, np.uint64)
    if a.size == 0:
        return b.copy() if b.base is not None else b
    if b.size == 0:
        return a
    lib = load_library()
    if lib is not None:
        out = np.empty((a.size + b.size,), np.uint64)
        n = int(lib.pbx_union_sorted(_p(a, _u64p), a.size, _p(b, _u64p),
                                     b.size, _p(out, _u64p)))
        # ``out`` is ours alone: give back the room the shared keys left
        out.resize((n,), refcheck=False)
        return out
    return np.union1d(a, b)


class SortedRunMerger:
    """Unites sorted unique key runs AS THEY ARRIVE, the way a
    log-structured tree compacts: a new run is merged into the newest
    held run while that one is at most twice its size, so the held runs
    shrink by halves down the stack (never more than log2 N of them) and
    a key is merged O(log k) times over k runs — the work of a balanced
    tree, done while the producer is still producing. ``merge()`` unites
    what is held, smallest first; the result is bit-identical to
    ``np.unique(concat(runs))``."""

    def __init__(self):
        self._runs: list = []

    def add_run(self, sorted_unique: np.ndarray) -> None:
        if not sorted_unique.size:
            return
        run = np.ascontiguousarray(sorted_unique, np.uint64)
        runs = self._runs
        while runs and runs[-1].size <= 2 * run.size:
            run = merge_unique(runs.pop(), run)
        runs.append(run)

    @property
    def num_runs(self) -> int:
        """Runs held: what ``merge()`` still has to unite."""
        return len(self._runs)

    def merge(self) -> np.ndarray:
        runs = self._runs
        if not runs:
            return np.empty((0,), np.uint64)
        run = runs.pop()
        while runs:
            run = merge_unique(runs.pop(), run)
        runs.append(run)
        return run

    def clear(self) -> None:
        self._runs = []


class KeyIndex:
    """Incremental key → row index. Not internally synchronized — callers
    serialize mutating calls (the pass lifecycle already does).

    The no-native fallback is VECTORIZED (round 13): a maintained sorted
    key view + row permutation served by threaded searchsorted
    (ss_locate), with new keys batch-appended through merge_sorted — the
    prior per-key python dict walk was ~100x off the native path and set
    BENCH_r02's 406K keys/s store-build wall on no-native hosts."""

    def __init__(self):
        self._lib = load_library()
        self._closed = False
        if self._lib is not None:
            self._h = self._lib.pbx_index_new()
        else:
            self._h = None
            # Fallback state: sorted unique keys + their rows, plus the
            # append-order key log (rows are first-appearance ranks).
            self._fb_sorted = np.empty((0,), np.uint64)
            self._fb_rows = np.empty((0,), np.int64)
            self._fb_by_row = np.empty((0,), np.uint64)
            self._fb_size = 0

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("KeyIndex used after close()")

    @property
    def size(self) -> int:
        self._check_open()
        if self._h is not None:
            return int(self._lib.pbx_index_size(self._h))
        return self._fb_size

    def reserve(self, n: int) -> None:
        """Pre-size for ~n more keys (skips incremental rehash churn; in
        the fallback, pre-grows the append log so batched upserts never
        reallocate it mid-build)."""
        if self._h is not None:
            self._lib.pbx_index_reserve(self._h, int(n))
        else:
            self._fb_grow_log(self._fb_size + int(n))

    def _fb_grow_log(self, want: int) -> None:
        if self._fb_by_row.shape[0] < want:
            grown = np.empty((max(want, 2 * self._fb_by_row.shape[0]),),
                             np.uint64)
            grown[:self._fb_size] = self._fb_by_row[:self._fb_size]
            # graftlint: allow-lock(caller-serialized by class contract)
            self._fb_by_row = grown

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """rows [n] int64; -1 for absent (and for the 0 null feasign)."""
        self._check_open()
        k = np.ascontiguousarray(keys, np.uint64)
        if self._h is not None:
            out = np.empty((k.size,), np.int64)
            if k.size:
                self._lib.pbx_index_lookup(self._h, _p(k, _u64p), k.size,
                                           _p(out, _i64p))
            return out
        out = np.full((k.size,), -1, np.int64)
        if k.size and self._fb_size:
            found, pos = ss_locate(self._fb_sorted, k)
            if found.any():
                out[found] = self._fb_rows[pos[found]]
        return out

    def upsert(self, keys: np.ndarray) -> Tuple[np.ndarray, int]:
        """(rows [n] int64, n_new). New keys get rows size.. in
        first-appearance order; key 0 maps to -1 and is never inserted."""
        self._check_open()
        k = np.ascontiguousarray(keys, np.uint64)
        out = np.empty((k.size,), np.int64)
        if self._h is not None:
            n_new = int(self._lib.pbx_index_upsert(self._h, _p(k, _u64p),
                                                   k.size, _p(out, _i64p)))
            return out, n_new
        if k.size == 0:
            return out, 0
        found, pos = ss_locate(self._fb_sorted, k)
        out[found] = self._fb_rows[pos[found]] if found.any() else 0
        zero = k == 0
        out[zero] = -1
        new_m = ~(found | zero)
        if not new_m.any():
            return out, 0
        nk = k[new_m]
        uniq, first, inv = np.unique(nk, return_index=True,
                                     return_inverse=True)
        # Rows follow FIRST-APPEARANCE order within the batch (the
        # native contract), not sorted order.
        order = np.argsort(first, kind="stable")
        rank = np.empty((order.size,), np.int64)
        rank[order] = np.arange(order.size)
        rows_of_uniq = self._fb_size + rank      # aligned to sorted uniq
        out[new_m] = rows_of_uniq[inv]
        n_old = self._fb_sorted.shape[0]
        merged, src = merge_sorted(self._fb_sorted, uniq)
        rows_merged = np.empty((merged.shape[0],), np.int64)
        is_new = src >= n_old
        rows_merged[~is_new] = self._fb_rows[src[~is_new]]
        rows_merged[is_new] = rows_of_uniq[src[is_new] - n_old]
        # graftlint: allow-lock(caller-serialized by class contract)
        self._fb_sorted, self._fb_rows = merged, rows_merged
        self._fb_grow_log(self._fb_size + order.size)
        self._fb_by_row[self._fb_size:self._fb_size + order.size] = \
            uniq[order]
        # graftlint: allow-lock(class contract: callers serialize)
        self._fb_size += int(order.size)
        return out, int(order.size)

    def bulk_build(self, sorted_keys: np.ndarray) -> np.ndarray:
        """Fresh-build bypass: populate an EMPTY index from sorted unique
        nonzero keys with rows 0..n-1 — bit-identical to ``upsert`` of
        the same array, but placement parallelizes (native: CAS-claimed
        slots across cores; fallback: the sorted view IS the input, no
        merge at all). Returns the rows (arange). Raises on a non-empty
        index or unsorted input — the caller chose the wrong API."""
        self._check_open()
        if self.size != 0:
            raise ValueError("bulk_build on a non-empty KeyIndex")
        k = np.ascontiguousarray(sorted_keys, np.uint64)
        if not is_sorted_unique_nonzero(k):
            raise ValueError(
                "bulk_build wants sorted unique nonzero keys "
                "(dedup_keys output) — use upsert for raw batches")
        if self._h is not None:
            got = int(self._lib.pbx_index_bulk_build(self._h, _p(k, _u64p),
                                                     k.size))
            if got != k.size:  # pragma: no cover - guarded above
                raise ValueError("native bulk_build rejected the input")
        else:
            n = k.shape[0]
            self._fb_sorted = k.copy()
            self._fb_rows = np.arange(n, dtype=np.int64)
            self._fb_by_row = k.copy()
            self._fb_size = n
        return np.arange(k.shape[0], dtype=np.int64)

    def keys_by_row(self) -> np.ndarray:
        """All keys, index = row (append order)."""
        self._check_open()
        n = self.size
        out = np.empty((n,), np.uint64)
        if self._h is not None:
            if n:
                self._lib.pbx_index_keys_fill(self._h, _p(out, _u64p))
            return out
        out[:] = self._fb_by_row[:n]
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._h is not None:
            self._lib.pbx_index_free(self._h)
            self._h = None
        self._fb_sorted = self._fb_rows = self._fb_by_row = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


def bench_index_build(n_keys: int, *, chunk: int = 10_000_000,
                      seed: int = 7, mode: str = "upsert") -> float:
    """ONE definition of the 'host pass-build' metric (SURVEY hard part
    #1 — PreBuildTask role, ps_gpu_wrapper.cc:114): fresh build of
    n_keys uniform-random keys into a pre-sized KeyIndex, chunked like a
    production bulk build. Returns keys/s. Shared by
    tools/bench_native_store.py and the round-13 sorted-run acceptance
    (tests/test_ingest.py) so recorded numbers can never drift in
    methodology.

    Modes (same keys in, same index out — rows differ only in the order
    contract each mode documents):

    - ``upsert``: the incremental find-or-insert walk (r02 methodology).
    - ``bulk``: the sorted-run build — per-chunk dedup_keys → sorted
      runs → k-way merge_unique → KeyIndex.bulk_build.
    - ``dict``: the pre-round-13 per-key python dict loop, kept as the
      measurable fallback baseline the 10x acceptance compares against.
    """
    import time as _time
    rng = np.random.default_rng(seed)
    keys = rng.integers(1, 1 << 62, n_keys, dtype=np.uint64)
    t0 = _time.perf_counter()
    if mode == "bulk":
        from paddlebox_tpu.native.keymap_py import dedup_keys
        merger = SortedRunMerger()
        for lo in range(0, n_keys, chunk):
            merger.add_run(dedup_keys(keys[lo:lo + chunk]))
        idx = KeyIndex()
        idx.bulk_build(merger.merge())
    elif mode == "dict":
        fb: dict = {}
        out = np.empty((min(chunk, n_keys),), np.int64)
        for lo in range(0, n_keys, chunk):
            for i, kk in enumerate(keys[lo:lo + chunk].tolist()):
                if not kk:
                    out[i] = -1
                    continue
                r = fb.get(kk)
                if r is None:
                    r = len(fb)
                    fb[kk] = r
                out[i] = r
        dt = _time.perf_counter() - t0
        return n_keys / dt
    else:
        if mode != "upsert":
            raise ValueError(f"unknown bench_index_build mode {mode!r}")
        idx = KeyIndex()
        idx.reserve(n_keys)
        for lo in range(0, n_keys, chunk):
            idx.upsert(keys[lo:lo + chunk])
    dt = _time.perf_counter() - t0
    idx.close()
    return n_keys / dt


def ss_locate(sorted_keys: np.ndarray, queries: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(found mask [m] bool, clipped positions [m] int64) of queries in the
    sorted array — threaded searchsorted (store.py _locate contract)."""
    s = np.ascontiguousarray(sorted_keys, np.uint64)
    q = np.ascontiguousarray(queries, np.uint64)
    m, n = q.size, s.size
    lib = load_library()
    if lib is None or n == 0 or m == 0:
        if n == 0:
            return np.zeros(m, bool), np.zeros(m, np.int64)
        pos = np.searchsorted(s, q)
        pos_c = np.minimum(pos, n - 1)
        return s[pos_c] == q, pos_c
    pos = np.empty((m,), np.int64)
    found = np.empty((m,), np.uint8)
    lib.pbx_ss_locate(_p(s, _u64p), n, _p(q, _u64p), m, _p(pos, _i64p),
                      _p(found, _u8p))
    return found.astype(bool), pos


def _rows2d(a: np.ndarray) -> Tuple[np.ndarray, int]:
    """View any row-shaped array as [n, width] contiguous float32."""
    v = np.ascontiguousarray(a, np.float32)
    width = int(np.prod(v.shape[1:], dtype=np.int64)) if v.ndim > 1 else 1
    return v.reshape(v.shape[0] if v.size else 0, max(width, 1)), width


def gather_rows(src: np.ndarray, idx: np.ndarray,
                mask: Optional[np.ndarray] = None,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """out[i] = src[idx[i]] (float32 rows), threaded; with ``mask`` only
    masked rows are written (others left as-is in a provided ``out``, or
    zero in a fresh one)."""
    lib = load_library()
    idx = np.ascontiguousarray(idx, np.int64)
    src2, width = _rows2d(src)
    if out is None:
        alloc = np.zeros if mask is not None else np.empty
        out = alloc((idx.size,) + src.shape[1:], np.float32)
    elif out.dtype != np.float32:
        raise ValueError("gather_rows: out must be float32")
    if lib is None or idx.size == 0:
        if idx.size:
            if mask is None:
                out[...] = src[idx]
            else:
                out[mask] = src[idx[mask]]
        return out
    out2 = out.reshape(idx.size, max(width, 1))
    if not out2.flags.c_contiguous:
        raise ValueError("gather_rows: out must be C-contiguous")
    if mask is None:
        lib.pbx_gather_rows(_p(src2, _f32p), _p(idx, _i64p), idx.size,
                            width, _p(out2, _f32p))
    else:
        mk = np.ascontiguousarray(mask, np.uint8)
        lib.pbx_gather_rows_masked(_p(src2, _f32p), _p(idx, _i64p),
                                   _p(mk, _u8p), idx.size, width,
                                   _p(out2, _f32p))
    return out


def scatter_rows(dst: np.ndarray, idx: np.ndarray, src: np.ndarray,
                 mask: Optional[np.ndarray] = None) -> None:
    """dst[idx[i]] = src[i] (float32 rows), threaded; idx duplicate-free
    (duplicates would race). ``mask`` limits to masked rows."""
    lib = load_library()
    idx = np.ascontiguousarray(idx, np.int64)
    if idx.size == 0:
        return
    if (lib is None or not dst.flags.c_contiguous
            or dst.dtype != np.float32):
        if mask is None:
            dst[idx] = src
        else:
            dst[idx[mask]] = src[mask]
        return
    dst2, width = _rows2d(dst)
    src2 = np.ascontiguousarray(src, np.float32).reshape(
        idx.size, max(width, 1))
    if mask is None:
        lib.pbx_scatter_rows(_p(dst2, _f32p), _p(idx, _i64p), idx.size,
                             width, _p(src2, _f32p))
    else:
        mk = np.ascontiguousarray(mask, np.uint8)
        lib.pbx_scatter_rows_masked(_p(dst2, _f32p), _p(idx, _i64p),
                                    _p(mk, _u8p), idx.size, width,
                                    _p(src2, _f32p))


def merge_sorted(old_keys: np.ndarray, add_keys: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Merge two sorted disjoint key arrays: returns (merged_keys [n+m],
    src [n+m] int64) with src[i] < n meaning old row src[i], else add row
    src[i]-n — one gather then materializes any merged value column."""
    o = np.ascontiguousarray(old_keys, np.uint64)
    a = np.ascontiguousarray(add_keys, np.uint64)
    n, m = o.size, a.size
    lib = load_library()
    if lib is None:
        ins = np.searchsorted(o, a)
        dst_new = ins + np.arange(m)
        merged = np.empty(n + m, np.uint64)
        src = np.empty(n + m, np.int64)
        is_new = np.zeros(n + m, bool)
        is_new[dst_new] = True
        merged[dst_new] = a
        src[dst_new] = n + np.arange(m)
        old_pos = np.flatnonzero(~is_new)
        merged[old_pos] = o
        src[old_pos] = np.arange(n)
        return merged, src
    merged = np.empty((n + m,), np.uint64)
    src = np.empty((n + m,), np.int64)
    lib.pbx_merge_sorted(_p(o, _u64p), n, _p(a, _u64p), m,
                         _p(merged, _u64p), _p(src, _i64p))
    return merged, src


def init_uniform(keys: np.ndarray, dim: int, seed: int,
                 scale: float) -> np.ndarray:
    """[n, dim] deterministic per-key uniform(-scale, scale) init —
    bit-exact twin of store.py _per_key_uniform."""
    k = np.ascontiguousarray(keys, np.uint64)
    lib = load_library()
    if lib is None or k.size == 0:
        from paddlebox_tpu.embedding.store import _per_key_uniform
        return _per_key_uniform(k, dim, np.uint64(seed), scale)
    out = np.empty((k.size, dim), np.float32)
    lib.pbx_init_uniform(_p(k, _u64p), k.size, dim, seed, scale,
                         _p(out, _f32p))
    return out
