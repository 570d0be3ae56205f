"""On-demand g++ build of the native library, with content-hash caching."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

from paddlebox_tpu.core import log

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["parser.cc", "keymap.cc", "store.cc", "graph.cc"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False


def _cache_dir() -> str:
    d = os.environ.get("PBX_NATIVE_CACHE",
                       os.path.join(os.path.expanduser("~"), ".cache",
                                    "paddlebox_tpu"))
    os.makedirs(d, exist_ok=True)
    return d


def _build() -> Optional[str]:
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    so_path = os.path.join(_cache_dir(), f"libpbx_native_{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           "-o", so_path + ".tmp"] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired) as e:
        err = getattr(e, "stderr", b"")
        log.warning("native build failed (%s); using python fallbacks: %s",
                    e, err.decode() if isinstance(err, bytes) else err)
        return None
    os.replace(so_path + ".tmp", so_path)
    log.vlog(1, "built native library -> %s", so_path)
    return so_path


def load_library() -> Optional[ctypes.CDLL]:
    """Build (cached) + dlopen the native library; None if unavailable."""
    global _lib, _failed
    with _lock:
        if _lib is not None or _failed:
            return _lib
        path = _build()
        if path is None:
            _failed = True
            return None
        lib = ctypes.CDLL(path)
        # Signatures.
        lib.pbx_parse_svm.restype = ctypes.c_void_p
        lib.pbx_parse_svm.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32, ctypes.c_int32]
        for fn in ("pbx_result_rows", "pbx_result_malformed",
                   "pbx_result_dropped"):
            getattr(lib, fn).restype = ctypes.c_int64
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.pbx_result_sparse_size.restype = ctypes.c_int64
        lib.pbx_result_sparse_size.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int32]
        lib.pbx_result_fill.restype = None
        lib.pbx_result_fill.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]
        lib.pbx_result_free.restype = None
        lib.pbx_result_free.argtypes = [ctypes.c_void_p]
        # keymap.cc
        lib.pbx_keymap_build.restype = ctypes.c_void_p
        lib.pbx_keymap_build.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64]
        lib.pbx_keymap_size.restype = ctypes.c_int64
        lib.pbx_keymap_size.argtypes = [ctypes.c_void_p]
        lib.pbx_keymap_lookup.restype = None
        lib.pbx_keymap_lookup.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32)]
        lib.pbx_keymap_free.restype = None
        lib.pbx_keymap_free.argtypes = [ctypes.c_void_p]
        lib.pbx_dedup_u64.restype = ctypes.c_void_p
        lib.pbx_dedup_u64.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64]
        lib.pbx_dedup_size.restype = ctypes.c_int64
        lib.pbx_dedup_size.argtypes = [ctypes.c_void_p]
        lib.pbx_dedup_fill.restype = None
        lib.pbx_dedup_fill.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64)]
        lib.pbx_dedup_free.restype = None
        lib.pbx_dedup_free.argtypes = [ctypes.c_void_p]
        # store.cc — incremental index
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.pbx_index_new.restype = ctypes.c_void_p
        lib.pbx_index_new.argtypes = []
        lib.pbx_index_size.restype = ctypes.c_int64
        lib.pbx_index_size.argtypes = [ctypes.c_void_p]
        lib.pbx_index_reserve.restype = None
        lib.pbx_index_reserve.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pbx_index_lookup.restype = None
        lib.pbx_index_lookup.argtypes = [ctypes.c_void_p, u64p,
                                         ctypes.c_int64, i64p]
        lib.pbx_index_upsert.restype = ctypes.c_int64
        lib.pbx_index_upsert.argtypes = [ctypes.c_void_p, u64p,
                                         ctypes.c_int64, i64p]
        lib.pbx_index_keys_fill.restype = None
        lib.pbx_index_keys_fill.argtypes = [ctypes.c_void_p, u64p]
        lib.pbx_index_bulk_build.restype = ctypes.c_int64
        lib.pbx_index_bulk_build.argtypes = [ctypes.c_void_p, u64p,
                                             ctypes.c_int64]
        lib.pbx_index_free.restype = None
        lib.pbx_index_free.argtypes = [ctypes.c_void_p]
        # store.cc — sorted-store primitives
        lib.pbx_ss_locate.restype = None
        lib.pbx_ss_locate.argtypes = [u64p, ctypes.c_int64, u64p,
                                      ctypes.c_int64, i64p, u8p]
        lib.pbx_gather_rows.restype = None
        lib.pbx_gather_rows.argtypes = [f32p, i64p, ctypes.c_int64,
                                        ctypes.c_int64, f32p]
        lib.pbx_scatter_rows.restype = None
        lib.pbx_scatter_rows.argtypes = [f32p, i64p, ctypes.c_int64,
                                         ctypes.c_int64, f32p]
        lib.pbx_gather_rows_masked.restype = None
        lib.pbx_gather_rows_masked.argtypes = [f32p, i64p, u8p,
                                               ctypes.c_int64,
                                               ctypes.c_int64, f32p]
        lib.pbx_scatter_rows_masked.restype = None
        lib.pbx_scatter_rows_masked.argtypes = [f32p, i64p, u8p,
                                                ctypes.c_int64,
                                                ctypes.c_int64, f32p]
        lib.pbx_merge_sorted.restype = None
        lib.pbx_merge_sorted.argtypes = [u64p, ctypes.c_int64, u64p,
                                         ctypes.c_int64, u64p, i64p]
        lib.pbx_union_sorted.restype = ctypes.c_int64
        lib.pbx_union_sorted.argtypes = [u64p, ctypes.c_int64, u64p,
                                         ctypes.c_int64, u64p]
        lib.pbx_init_uniform.restype = None
        lib.pbx_init_uniform.argtypes = [u64p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_uint64,
                                         ctypes.c_double, f32p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_library() is not None
