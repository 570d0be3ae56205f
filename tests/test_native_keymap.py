"""Native key-map parity + smoke perf tests (role of the PreBuildTask /
CopyKeys host path, SURVEY.md §7 hard part #1)."""

import time

import numpy as np
import pytest

from paddlebox_tpu.embedding.table import map_keys_to_rows
from paddlebox_tpu.native.build import native_available
from paddlebox_tpu.native.keymap_py import KeyMap, dedup_keys

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native lib unavailable")


def test_dedup_matches_numpy():
    # Full uint64 range so every range shard (top byte) is exercised and
    # the cross-shard sorted concatenation is verified.
    rng = np.random.default_rng(0)
    keys = rng.integers(0, np.iinfo(np.uint64).max, 100_000, dtype=np.uint64)
    keys[::7] = 0  # null feasigns dropped
    keys[1::3] = keys[::3][:keys[1::3].size]  # heavy duplication
    out = dedup_keys(keys)
    ref = np.unique(keys)
    ref = ref[ref != 0]
    np.testing.assert_array_equal(out, ref)


@needs_native
def test_native_dedup_full_range_all_shards():
    """Force the NATIVE path regardless of core count: full-range keys hit
    all 256 range shards of pbx_dedup_u64."""
    import ctypes
    from paddlebox_tpu.native.build import load_library
    lib = load_library()
    rng = np.random.default_rng(42)
    keys = rng.integers(0, np.iinfo(np.uint64).max, 50_000, dtype=np.uint64)
    keys = np.concatenate([keys, keys[:10_000], np.zeros(100, np.uint64)])
    h = lib.pbx_dedup_u64(
        np.ascontiguousarray(keys).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint64)), keys.size)
    try:
        n = lib.pbx_dedup_size(h)
        out = np.empty((n,), np.uint64)
        lib.pbx_dedup_fill(
            h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    finally:
        lib.pbx_dedup_free(h)
    ref = np.unique(keys)
    ref = ref[ref != 0]
    np.testing.assert_array_equal(out, ref)
    # sanity: keys really spanned many top-byte shards
    assert np.unique(keys >> np.uint64(56)).size > 200


def test_dedup_empty_and_tiny():
    assert dedup_keys(np.empty((0,), np.uint64)).size == 0
    np.testing.assert_array_equal(
        dedup_keys(np.array([5, 5, 0, 3], np.uint64)), [3, 5])


@pytest.mark.parametrize("num_shards", [1, 4])
def test_keymap_matches_numpy_map(num_shards):
    rng = np.random.default_rng(1)
    n_keys = 5000
    keys = np.unique(rng.integers(1, 1 << 50, n_keys, dtype=np.uint64))
    rps = -(-keys.size // num_shards)
    km = KeyMap(keys, rps, num_shards)
    batch = rng.choice(keys, 20_000).astype(np.uint64)
    batch[::11] = rng.integers(1 << 51, 1 << 52, batch[::11].size,
                               dtype=np.uint64)  # misses
    batch[::13] = 0  # null
    out = km.lookup(batch)
    ref = map_keys_to_rows(keys, batch, rps, num_shards)
    np.testing.assert_array_equal(out, ref)
    km.close()


def test_keymap_empty_batch():
    keys = np.array([7, 9], np.uint64)
    km = KeyMap(keys, 2, 1)
    assert km.lookup(np.empty((0,), np.uint64)).size == 0
    km.close()


@pytest.mark.slow  # asserts a CPU wall-clock ratio: flakes under load
@needs_native
def test_native_faster_than_numpy_on_large_batch():
    """Smoke perf: native path should beat np.searchsorted on a realistic
    pass (4M keys, 4M-id batch). Generous 1.0x bar to avoid CI flakes —
    locally it's typically 3-10x."""
    rng = np.random.default_rng(2)
    keys = np.unique(rng.integers(1, 1 << 52, 4_000_000, dtype=np.uint64))
    rps = -(-keys.size // 8)
    batch = rng.choice(keys, 4_000_000).astype(np.uint64)

    km = KeyMap(keys, rps, 8)
    km.lookup(batch[:1000])  # warm
    t0 = time.perf_counter()
    out = km.lookup(batch)
    t_native = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref = map_keys_to_rows(keys, batch, rps, 8)
    t_numpy = time.perf_counter() - t0
    km.close()

    np.testing.assert_array_equal(out, ref)
    assert t_native < t_numpy * 1.0, (t_native, t_numpy)


@pytest.mark.slow  # asserts a CPU wall-clock ratio: flakes under load
@needs_native
def test_native_dedup_perf_smoke():
    """dedup_keys picks native only with >=4 cores; either way the result
    must match numpy, and on multi-core boxes be competitive."""
    import os
    rng = np.random.default_rng(3)
    keys = rng.integers(1, 1 << 40, 8_000_000, dtype=np.uint64)
    t0 = time.perf_counter()
    out = dedup_keys(keys)
    t_chosen = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = np.unique(keys)
    t_numpy = time.perf_counter() - t0
    np.testing.assert_array_equal(out, ref[ref != 0])
    if (os.cpu_count() or 1) >= 4:
        assert t_chosen < t_numpy * 2.0, (t_chosen, t_numpy)
