"""Host pass-build benchmark: native KeyIndex + dedup at production scale.

Measures the CPU-side half of the pass lifecycle that SURVEY.md §7 ranks
hard part #1 — "per-pass index build throughput on host" (role of the
reference's 16-way-sharded PreBuildTask, ps_gpu_wrapper.cc:114):

- ``index_build``: fresh upsert of N unique keys into the incremental
  key->row index (native/store.cc pbx_index_upsert; hugepage-backed
  open addressing + software prefetch pipeline).
- ``index_mixed``: a pass-shaped batch (half hits, half new keys).
- ``index_lookup``: the per-batch read path (threaded find).
- ``dedup``: unsorted duplicate-heavy pass keys -> sorted unique
  (native/keymap.cc pbx_dedup_u64; feed_pass role).

Runs entirely on the host (no TPU needed). Prints one JSON line per
metric; ``--json`` prints a single combined object instead.

    python tools/bench_native_store.py [--keys 50000000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=50_000_000)
    ap.add_argument("--batch", type=int, default=8_000_000)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    from paddlebox_tpu.native.build import native_available
    from paddlebox_tpu.native.keymap_py import dedup_keys
    from paddlebox_tpu.native.store_py import KeyIndex, bench_index_build

    if not native_available():
        print(json.dumps({"error": "native library unavailable"}))
        return

    n, b = args.keys, args.batch
    rng = np.random.default_rng(0)
    keys = rng.integers(1, 1 << 62, n, dtype=np.uint64)

    out = {"keys": n}
    # The headline metrics come from the ONE shared definition
    # (store_py.bench_index_build).
    out["index_build_keys_per_s"] = round(bench_index_build(n))
    # Round 13: sorted-run build (per-chunk dedup → run merge →
    # bulk_build) and the pre-r13 per-key dict walk it is measured
    # against (the ≥10× acceptance baseline).
    out["index_bulk_build_keys_per_s"] = round(
        bench_index_build(n, mode="bulk"))
    out["index_dict_build_keys_per_s"] = round(
        bench_index_build(min(n, 8_000_000), mode="dict"))

    # The remaining metrics reuse a populated index at the same scale.
    idx = KeyIndex()
    idx.reserve(n)
    for lo in range(0, n, 10_000_000):
        idx.upsert(keys[lo:lo + 10_000_000])

    mix = np.concatenate([
        rng.choice(keys, b // 2),
        rng.integers(1 << 62, 1 << 63, b // 2, dtype=np.uint64)])
    rng.shuffle(mix)
    t0 = time.perf_counter()
    rows, n_new = idx.upsert(mix)
    out["index_mixed_keys_per_s"] = round(b / (time.perf_counter() - t0))

    t0 = time.perf_counter()
    r2 = idx.lookup(mix)
    out["index_lookup_keys_per_s"] = round(b / (time.perf_counter() - t0))
    assert np.array_equal(rows, r2), "upsert/lookup row mismatch"

    # Pass-key dedup: 4x duplication factor, like a pass's batch stream.
    dup = rng.choice(keys[:b], b * 4)
    t0 = time.perf_counter()
    uniq = dedup_keys(dup)
    out["dedup_keys_per_s"] = round(dup.size / (time.perf_counter() - t0))
    assert uniq.size <= b and np.all(np.diff(uniq.astype(np.int64)) > 0)

    if args.json:
        print(json.dumps(out))
    else:
        for k, v in out.items():
            print(json.dumps({"metric": k, "value": v}))


if __name__ == "__main__":
    main()
