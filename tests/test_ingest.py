"""Round-13 host-ingest suite: bulk-parse bit-parity, multi-process
shared-memory ingest vs the thread reader, sorted-run store build vs the
incremental walk, worker-death surfacing, and shm leak hygiene.

Every comparison here is exact (np.array_equal) — the new ingest path is
an ACCELERATION of the old one, never an approximation.
"""

import gc
import os
import time

import numpy as np
import pytest

from paddlebox_tpu.core import faults, flags
from paddlebox_tpu.data import Dataset, DataFeedConfig, SlotConf, parse_lines
from paddlebox_tpu.data.columnar import instances_to_chunk
from paddlebox_tpu.data.parser import parse_block_numpy

CFG = DataFeedConfig(
    slots=(
        SlotConf("user", avg_len=2.0),
        SlotConf("item", avg_len=1.0),
        SlotConf("dense0", is_dense=True, dim=3),
    ),
    batch_size=4,
    num_labels=1,
)


def _shm_leftovers():
    d = "/dev/shm"
    if not os.path.isdir(d):
        return []
    return [e for e in os.listdir(d) if e.startswith("pbx-ing-")]


def _assert_chunks_equal(a, b):
    np.testing.assert_array_equal(a.labels, b.labels)
    assert set(a.sparse_ids) == set(b.sparse_ids)
    for s in a.sparse_ids:
        np.testing.assert_array_equal(a.sparse_ids[s], b.sparse_ids[s])
        np.testing.assert_array_equal(a.sparse_offsets[s],
                                      b.sparse_offsets[s])
    assert set(a.dense) == set(b.dense)
    for s in a.dense:
        np.testing.assert_array_equal(a.dense[s], b.dense[s])


@pytest.fixture(autouse=True)
def _reset_flags():
    prev = flags.get_flags(["ingest_workers", "ingest_file_retries",
                            "ingest_key_runs"])
    yield
    flags.set_flags(prev)
    faults.clear()


def _write_files(tmp_path, n_files=3, n_rows=40, seed=0):
    rng = np.random.default_rng(seed)
    files = []
    for j in range(n_files):
        lines = []
        for i in range(n_rows):
            uids = rng.integers(1, 1 << 40, rng.integers(1, 4))
            user = " ".join(f"user:{u}" for u in uids)
            lines.append(f"{i % 2} {user} item:{j * n_rows + i + 1} "
                         f"dense0:{i}.5,{i},{i}")
        p = tmp_path / f"part-{j}"
        p.write_text("\n".join(lines) + "\n")
        files.append(str(p))
    return files


# -- bulk parser bit-parity -------------------------------------------------

def test_bulk_parse_matches_per_line_parser():
    blocks = [
        b"1 user:11 user:12 item:7 dense0:0.5,1.5,2.5\n0 user:13 item:9\n",
        b"1 user:5\n",
        b"1 user:0 item:3\n",              # null feasign -> dropped token
        b"\n\n1 user:1\n",                 # empty lines skipped
        b"1 unknown:9 user:2\n",           # unused slot ignored
        b"1 dense0:1,2,3 dense0:4,5,6\n",  # dup dense -> last wins
        b"0.5 user:3",                     # no trailing newline
        b"1\n",                            # labels only
    ]
    for blk in blocks:
        got = parse_block_numpy(blk, CFG)
        assert got is not None, blk
        want = instances_to_chunk(
            parse_lines(blk.decode("utf-8", "replace").split("\n"), CFG),
            CFG)
        _assert_chunks_equal(got, want)


def test_bulk_parse_defers_exotic_input_to_exact_path():
    # Inputs whose handling depends on per-token error semantics must
    # go to the exact parser (None), never be approximated.
    for blk in (b"1 user:-5\n", b"garbage nolabel\n", b"1 user:abc\n",
                b"1  user:3\n", b"1 user:3 \n", b"1\tuser:3\n",
                b"1 user:99999999999999999999\n", b"1 user\n",
                b"1 user:\n", "1 user:é\n".encode()):
        assert parse_block_numpy(blk, CFG) is None, blk


def test_bulk_parse_large_random_block_parity():
    rng = np.random.default_rng(3)
    lines = []
    for i in range(2000):
        n_u = rng.integers(0, 5)
        toks = [str(i % 2)]
        toks += [f"user:{rng.integers(1, 1 << 60)}" for _ in range(n_u)]
        if rng.random() < 0.7:
            toks.append(f"item:{rng.integers(1, 1 << 30)}")
        if rng.random() < 0.5:
            toks.append(f"dense0:{rng.random():.4f},{rng.random():.4f},1")
        lines.append(" ".join(toks))
    blk = ("\n".join(lines) + "\n").encode()
    got = parse_block_numpy(blk, CFG)
    assert got is not None
    want = instances_to_chunk(parse_lines(blk.decode().split("\n"), CFG),
                              CFG)
    _assert_chunks_equal(got, want)


# -- multi-process ingest vs thread reader ----------------------------------

def test_mp_ingest_bit_parity_across_worker_counts(tmp_path):
    files = _write_files(tmp_path)
    ds_ref = Dataset(CFG, num_reader_threads=2)
    ds_ref.set_filelist(files)
    ds_ref.load_into_memory()
    ref_keys = ds_ref.pass_keys()
    ref_user = ds_ref.pass_keys(slots=["user"])
    ref_batches = list(ds_ref.batches())

    from paddlebox_tpu.embedding.table import map_keys_to_rows
    probe = ref_keys[:: max(1, ref_keys.size // 64)]
    ref_rows = map_keys_to_rows(ref_keys, probe, 1 << 12, 2)

    for workers in (1, 4):
        flags.set_flags({"ingest_workers": workers})
        seen = []
        ds = Dataset(CFG)
        ds.key_sink = lambda k: seen.append(k)
        ds.set_filelist(files)
        ds.load_into_memory()
        assert ds.num_instances == ds_ref.num_instances
        # Identical pass keys (and per-slot key sets) regardless of
        # which process parsed what in which order.
        np.testing.assert_array_equal(ds.pass_keys(), ref_keys)
        np.testing.assert_array_equal(ds.pass_keys(slots=["user"]),
                                      ref_user)
        # key_sink saw the same key multiset the thread path feeds.
        np.testing.assert_array_equal(
            np.unique(np.concatenate(seen)), ref_keys)
        # Identical row maps: same sorted keys -> same sharded layout.
        np.testing.assert_array_equal(
            map_keys_to_rows(ds.pass_keys(), probe, 1 << 12, 2), ref_rows)
        # Identical chunk CONTENTS: rows in a canonical order.
        got = _sorted_rows(ds)
        want = _sorted_rows(ds_ref)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert len(list(ds.batches())) == len(ref_batches)
        ds.clear()
    gc.collect()
    assert not _shm_leftovers()


def _sorted_rows(ds):
    """Canonical (order-insensitive) view of the loaded records: rows
    sorted by (item key) — unique per row in _write_files — so thread
    and process loads compare content-equal despite arrival order."""
    merged = ds._merge()
    item = merged.sparse_ids["item"][merged.sparse_offsets["item"][:-1]]
    order = np.argsort(item, kind="stable")
    m = merged.take(order)
    return [m.labels, m.sparse_ids["user"], m.sparse_offsets["user"],
            m.sparse_ids["item"], m.dense["dense0"]]


def test_ingest_worker_never_reaches_a_device():
    """One process per chip: a worker spawned while the parent holds the
    accelerator must not initialise a backend. The parse path imports no
    jax at all, and worker_main pins the platform to CPU for the case
    where spawn re-imports a parent main module that does."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "import paddlebox_tpu.data.ingest_worker as w\n"
        "from paddlebox_tpu.data.dataset import _parse_block, _read_blocks\n"
        "assert 'jax' not in sys.modules, 'parse path imports jax'\n"
        "import jax\n"
        "w._pin_cpu()\n"
        "assert jax.config.jax_platforms == 'cpu'\n"
        "assert jax.default_backend() == 'cpu'\n")
    env = dict(os.environ, JAX_PLATFORMS="tpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_mp_ingest_worker_error_surfaces(tmp_path):
    files = _write_files(tmp_path, n_files=2)
    cfg = DataFeedConfig(slots=CFG.slots, batch_size=4,
                         pipe_command="nonexistent-cmd-xyz")
    flags.set_flags({"ingest_workers": 2})
    ds = Dataset(cfg)
    ds.set_filelist(files)
    with pytest.raises(RuntimeError, match="pipe_command"):
        ds.load_into_memory()
    gc.collect()
    assert not _shm_leftovers()


def test_mp_ingest_faultpoints_surface(tmp_path):
    files = _write_files(tmp_path, n_files=1)
    flags.set_flags({"ingest_workers": 1})
    for site, exc in (("ingest/worker_spawn", OSError),
                      ("ingest/shm_attach", OSError)):
        faults.configure(f"{site}:raise=IOError")
        ds = Dataset(CFG)
        ds.set_filelist(files)
        with pytest.raises(exc):
            ds.load_into_memory()
        faults.clear()
        gc.collect()
        assert not _shm_leftovers(), site


def test_mp_ingest_custom_parser_falls_back_to_threads(tmp_path):
    # parser_fn closures cannot cross a process boundary; the flag must
    # not break instance-scoped parsers.
    files = _write_files(tmp_path, n_files=1)
    flags.set_flags({"ingest_workers": 4})
    calls = []

    def pf(lines, config):
        calls.append(1)
        return parse_lines(lines, config)

    ds = Dataset(CFG, parser_fn=pf)
    ds.set_filelist(files)
    ds.load_into_memory()
    assert calls, "custom parser_fn was bypassed"
    assert ds.num_instances == 40


def test_mp_ingest_dump_into_disk(tmp_path):
    files = _write_files(tmp_path)
    spill = tmp_path / "spill"
    flags.set_flags({"ingest_workers": 2})
    ds = Dataset(CFG)
    ds.set_filelist(files)
    n = ds.dump_into_disk(str(spill))
    assert n >= 1
    ds2 = Dataset(CFG)
    ds2.load_from_disk(str(spill))
    assert ds2.num_instances == 120
    gc.collect()
    assert not _shm_leftovers()


# -- sorted-run pass keys ----------------------------------------------------

def test_pass_keys_runs_vs_fallback_parity(tmp_path):
    files = _write_files(tmp_path)
    # ONE reader thread, in-process: the global_shuffle partition below
    # drops rows BY POSITION, so this parity needs the two datasets
    # loaded in the same row order — multi-threaded (or mp-ingest)
    # chunk arrival order is scheduling-dependent and flaked this test.
    flags.set_flags({"ingest_workers": 0, "ingest_key_runs": True})
    ds_runs = Dataset(CFG, num_reader_threads=1)
    ds_runs.set_filelist(files)
    ds_runs.load_into_memory()
    assert ds_runs._key_runs_valid

    flags.set_flags({"ingest_key_runs": False})
    ds_flat = Dataset(CFG, num_reader_threads=1)
    ds_flat.set_filelist(files)
    ds_flat.load_into_memory()
    assert not ds_flat._key_runs_valid

    np.testing.assert_array_equal(ds_runs.pass_keys(), ds_flat.pass_keys())
    for slots in (["user"], ["item"], ["user", "item"], ["nosuch"]):
        np.testing.assert_array_equal(ds_runs.pass_keys(slots=slots),
                                      ds_flat.pass_keys(slots=slots))
    # local_shuffle preserves the key set -> runs stay valid and exact.
    ds_runs.local_shuffle(7)
    ds_flat.local_shuffle(7)
    np.testing.assert_array_equal(ds_runs.pass_keys(), ds_flat.pass_keys())
    # global_shuffle with a partition DROPS rows -> must fall back.
    ds_runs.global_shuffle(num_ranks=2, rank=0, seed=1,
                           allow_partition=True)
    ds_flat.global_shuffle(num_ranks=2, rank=0, seed=1,
                           allow_partition=True)
    assert not ds_runs._key_runs_valid
    np.testing.assert_array_equal(ds_runs.pass_keys(), ds_flat.pass_keys())


def test_pass_keys_runs_preserve_zero_key():
    # A custom parser may emit the 0 sentinel; pass_keys always reported
    # it and the run path must too (dedup_keys drops it by design).
    from paddlebox_tpu.data.slots import Instance

    def pf(lines, config):
        out = []
        for line in lines:
            if not line:
                continue
            out.append(Instance(
                labels=np.zeros((1,), np.float32),
                sparse={"user": np.array([0, 5], np.uint64)},
                dense={}))
        return out

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "f")
        with open(p, "w") as f:
            f.write("x\nx\n")
        ds = Dataset(CFG, parser_fn=pf)
        ds.set_filelist([p])
        ds.load_into_memory()
        assert ds._key_runs_valid
        np.testing.assert_array_equal(ds.pass_keys(),
                                      np.array([0, 5], np.uint64))


# -- the streamed merger: keys united while the load runs ---------------------

def _made_chunks(n_chunks, seed, *, zero_in=None, empty=None, hi=400):
    """Chunks of 1..40 rows whose slots share many ids (drawn under
    ``hi``); ``zero_in`` plants the 0 sentinel in that slot of one chunk,
    ``empty`` leaves that slot without an id in every row."""
    from paddlebox_tpu.data.slots import Instance
    rng = np.random.default_rng(seed)
    chunks = []
    for c in range(n_chunks):
        rows = []
        for _ in range(int(rng.integers(1, 41))):
            sparse = {s: rng.integers(1, hi, rng.integers(1, 4),
                                      dtype=np.uint64)
                      for s in ("user", "item") if s != empty}
            rows.append(Instance(labels=np.zeros((1,), np.float32),
                                 sparse=sparse,
                                 dense={"dense0": np.zeros(3, np.float32)}))
        if zero_in is not None and c == n_chunks // 2:
            rows[0].sparse[zero_in] = np.array([0, 7], np.uint64)
        chunks.append(instances_to_chunk(rows, CFG))
    return chunks


def _drained(chunks):
    """A Dataset that took ``chunks`` through _drain in this order."""
    from paddlebox_tpu.data.channel import Channel
    ds = Dataset(CFG)
    ch = Channel(len(chunks) + 1)
    for c in chunks:
        ch.put(c)
    ch.close()
    ds._drain(ch)
    ds._raise_reader_errors()
    return ds


def _exact_keys(chunks, slots=None):
    names = ("user", "item") if slots is None else slots
    parts = [c.sparse_ids[s] for c in chunks for s in names
             if s in c.sparse_ids]
    return np.unique(np.concatenate(parts)) if parts else \
        np.empty((0,), np.uint64)


SLOT_SUBSETS = (None, ["user"], ["item"], ["item", "user"],
                ["item", "nosuch"], ["nosuch"])


@pytest.mark.parametrize("order", ["as_made", "reversed", "shuffled"])
@pytest.mark.parametrize("n_chunks", [1, 3, 7, 12, 21])
def test_streamed_merger_equals_np_unique(n_chunks, order):
    flags.set_flags({"ingest_key_runs": True})
    chunks = _made_chunks(n_chunks, seed=n_chunks)
    if order == "reversed":
        chunks = chunks[::-1]
    elif order == "shuffled":
        np.random.default_rng(5).shuffle(chunks)
    ds = _drained(chunks)
    assert ds._key_runs_valid
    # every slot's stack was united before the load returned
    assert all(m.num_runs == 1 for m in ds._key_mergers.values())
    for slots in SLOT_SUBSETS:
        got = ds.pass_keys(slots=slots)
        assert got.dtype == np.uint64
        np.testing.assert_array_equal(got, _exact_keys(chunks, slots))
    # a second call answers the same, from the same united runs
    np.testing.assert_array_equal(ds.pass_keys(), _exact_keys(chunks))


@pytest.mark.parametrize("zero_in", ["user", "item"])
def test_streamed_merger_keeps_a_zero_id_of_one_slot(zero_in):
    chunks = _made_chunks(6, seed=3, zero_in=zero_in)
    ds = _drained(chunks)
    other = "item" if zero_in == "user" else "user"
    assert ds.pass_keys()[0] == 0
    assert ds.pass_keys(slots=[zero_in])[0] == 0
    assert ds.pass_keys(slots=[other])[0] != 0
    for slots in SLOT_SUBSETS:
        np.testing.assert_array_equal(ds.pass_keys(slots=slots),
                                      _exact_keys(chunks, slots))


def test_streamed_merger_with_an_empty_slot():
    chunks = _made_chunks(5, seed=4, empty="item")
    ds = _drained(chunks)
    assert ds.pass_keys(slots=["item"]).size == 0
    for slots in SLOT_SUBSETS:
        np.testing.assert_array_equal(ds.pass_keys(slots=slots),
                                      _exact_keys(chunks, slots))
    assert _drained([]).pass_keys().size == 0


def test_streamed_merger_counts_its_runs():
    from paddlebox_tpu.core import monitor
    chunks = _made_chunks(9, seed=8)
    runs0 = monitor.get("ingest/key_runs")
    merged0 = monitor.get("ingest/key_runs_merged_in_load")
    served0 = monitor.get("ingest/pass_keys_from_runs")
    ds = _drained(chunks)
    # 9 chunks x 2 slots handed in, all but one a slot folded under the load
    assert monitor.get("ingest/key_runs") - runs0 == 18
    assert monitor.get("ingest/key_runs_merged_in_load") - merged0 == 16
    assert ds.key_run_counts() == (18, 16)
    ds.pass_keys()
    ds.pass_keys(slots=["user"])
    assert monitor.get("ingest/pass_keys_from_runs") - served0 == 2
    # the load's helper is gone with the load
    import threading
    assert not [t for t in threading.enumerate()
                if t.name == "pbx-ingest-keys"]


@pytest.mark.parametrize("how", ["global_shuffle", "restored_chunk",
                                 "runs_off"])
def test_exact_path_answers_once_the_runs_are_invalid(how):
    from paddlebox_tpu.core import monitor
    chunks = _made_chunks(6, seed=6, zero_in="user")
    flags.set_flags({"ingest_key_runs": how != "runs_off"})
    ds = _drained(chunks)
    if how == "global_shuffle":
        ds.global_shuffle(num_ranks=2, rank=0, seed=1, allow_partition=True)
    elif how == "restored_chunk":
        ds.restore_chunks(ds.snapshot_chunks())
    assert not ds._key_runs_valid and not ds._key_mergers
    served0 = monitor.get("ingest/pass_keys_from_runs")
    kept = [ds._merge()]
    for slots in SLOT_SUBSETS:
        np.testing.assert_array_equal(ds.pass_keys(slots=slots),
                                      _exact_keys(kept, slots))
    assert monitor.get("ingest/pass_keys_from_runs") == served0
    # a load after clear() collects runs again (with the flag on)
    ds.clear()
    assert ds._key_runs_valid


def test_a_failing_key_helper_fails_the_load(monkeypatch):
    from paddlebox_tpu.native import keymap_py

    def boom(ids):
        raise OSError("dedup failed")
    monkeypatch.setattr(keymap_py, "dedup_keys", boom)
    with pytest.raises(OSError, match="dedup failed"):
        _drained(_made_chunks(3, seed=1))


@pytest.mark.parametrize("n_runs", [1, 2, 3, 5, 8, 13, 33, 100])
def test_sorted_run_merger_holds_few_runs_and_merges_exactly(n_runs):
    from paddlebox_tpu.native.store_py import SortedRunMerger
    rng = np.random.default_rng(n_runs)
    runs = [np.unique(rng.integers(1, 5000, rng.integers(1, 700),
                                   dtype=np.uint64))
            for _ in range(n_runs)]
    merger = SortedRunMerger()
    total = 0
    for r in runs:
        merger.add_run(r)
        total += r.size
        # held runs shrink by halves: never more than log2 of the ids
        assert merger.num_runs <= max(1, int(np.log2(total)) + 1)
    want = np.unique(np.concatenate(runs))
    np.testing.assert_array_equal(merger.merge(), want)
    assert merger.num_runs == 1
    np.testing.assert_array_equal(merger.merge(), want)
    merger.add_run(np.array([7, 9999], np.uint64))      # goes on after
    np.testing.assert_array_equal(
        merger.merge(), np.union1d(want, np.array([7, 9999], np.uint64)))


@pytest.mark.parametrize("native", [True, False])
def test_merge_unique_native_and_fallback_agree(native, monkeypatch):
    from paddlebox_tpu.native import store_py
    if not native:
        monkeypatch.setattr(store_py, "load_library", lambda: None)
    elif store_py.load_library() is None:
        pytest.skip("no native library here")
    rng = np.random.default_rng(2)
    empty = np.empty((0,), np.uint64)
    # the last pair is long enough for the native union to run threaded
    for n, m, hi in [(0, 0, 9), (0, 5, 9), (5, 0, 9), (50, 50, 60),
                     (3000, 10, 1 << 40), (10, 3000, 5000),
                     (300_000, 200_000, 600_000)]:
        a = np.unique(rng.integers(1, hi, n, dtype=np.uint64)) if n else empty
        b = np.unique(rng.integers(1, hi, m, dtype=np.uint64)) if m else empty
        np.testing.assert_array_equal(store_py.merge_unique(a, b),
                                      np.union1d(a, b))


# -- sorted-run store build vs incremental upsert ---------------------------

def test_bulk_build_matches_upsert_rows_and_keys():
    from paddlebox_tpu.native.store_py import KeyIndex, SortedRunMerger
    from paddlebox_tpu.native.keymap_py import dedup_keys
    rng = np.random.default_rng(11)
    chunks = [rng.integers(1, 1 << 48, 20_000, dtype=np.uint64)
              for _ in range(5)]
    # Sorted-run build: dedup each chunk as it "arrives", merge, bulk.
    merger = SortedRunMerger()
    for c in chunks:
        merger.add_run(dedup_keys(c))
    keys = merger.merge()
    np.testing.assert_array_equal(
        keys, np.unique(np.concatenate(chunks)))
    bulk, inc = KeyIndex(), KeyIndex()
    rows_bulk = bulk.bulk_build(keys)
    rows_inc, n_new = inc.upsert(keys)
    assert n_new == keys.size
    np.testing.assert_array_equal(rows_bulk, rows_inc)
    np.testing.assert_array_equal(bulk.keys_by_row(), inc.keys_by_row())
    q = rng.integers(1, 1 << 48, 5_000, dtype=np.uint64)
    np.testing.assert_array_equal(bulk.lookup(q), inc.lookup(q))
    bulk.close()
    inc.close()


def test_keyindex_fallback_matches_native():
    """The vectorized numpy fallback must be bit-identical to the native
    index on every surface (lookup/upsert/bulk_build/keys_by_row),
    including first-appearance row order and intra-batch duplicates."""
    import paddlebox_tpu.native.store_py as sp
    rng = np.random.default_rng(4)
    b1 = rng.integers(0, 500, 2_000, dtype=np.uint64)     # dups + zeros
    b2 = rng.integers(0, 1_000, 1_500, dtype=np.uint64)
    native = sp.KeyIndex()
    if native._h is None:
        pytest.skip("native library unavailable — nothing to compare")
    orig = sp.load_library
    sp.load_library = lambda: None
    try:
        fb = sp.KeyIndex()
        fb.reserve(2_000)  # honored as a pre-size hint, not a no-op
        assert fb._fb_by_row.shape[0] >= 2_000
    finally:
        sp.load_library = orig
    for idx in (native, fb):
        r1, n1 = idx.upsert(b1)
        r2, n2 = idx.upsert(b2)
        idx._res = (r1, n1, r2, n2)
    for a, b in zip(native._res, fb._res):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(native.keys_by_row(), fb.keys_by_row())
    q = rng.integers(0, 1_200, 3_000, dtype=np.uint64)
    np.testing.assert_array_equal(native.lookup(q), fb.lookup(q))
    assert native.size == fb.size
    native.close()
    fb.close()


def test_device_store_bulk_build_bit_parity(devices8, monkeypatch):
    """Fresh-build bypass vs incremental upsert on the HBM-tier store,
    SAME sorted input: same rows, same on-device values."""
    from paddlebox_tpu.core import monitor
    from paddlebox_tpu.embedding import TableConfig, device_store
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(1, 1 << 40, 3_000, dtype=np.uint64))
    cfg = TableConfig(dim=8)
    before = monitor.get("device_store/bulk_builds")
    fresh = device_store.DeviceFeatureStore(cfg)  # sorted -> bulk path
    r_fresh = fresh.ensure_rows(keys)
    assert monitor.get("device_store/bulk_builds") == before + 1
    # Same input through the incremental walk (bypass disabled).
    monkeypatch.setattr(device_store.native_store,
                        "is_sorted_unique_nonzero", lambda k: False)
    incr = device_store.DeviceFeatureStore(cfg)
    r_incr = incr.ensure_rows(keys)
    np.testing.assert_array_equal(r_fresh, r_incr)
    for a, b in zip(fresh._parts, incr._parts):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Later batches through the normal upsert path still line up.
    more = np.unique(rng.integers(1, 1 << 40, 500, dtype=np.uint64))
    np.testing.assert_array_equal(fresh.ensure_rows(more),
                                  incr.ensure_rows(more))


def test_bench_index_build_modes_agree():
    from paddlebox_tpu.native.store_py import bench_index_build
    for mode in ("upsert", "bulk", "dict"):
        rate = bench_index_build(50_000, chunk=20_000, mode=mode)
        assert rate > 0
    with pytest.raises(ValueError):
        bench_index_build(1000, mode="nope")


# -- worker death ------------------------------------------------------------

@pytest.mark.slow
def test_mp_ingest_worker_death_exhausted_retries_raises(tmp_path):
    files = _write_files(tmp_path, n_files=1)
    started = tmp_path / "started"
    cfg = DataFeedConfig(slots=CFG.slots, batch_size=4,
                         pipe_command=f"touch {started}; sleep 30; cat")
    flags.set_flags({"ingest_workers": 1, "ingest_file_retries": 0})
    ds = Dataset(cfg)
    ds.set_filelist(files)
    ds.preload_into_memory()
    t0 = time.time()
    # The sentinel proves the worker is INSIDE the file (file_start
    # sent) — killing earlier would be an idle death, which respawns.
    while not started.exists() and time.time() - t0 < 60:
        time.sleep(0.05)
    assert started.exists()
    time.sleep(0.2)
    assert ds._ingest_procs
    os.kill(ds._ingest_procs[0].pid, 9)
    with pytest.raises(RuntimeError, match="ingest worker died"):
        ds.wait_preload_done()
    gc.collect()
    assert not _shm_leftovers()
