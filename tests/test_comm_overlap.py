"""Overlapped DCN exchange, quantized dense allreduce, chunked COPY.

Pins for the "hide and shrink every DCN byte" round (MULTIHOST.md):

- overlapped boundary exchange: the async push + barrier-free boundary
  pull sequence is BIT-identical to the serial wire across shared-key
  fractions {0, 0.5, 1} x wire dtypes {f32, int8} — overlap changes
  when bytes move, never which bytes;
- exchange worker safety: queued jobs always run to completion (reads
  drain first, reset after an async push leaves no torn rows);
- one coalesced boundary pull + one owner-plan derivation per pass
  (multihost/boundary_pulls, multihost/plan_misses);
- quantized_psum: f32 wire bit-identical to lax.psum; int8 wire within
  the blocked-codec error bound derived from the np twin; trainer-level
  dense sync at int8 still learns and tracks the f32 loss;
- chunked COPY: paged pull_range walk is digest-identical to the
  whole-range move, kill -9 between chunk windows recovers through
  recovery_chain with no lost/double rows; chunked replica snapshot
  commits atomically (mid-stream crash leaves the sentinel epoch that
  forces a clean re-snapshot).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.core import monitor
from paddlebox_tpu.embedding.store import _FIELDS
from paddlebox_tpu.embedding.table import TableConfig, shared_key_mask
from paddlebox_tpu.multihost import (MultiHostStore, ShardRangeTable,
                                     execute_reshard, start_local_shards,
                                     stop_shards)
from paddlebox_tpu.multihost.quant import (dequantize_blocked_np,
                                           quantize_blocked_np)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TableConfig(name="emb", dim=8, learning_rate=0.1)


def _rand_keys(n, seed=0, hi=1 << 50):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, hi, size=n + 64, dtype=np.uint64))
    assert keys.size >= n
    return keys[:n]


def _two_pass_keys(share: float, n=1200, seed=21):
    """Two sorted pass key arrays where `share` of pass 2's keys also
    appear in pass 1 (the boundary's shared-key fraction)."""
    k1 = _rand_keys(n, seed=seed)
    n_sh = int(round(share * n))
    rng = np.random.default_rng(seed + 1)
    fresh = np.unique(rng.integers(1 << 51, 1 << 52, size=n - n_sh,
                                   dtype=np.uint64))
    k2 = np.sort(np.concatenate([
        rng.choice(k1, size=n_sh, replace=False), fresh]))
    assert np.unique(k2).size == k2.size
    return k1, k2


def _boundary_sequence(eps, k1, k2):
    """The pass-engine boundary wire sequence against one cluster:
    seed pass 1's rows, write them back split priority/bulk, then pull
    pass 2 as early (non-shared, barriered) + boundary (shared,
    barrier-free) windows. Returns pass 2's assembled rows."""
    store = MultiHostStore(CFG, eps)
    try:
        rows = store.pull_for_pass(k1, pass_id=1)
        rng = np.random.default_rng(5)
        rows["emb"] = rng.normal(size=rows["emb"].shape).astype(
            np.float32)
        rows["show"] += 1.0
        pri = shared_key_mask(k2, k1)     # prev ∩ next, over k1
        job = store.push_from_pass_async(k1, rows, priority_select=pri,
                                         pass_id=1)
        shared2 = shared_key_mask(k1, k2)  # prev ∩ next, over k2
        full = {}
        early = (store.pull_for_pass(k2, ~shared2, pass_id=2)
                 if (~shared2).any() else None)
        boundary = (store.pull_for_pass(k2, shared2, pass_id=2,
                                        barrier=False, boundary=True)
                    if shared2.any() else None)
        job.wait()
        for f in _FIELDS:
            ref = (early or boundary)[f]
            buf = np.zeros((k2.size,) + ref.shape[1:], ref.dtype)
            if early is not None:
                buf[~shared2] = early[f]
            if boundary is not None:
                buf[shared2] = boundary[f]
            full[f] = buf
        return full
    finally:
        store.close()


@pytest.mark.parametrize("wire", ["f32", "int8"])
@pytest.mark.parametrize("share", [0.0, 0.5, 1.0])
def test_overlap_bit_identical_to_serial(share, wire):
    """Overlap on vs off is a pure scheduling change: the assembled
    pass-2 rows are BIT-identical on every wire dtype at every
    shared-key fraction."""
    k1, k2 = _two_pass_keys(share)
    prev = flagmod.get_flags(["multihost_overlap_exchange",
                              "multihost_wire_dtype"])
    outs = {}
    try:
        for overlap in (True, False):
            flagmod.set_flags({"multihost_overlap_exchange": overlap,
                               "multihost_wire_dtype": wire})
            servers, eps = start_local_shards(2, CFG)
            try:
                outs[overlap] = _boundary_sequence(eps, k1, k2)
            finally:
                stop_shards(servers)
    finally:
        flagmod.set_flags(prev)
    for f in _FIELDS:
        np.testing.assert_array_equal(outs[True][f], outs[False][f],
                                      err_msg=f"{f} wire={wire}")


def test_exchange_jobs_complete_reads_drain_reset_not_torn():
    """The worker never leaves torn peer state: a queued bulk push is
    fully visible to the next read (reads drain), and an admin reset
    right behind an async push still lands on a quiesced cluster."""
    servers, eps = start_local_shards(2, CFG)
    store = MultiHostStore(CFG, eps)
    try:
        k1, k2 = _two_pass_keys(0.5, n=800, seed=33)
        rows = store.pull_for_pass(k1, pass_id=1)
        rows["click"] += 3.0
        pri = shared_key_mask(k2, k1)
        store.push_from_pass_async(k1, rows, priority_select=pri,
                                   pass_id=1)
        # contains() drains the queue before asking the owners.
        assert store.contains(k1).all()
        back = store.pull_for_pass(k1)
        np.testing.assert_array_equal(back["click"], rows["click"])
        s = store.exchange_stats()
        assert s["exchange_busy_ms"] >= 0.0
        assert 0.0 <= store.exchange_overlap_frac() <= 1.0
        # reset() behind another in-flight async push: quiesce, then
        # wipe — no half-applied push survives on any server.
        rows["click"] += 1.0
        store.push_from_pass_async(k1, rows, priority_select=pri,
                                   pass_id=2)
        store.reset()
        assert store.num_features == 0
    finally:
        store.close()
        stop_shards(servers)


def test_one_boundary_pull_one_plan_per_pass():
    """Satellites 1+2: the boundary shared pull is ONE coalesced fanout
    (multihost/boundary_pulls) and the whole pull/push cycle of a pass
    derives its owner plan ONCE (multihost/plan_misses keyed by
    pass id)."""
    servers, eps = start_local_shards(2, CFG)
    store = MultiHostStore(CFG, eps)
    try:
        k1, k2 = _two_pass_keys(0.5, n=600, seed=44)
        before = (monitor.GLOBAL.get("multihost/plan_misses"),
                  monitor.GLOBAL.get("multihost/boundary_pulls"))
        rows = store.pull_for_pass(k1, pass_id=1)          # plan(k1)
        shared2 = shared_key_mask(k1, k2)
        store.pull_for_pass(k2, ~shared2, pass_id=2)       # plan(k2)
        store.pull_for_pass(k2, shared2, pass_id=2, barrier=False,
                            boundary=True)                 # cached
        store.push_from_pass_async(
            k1, rows, priority_select=shared_key_mask(k2, k1),
            pass_id=1)                                     # cached
        store.contains(k1)  # drain
        misses = monitor.GLOBAL.get("multihost/plan_misses") - before[0]
        bpulls = (monitor.GLOBAL.get("multihost/boundary_pulls")
                  - before[1])
        assert misses == 2, misses  # exactly one plan per pass
        assert bpulls == 1, bpulls  # one coalesced boundary fanout
    finally:
        store.close()
        stop_shards(servers)


# ---------------------------------------------------------------------------
# quantized dense-grad allreduce
# ---------------------------------------------------------------------------

def test_quantized_psum_f32_bit_identical_int8_bounded(devices8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddlebox_tpu.parallel.collective import quantized_psum

    mesh = Mesh(np.array(devices8), ("dp",))
    rng = np.random.default_rng(9)
    n = 8
    tree = {"w": rng.normal(size=(n, 37, 5)).astype(np.float32) * 2.0,
            "b": rng.normal(size=(n, 11)).astype(np.float32)}
    block = 16

    def run(wire):
        fn = jax.jit(jax.shard_map(
            lambda t: quantized_psum(t, "dp", wire_dtype=wire,
                                     block=block),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))
        out = fn(tree)
        return {k: np.asarray(v)[0] for k, v in out.items()}

    exact = {k: v.sum(axis=0) for k, v in tree.items()}
    f32 = run("f32")
    for k in tree:
        np.testing.assert_array_equal(f32[k], np.asarray(
            jax.jit(jax.shard_map(lambda t: jax.lax.psum(t, "dp"),
                                  mesh=mesh, in_specs=P("dp"),
                                  out_specs=P("dp")))(tree)[k])[0],
            err_msg=k)

    q = run("int8")
    # Error bound from the np twin codec: each element crosses the
    # int8 codec twice (per-rank scatter + reduced-segment gather), so
    # |err| <= sum_r bound_r + bound_seg, with bound = absmax/254 + eps
    # per block. Derive it on the SAME fused-flat layout the op uses.
    flat = np.concatenate([tree["w"].reshape(n, -1),
                           tree["b"].reshape(n, -1)], axis=1)
    pad = (-flat.shape[1]) % n
    flat = np.pad(flat, ((0, 0), (0, pad)))
    seg_w = flat.shape[1] // n
    got = np.concatenate([q["w"].ravel(), q["b"].ravel()])
    want = np.concatenate([exact["w"].ravel(), exact["b"].ravel()])
    err = np.abs(got - want)
    # Per-rank scatter error (exact, from the twin) ...
    scatter = np.zeros((n, seg_w), np.float32)
    for r in range(n):
        rows = flat[r].reshape(n, seg_w)
        qr, sr = quantize_blocked_np(rows, block)
        scatter += np.abs(
            dequantize_blocked_np(qr, sr, seg_w, block) - rows)
    # ... plus the gather-hop bound on the reduced segment: half a
    # quant step for rounding, plus one FULL step of allowance — the
    # device accumulates the dequantized segments in its own order and
    # with its own scatter error, so its requantization can land one
    # bucket away from the twin's half-step envelope.
    seg_sum = flat.reshape(n, n, seg_w).sum(axis=0)
    nb = -(-seg_w // block)
    amax = np.abs(np.pad(seg_sum, ((0, 0), (0, nb * block - seg_w)))
                  .reshape(n, nb, block)).max(-1)
    step = np.repeat(amax / 127.0 + 1e-6, block, axis=1)[:, :seg_w]
    total = (scatter + 1.5 * step).reshape(-1)[:err.size]
    assert (err <= total + 1e-5).all(), float((err - total).max())
    assert not np.array_equal(got, want)  # int8 wire really engaged


def test_trainer_int8_dense_sync_learns(tmp_path):
    """_build_step wiring: FLAGS_dense_allreduce_dtype=int8 trains and
    tracks the f32 loss curve within quantization tolerance."""
    from paddlebox_tpu.data import DataFeedConfig, Dataset, SlotConf
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    slots = ("u", "i")
    rng = np.random.default_rng(3)
    path = str(tmp_path / "part-0")
    with open(path, "w") as f:
        for _ in range(256):
            feats = {s: rng.integers(1, 120, rng.integers(1, 3))
                     for s in slots}
            click = np.mean([(int(v) % 5 == 0)
                             for vs in feats.values() for v in vs])
            label = int(rng.random() < 0.1 + 0.8 * click)
            toks = " ".join(f"{s}:{v}" for s, vs in feats.items()
                            for v in vs)
            f.write(f"{label} {toks}\n")

    def train(wire):
        prev = flagmod.get_flags(["dense_allreduce_dtype"])
        flagmod.set_flags({"dense_allreduce_dtype": wire})
        try:
            mesh = build_mesh(HybridTopology(dp=8))
            feed = DataFeedConfig(
                slots=tuple(SlotConf(s, avg_len=1.5) for s in slots),
                batch_size=32)
            t = CTRTrainer(
                DeepFM(slot_names=slots, emb_dim=8, hidden=(16,)),
                feed, TableConfig(dim=8, learning_rate=0.1),
                mesh=mesh, config=TrainerConfig(
                    dense_learning_rate=0.01,
                    auc_num_buckets=1 << 10))
            t.init(seed=0)
            ds = Dataset(feed, num_reader_threads=1)
            ds.set_filelist([path])
            ds.load_into_memory()
            return [t.train_pass(ds)["loss"] for _ in range(2)]
        finally:
            flagmod.set_flags(prev)

    lf = train("f32")
    li = train("int8")
    assert lf[1] < lf[0]  # learns
    for a, b in zip(lf, li):
        assert np.isclose(a, b, rtol=5e-2, atol=5e-2), (lf, li)
    assert monitor.GLOBAL.get_gauge("dense/allreduce_wire_bits") == 8


def test_dense_allreduce_dtype_validated(tmp_path):
    from paddlebox_tpu.data import DataFeedConfig, SlotConf
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig

    prev = flagmod.get_flags(["dense_allreduce_dtype"])
    flagmod.set_flags({"dense_allreduce_dtype": "fp4"})
    try:
        mesh = build_mesh(HybridTopology(dp=8))
        feed = DataFeedConfig(slots=(SlotConf("u", avg_len=1.5),),
                              batch_size=32)
        t = CTRTrainer(DeepFM(slot_names=("u",), emb_dim=8,
                              hidden=(16,)),
                       feed, TableConfig(dim=8, learning_rate=0.1),
                       mesh=mesh, config=TrainerConfig())
        t.init(seed=0)
        with pytest.raises(ValueError, match="dense_allreduce_dtype"):
            t._build_step()
    finally:
        flagmod.set_flags(prev)


# ---------------------------------------------------------------------------
# bounded-memory chunked COPY
# ---------------------------------------------------------------------------

def _seeded_cluster(world=2, n=3000, seed=51):
    servers, eps = start_local_shards(world, CFG)
    store = MultiHostStore(CFG, eps)
    keys = _rand_keys(n, seed=seed)
    rows = store.pull_for_pass(keys)
    rows["emb"] += 0.75
    rows["show"] += 2.0
    store.push_from_pass(keys, rows)
    store.close()
    return servers, eps, keys, rows


@pytest.mark.parametrize("chunk", [0, 277])
def test_chunked_copy_digest_identical(chunk):
    """The paged COPY walk moves exactly the whole-range rows: final
    contents are bit-identical, and with a chunk window the walk really
    pages (multihost/reshard_chunks > segment count)."""
    from paddlebox_tpu.multihost import rows_moved_minimal

    prev = flagmod.get_flags(["reshard_chunk_rows"])
    flagmod.set_flags({"reshard_chunk_rows": chunk})
    servers, eps, keys, rows = _seeded_cluster()
    s3, e3 = start_local_shards(3, CFG)
    joiner, jep = s3[2], e3[2]
    stop_shards(s3[:2])
    try:
        before = monitor.GLOBAL.get("multihost/reshard_chunks")
        rec = execute_reshard(eps, eps + [jep])
        t2 = ShardRangeTable.for_world(2)
        t3 = ShardRangeTable.for_world(3)
        assert rec["moved_rows"] == rows_moved_minimal(t2, t3, keys)
        chunks = monitor.GLOBAL.get("multihost/reshard_chunks") - before
        if chunk:
            assert chunks > rec["segments"], (chunks, rec["segments"])
        store = MultiHostStore(CFG, eps + [jep], ranges=t3)
        got = store.pull_for_pass(keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(got[f], rows[f], err_msg=f)
        store.close()
        for i, s in enumerate(servers + [joiner]):
            skeys, _ = s.store.key_stats()
            if skeys.size:
                assert (t3.owner_of(skeys) == i).all()
    finally:
        flagmod.set_flags(prev)
        stop_shards(servers + [joiner])


@pytest.mark.slow  # multi-process kill -9 drill; tier-1 keeps its 870 s window
def test_kill9_between_chunk_windows_recovers(tmp_path):
    """SIGKILL between two chunk windows of one COPY segment (some
    windows applied, source not yet dropped): recovery through the
    checkpoint chain is digest-identical to the seed — per-window
    idempotence carries the drill."""
    root = str(tmp_path / "ck")
    os.makedirs(root, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["FLAGS_reshard_chunk_rows"] = "400"
    worker = os.path.join(REPO, "tests", "multihost_reshard_worker.py")

    def run(mode, world=None, fault="", check=True):
        e = dict(env)
        if fault:
            e["FLAGS_fault_spec"] = fault
        cmd = [sys.executable, worker, root, mode]
        if world is not None:
            cmd.append(str(world))
        return subprocess.run(cmd, env=e, cwd=REPO, timeout=180,
                              check=check, capture_output=True)

    run("seed")
    with open(os.path.join(root, "digest_seed.json")) as f:
        seed = json.load(f)
    assert seed["rows"] > 0

    r = run("reshard", 3, fault="multihost/reshard_chunk:hit=2:kill",
            check=False)
    assert r.returncode in (-signal.SIGKILL, 137), (
        r.returncode, r.stdout[-500:], r.stderr[-500:])

    run("recover", 3)
    with open(os.path.join(root, "digest_recover.json")) as f:
        rec = json.load(f)
    assert rec == seed

    run("reshard", 3)
    with open(os.path.join(root, "digest_reshard.json")) as f:
        done = json.load(f)
    assert done == seed


def test_chunked_replica_snapshot_and_partial_sentinel():
    """Re-replication streams in chunk windows and commits atomically:
    the caught-up backup is digest-identical to the primary, and a
    snapshot that stops mid-stream leaves the sentinel epoch so the
    next catch-up re-snapshots instead of trusting a torn store."""
    import hashlib

    from paddlebox_tpu.multihost import ReplicaMap
    from paddlebox_tpu.multihost.shard_service import (_SNAPSHOT_PARTIAL,
                                                       ShardServer)

    def digest(fs):
        keys, _ = fs.key_stats()
        keys = np.sort(keys)
        vals = fs.pull_for_pass(keys)
        h = hashlib.sha256(keys.tobytes())
        for f in _FIELDS:
            h.update(np.ascontiguousarray(vals[f]).tobytes())
        return h.hexdigest()

    prev = flagmod.get_flags(["reshard_chunk_rows",
                              "multihost_journal_entries"])
    flagmod.set_flags({"reshard_chunk_rows": 200,
                       "multihost_journal_entries": 0})  # force snapshot
    servers, eps = start_local_shards(2, CFG, replicas=2)
    store = MultiHostStore(CFG, eps, replicas=2)
    fresh = None
    try:
        keys = _rand_keys(1500, seed=61)
        rows = store.pull_for_pass(keys)
        rows["w"] += 2.0
        store.push_from_pass(keys, rows)

        # Replace the backup of slot 0 with an empty server; the next
        # mutation triggers a CHUNKED snapshot catch-up.
        old = servers[1]
        old.kill()
        fresh = ShardServer(eps[1], 1, ShardRangeTable.for_world(2),
                            CFG)
        fresh.adopt_replica_map(ReplicaMap.ring(eps, 2))
        before = monitor.GLOBAL.get("multihost/replica_snapshot_chunks")
        rows["w"] += 1.0
        store.push_from_pass(keys, rows)
        chunks = (monitor.GLOBAL.get("multihost/replica_snapshot_chunks")
                  - before)
        assert chunks >= 2, chunks
        assert digest(servers[0]._slot_stores[0]) == digest(
            fresh._slot_stores[0])
        assert fresh._slot_epoch[0] == servers[0]._journals[0].epoch

        # Mid-stream crash simulation: a first chunk with no last chunk
        # leaves the sentinel epoch; the following sync re-snapshots.
        sub = keys[:100]
        fresh.handle_replica_snapshot(
            {"slot": 0, "seq": 999, "epoch": "next",
             "keys": sub, "values": store.pull_for_pass(sub),
             "unseen": np.zeros(sub.size, np.int32), "part": "first"})
        assert fresh._slot_epoch[0] == _SNAPSHOT_PARTIAL
        with pytest.raises(RuntimeError, match="SNAPSHOT_GAP"):
            servers[0].handle_replica_snapshot(
                {"slot": 1, "seq": 1, "epoch": "x", "keys": sub,
                 "values": store.pull_for_pass(sub),
                 "unseen": np.zeros(sub.size, np.int32), "part": "mid"})
        # The next mutation's forward hits the epoch mismatch, falls
        # into catch-up, sees the sentinel, and re-snapshots cleanly.
        rows["w"] += 1.0
        store.push_from_pass(keys, rows)
        assert digest(servers[0]._slot_stores[0]) == digest(
            fresh._slot_stores[0])
        assert fresh._slot_epoch[0] == servers[0]._journals[0].epoch
    finally:
        flagmod.set_flags(prev)
        store.close()
        stop_shards(servers + ([fresh] if fresh else []))
