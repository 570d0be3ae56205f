"""Multi-host embedding exchange tier (MULTIHOST.md).

Pins, tier-1 (CPU, loopback sockets — the wire is real, the hosts are
in-process):

- hash-range placement: partition coverage, plan_moves minimality
  (segments cover EXACTLY the changed-owner keys, 2→3→2 returns home);
- int8 per-block codec: np/jnp twins bit-identical, round-trip error
  bound, exact zeros;
- the host-sharded parameter service: 2-host MultiHostStore is
  BIT-identical to a flat FeatureStore on the f32 wire (pulls, pushes,
  unseen-key init, num_features), int8 wire within tolerance with the
  byte accounting shrinking;
- a full 2-host training day (DayRunner + CTRTrainer backed by the
  shard tier) bit-identical to the single-host run — losses AND final
  store contents;
- elastic reshard: live 2→3→2 mid-day through the pass-boundary hook,
  final state bit-identical to an unresized run at the same data
  order; per-row move counts equal to the minimal-transfer bound; a
  failed reshard rolls back via recovery_chain and retries cleanly;
  kill -9 mid-move recovers with no lost/double-applied rows
  (subprocess drill, tests/multihost_reshard_worker.py);
- the elastic rank table carries per-host shard endpoints (meta) end
  to end through two live ElasticManagers.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from paddlebox_tpu.core import faults
from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.embedding.store import _FIELDS, FeatureStore
from paddlebox_tpu.embedding.table import TableConfig
from paddlebox_tpu.multihost import (MultiHostStore, ShardRangeTable,
                                     execute_reshard, mix_keys, plan_moves,
                                     rows_moved_minimal, start_local_shards,
                                     stop_shards)
from paddlebox_tpu.multihost.keyrange import range_bounds
from paddlebox_tpu.multihost.quant import (dequantize_blocked,
                                           dequantize_blocked_np,
                                           quantize_blocked,
                                           quantize_blocked_np)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TableConfig(name="emb", dim=8, learning_rate=0.1)


def _rand_keys(n, seed=0, hi=1 << 50):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, hi, size=n + 64, dtype=np.uint64))
    assert keys.size >= n  # collisions are ~impossible at this range
    return keys[:n]


# ---------------------------------------------------------------------------
# keyrange
# ---------------------------------------------------------------------------

def test_range_partition_covers_and_balances():
    for world in (1, 2, 3, 7):
        b = range_bounds(world)
        assert b[0] == 0 and b[-1] == 1 << 64
        assert all(b[i] < b[i + 1] for i in range(world))
        t = ShardRangeTable.for_world(world)
        keys = _rand_keys(20000, seed=1)
        owner = t.owner_of(keys)
        assert owner.min() >= 0 and owner.max() < world
        if world > 1:
            counts = np.bincount(owner, minlength=world)
            # The mix spreads uniformly: no shard takes > 2x its share.
            assert counts.max() < 2 * keys.size / world


def test_owner_matches_mask_in_range():
    t = ShardRangeTable.for_world(3)
    keys = _rand_keys(5000, seed=2)
    owner = t.owner_of(keys)
    for h in range(3):
        lo, hi = t.range_of(h)
        np.testing.assert_array_equal(t.mask_in_range(keys, lo, hi),
                                      owner == h)


def test_plan_moves_is_minimal_and_exact():
    keys = _rand_keys(30000, seed=3)
    for w_old, w_new in ((2, 3), (3, 2), (2, 5), (4, 3), (1, 4)):
        old = ShardRangeTable.for_world(w_old)
        new = ShardRangeTable.for_world(w_new)
        plan = plan_moves(old, new)
        o, n = old.owner_of(keys), new.owner_of(keys)
        covered = np.zeros(keys.size, bool)
        for seg in plan:
            m = old.mask_in_range(keys, seg.lo, seg.hi)
            assert not (covered & m).any(), "overlapping segments"
            covered |= m
            # Every key in the segment really moves src -> dst.
            assert (o[m] == seg.src).all() and (n[m] == seg.dst).all()
        # Exactly the changed-owner keys are covered: minimal transfer.
        np.testing.assert_array_equal(covered, o != n)
        assert int(covered.sum()) == rows_moved_minimal(old, new, keys)


def test_same_world_plan_is_empty_and_dict_roundtrip():
    t = ShardRangeTable.for_world(4)
    assert plan_moves(t, ShardRangeTable.for_world(4)) == []
    assert ShardRangeTable.from_dict(t.to_dict()) == t
    assert mix_keys(np.array([5], np.uint64)).dtype == np.uint64


# ---------------------------------------------------------------------------
# int8 per-block codec
# ---------------------------------------------------------------------------

def test_quant_np_jnp_twins_bit_identical():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(33, 21)).astype(np.float32) * 3.0
    for block in (4, 8, 21, 128):
        qn, sn = quantize_blocked_np(x, block)
        qj, sj = quantize_blocked(x, block)
        np.testing.assert_array_equal(qn, np.asarray(qj),
                                      err_msg=f"block {block}")
        np.testing.assert_array_equal(sn, np.asarray(sj))
        dn = dequantize_blocked_np(qn, sn, x.shape[1], block)
        dj = np.asarray(dequantize_blocked(qj, sj, x.shape[1], block))
        np.testing.assert_array_equal(dn, dj)


def test_quant_roundtrip_error_bound_and_zeros():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(64, 24)).astype(np.float32) * 10.0
    x[7] = 0.0  # all-zero row must round-trip EXACTLY (scale 1)
    for block in (6, 24):
        q, s = quantize_blocked_np(x, block)
        assert q.shape == x.shape  # unpadded wire
        d = dequantize_blocked_np(q, s, x.shape[1], block)
        nb = -(-x.shape[1] // block)
        amax = np.abs(
            np.pad(x, ((0, 0), (0, nb * block - x.shape[1])))
            .reshape(64, nb, block)).max(-1)
        bound = np.repeat(amax / 254.0 + 1e-6, block, axis=1)[:, :24]
        assert (np.abs(d - x) <= bound).all()
        np.testing.assert_array_equal(d[7], 0.0)


# ---------------------------------------------------------------------------
# host-sharded parameter service
# ---------------------------------------------------------------------------

@pytest.fixture
def cluster2():
    servers, eps = start_local_shards(2, CFG)
    yield servers, eps
    stop_shards(servers)


def test_two_host_store_bit_identical_to_flat(cluster2):
    servers, eps = cluster2
    store = MultiHostStore(CFG, eps)
    flat = FeatureStore(CFG, seed=0)
    keys = _rand_keys(3000, seed=6)
    a, b = store.pull_for_pass(keys), flat.pull_for_pass(keys)
    for f in _FIELDS:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)
    a["emb"] += 0.25
    a["show"] += 1.0
    store.push_from_pass(keys, a)
    flat.push_from_pass(keys, a)
    assert store.num_features == flat.num_features == keys.size
    # Second pull serves the written rows identically (and the plan
    # cache reused the owner argsort between push and this pull).
    sub = keys[::3]
    a2, b2 = store.pull_for_pass(sub), flat.pull_for_pass(sub)
    for f in _FIELDS:
        np.testing.assert_array_equal(a2[f], b2[f], err_msg=f)


def test_int8_dcn_wire_tolerance_and_bytes(cluster2):
    from paddlebox_tpu.core import monitor
    servers, eps = cluster2
    store = MultiHostStore(CFG, eps)
    keys = _rand_keys(2000, seed=7)
    rows = store.pull_for_pass(keys)
    rng = np.random.default_rng(8)
    rows["emb"] = rng.normal(size=rows["emb"].shape).astype(np.float32)
    store.push_from_pass(keys, rows)

    def pull_bytes():
        before = monitor.GLOBAL.get("multihost/pull_bytes")
        out = store.pull_for_pass(keys)
        return out, monitor.GLOBAL.get("multihost/pull_bytes") - before

    prev = flagmod.flag("multihost_wire_dtype")
    try:
        flagmod.set_flags({"multihost_wire_dtype": "f32"})
        exact, b_f32 = pull_bytes()
        np.testing.assert_array_equal(exact["emb"], rows["emb"])
        flagmod.set_flags({"multihost_wire_dtype": "int8"})
        quant, b_int8 = pull_bytes()
        flagmod.set_flags({"multihost_wire_dtype": "f16"})
        half, b_f16 = pull_bytes()
    finally:
        flagmod.set_flags({"multihost_wire_dtype": prev})
    # Tolerance: per-block absmax/254; these are ~N(0,1) values.
    np.testing.assert_allclose(quant["emb"], rows["emb"],
                               rtol=4e-2, atol=4e-2)
    np.testing.assert_allclose(half["emb"], rows["emb"],
                               rtol=1e-3, atol=1e-3)
    assert not np.array_equal(quant["emb"], rows["emb"])
    # Non-emb fields stay exact on every wire.
    for f in ("w", "emb_state", "w_state", "show", "click"):
        np.testing.assert_array_equal(quant[f], rows[f], err_msg=f)
    # Byte accounting: int8 < f16 < f32 on the emb payload share.
    assert b_int8 < b_f16 < b_f32


def test_stale_range_table_fails_loudly(cluster2):
    servers, eps = cluster2
    # A client that thinks the world is 3 routes keys the 2-server
    # cluster does not own — the ownership check must name the drift,
    # not serve garbage.
    store = MultiHostStore(CFG, [eps[0], eps[1], eps[0]],
                           ranges=ShardRangeTable.for_world(3))
    keys = _rand_keys(500, seed=9)
    with pytest.raises(RuntimeError, match="not owned"):
        store.pull_for_pass(keys)


def test_checkpoint_world_agnostic_reload(cluster2, tmp_path):
    """A checkpoint written at world 2 reloads bit-identical into
    world 3 and world 1 (hostshard files are range-filtered on load) —
    the property every reshard rollback and elastic recovery rides."""
    servers, eps = cluster2
    store = MultiHostStore(CFG, eps)
    keys = _rand_keys(2500, seed=10)
    rows = store.pull_for_pass(keys)
    rows["click"] += 2.0
    store.push_from_pass(keys, rows)
    path = str(tmp_path / "ck")
    store.save_base(path)
    for world in (3, 1):
        s2, e2 = start_local_shards(world, CFG)
        try:
            other = MultiHostStore(CFG, e2)
            other.load(path, "base")
            assert other.num_features == keys.size
            got = other.pull_for_pass(keys)
            for f in _FIELDS:
                np.testing.assert_array_equal(got[f], rows[f],
                                              err_msg=f)
        finally:
            stop_shards(s2)


# ---------------------------------------------------------------------------
# live reshard
# ---------------------------------------------------------------------------

def _start_joiner(world, index):
    """One server of a world-`world` partition (a joining host)."""
    servers, eps = start_local_shards(world, CFG)
    for j, s in enumerate(servers):
        if j != index:
            s.stop()
    return servers[index], eps[index]


def test_reshard_2_3_2_minimal_moves_and_parity(cluster2):
    servers, eps = cluster2
    store = MultiHostStore(CFG, eps)
    keys = _rand_keys(4000, seed=11)
    rows = store.pull_for_pass(keys)
    rows["emb"] += 0.5
    store.push_from_pass(keys, rows)

    t2, t3 = ShardRangeTable.for_world(2), ShardRangeTable.for_world(3)
    joiner, jep = _start_joiner(3, 2)
    try:
        rec = execute_reshard(eps, eps + [jep])
        # Per-row move counts match the minimal-transfer plan exactly.
        assert rec["moved_rows"] == rows_moved_minimal(t2, t3, keys)
        assert rec["moved_rows"] == sum(rec["segment_rows"])
        assert rec["new_world"] == 3
        store.set_topology(eps + [jep], t3)
        got = store.pull_for_pass(keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(got[f], rows[f], err_msg=f)
        # Every server now holds ONLY its world-3 range.
        for i, s in enumerate(servers + [joiner]):
            skeys, _ = s.store.key_stats()
            if skeys.size:
                assert (t3.owner_of(skeys) == i).all()
        # ...and back: 3 -> 2 drains the joiner completely.
        rec2 = execute_reshard(eps + [jep], eps)
        assert rec2["moved_rows"] == rows_moved_minimal(t3, t2, keys)
        store.set_topology(eps, t2)
        got2 = store.pull_for_pass(keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(got2[f], rows[f], err_msg=f)
        jk, _ = joiner.store.key_stats()
        assert jk.size == 0
    finally:
        joiner.stop()


def test_reshard_failure_rolls_back_and_retries(cluster2, tmp_path):
    """A transient fault mid-move: the controller rolls the shard tier
    back through recovery_chain() (published state), reports the resize
    not-applied, and the retry at the next boundary lands it."""
    from paddlebox_tpu.checkpoint.protocol import CheckpointProtocol
    from paddlebox_tpu.launch.elastic import RankTable
    from paddlebox_tpu.multihost.reshard import ElasticReshardController

    servers, eps = cluster2
    store = MultiHostStore(CFG, eps)
    keys = _rand_keys(2000, seed=12)
    rows = store.pull_for_pass(keys)
    rows["w"] += 3.0
    store.push_from_pass(keys, rows)
    ckpt = CheckpointProtocol(str(tmp_path / "out"))
    store.save_delta(ckpt.model_dir("20260801", 1))
    ckpt.publish("20260801", 1)

    joiner, jep = _start_joiner(3, 2)
    tables = {"t": RankTable(generation=0, hosts=["a", "b"])}
    ctl = ElasticReshardController(store, ckpt,
                                   table_fn=lambda: tables["t"])
    try:
        assert ctl.maybe_apply("20260801", 1) is None  # anchors gen 0
        meta = {"a": {"shard_endpoint": eps[0]},
                "b": {"shard_endpoint": eps[1]},
                "c": {"shard_endpoint": jep}}
        tables["t"] = RankTable(generation=1, hosts=["a", "b", "c"],
                                meta=meta)
        faults.configure("multihost/reshard_move:hit=2:raise=IOError")
        try:
            assert ctl.maybe_apply("20260801", 2) is None  # failed
        finally:
            faults.clear()
        # Rolled back: still world 2, contents intact.
        assert store.world == 2
        got = store.pull_for_pass(keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(got[f], rows[f], err_msg=f)
        # Next boundary retries the SAME pending generation and lands.
        rec = ctl.maybe_apply("20260801", 3)
        assert rec is not None and rec["new_world"] == 3
        assert store.world == 3
        got = store.pull_for_pass(keys)
        for f in _FIELDS:
            np.testing.assert_array_equal(got[f], rows[f], err_msg=f)
    finally:
        joiner.stop()


@pytest.mark.slow  # 12 s multi-process kill -9 drill; tier-1 keeps its 870 s window (PR 21)
def test_kill9_mid_reshard_recovers_via_recovery_chain(tmp_path):
    """Subprocess drill: SIGKILL inside the reshard COPY phase, then a
    fresh cluster recovers through recovery_chain() — the content
    digest (layout-independent) must equal the seeded state: no lost
    rows, no double-applied rows."""
    root = str(tmp_path / "ck")
    os.makedirs(root, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
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

    # Kill -9 on the SECOND move segment: segment 1's rows are already
    # applied to their dest but not yet dropped from their source — the
    # worst crash window for double-apply.
    r = run("reshard", 3, fault="multihost/reshard_move:hit=2:kill",
            check=False)
    assert r.returncode in (-signal.SIGKILL, 137), (
        r.returncode, r.stdout[-500:], r.stderr[-500:])
    assert not os.path.exists(os.path.join(root, "digest_reshard.json"))

    # Recover into the NEW layout (the elastic restart path): reset +
    # recovery_chain reload, range-filtered per server.
    run("recover", 3)
    with open(os.path.join(root, "digest_recover.json")) as f:
        rec = json.load(f)
    assert rec == seed

    # And a clean reshard replay from the same chain also matches.
    run("reshard", 3)
    with open(os.path.join(root, "digest_reshard.json")) as f:
        done = json.load(f)
    assert done == seed


# ---------------------------------------------------------------------------
# elastic rank-table meta plumbing
# ---------------------------------------------------------------------------

def test_elastic_meta_carries_shard_endpoints(tmp_path):
    from paddlebox_tpu.launch.elastic import ElasticManager
    from paddlebox_tpu.multihost.reshard import ElasticReshardController

    root = str(tmp_path / "el")
    mgrs = [ElasticManager(root, f"host{r}", heartbeat_interval=0.05,
                           timeout=1.0, settle=0.1,
                           meta={"shard_endpoint": f"127.0.0.1:90{r}0"})
            for r in range(2)]
    try:
        for m in mgrs:
            m.start()
        t = mgrs[0].wait_for_quorum(timeout=20)
        deadline = time.time() + 20
        while time.time() < deadline:
            t = mgrs[1].current_table() or t
            eps = ElasticReshardController.endpoints_of(t)
            if t.world_size == 2 and eps is not None:
                break
            time.sleep(0.05)
        assert t.world_size == 2
        assert eps == ["127.0.0.1:9000", "127.0.0.1:9010"]
    finally:
        for m in mgrs:
            m.stop()
