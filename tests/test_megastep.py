"""K-step scanned device megastep (FLAGS_trainer_steps_per_dispatch).

The megastep exists to amortize host dispatch + sync out of the CTR hot
loop: K steps run inside ONE lax.scan'd XLA program, so the pass loop
pays one dispatch and at most one host sync per K steps. Capacity is
padding and the scan is a pure re-staging of the same per-step body —
so K=4 must be BIT-identical to K=1 on CPU: params, opt_state, AUC
state, and every per-step loss, including a non-multiple-of-K step
count (masked tail block) and a kstep dense-sync boundary that falls
mid-block. The dispatch/sync-count pins are the acceptance criterion:
O(steps) -> O(steps/K).
"""

import numpy as np
import pytest

import jax

from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.data import Dataset, DataFeedConfig, SlotConf
from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from paddlebox_tpu.train import CTRTrainer, TrainerConfig

SLOTS = ("u", "i", "c")


def _shard(path, n, seed=7, n_keys=150):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            feats = {s: rng.integers(1, n_keys, rng.integers(1, 3))
                     for s in SLOTS}
            click = np.mean([(int(v) % 5 == 0)
                             for vs in feats.values() for v in vs])
            label = int(rng.random() < 0.1 + 0.8 * click)
            toks = " ".join(f"{s}:{v}" for s, vs in feats.items()
                            for v in vs)
            f.write(f"{label} {toks}\n")
    return str(path)


@pytest.fixture(scope="module")
def shard_13(tmp_path_factory):
    # 13 batches of 32 -> K=4 gives blocks of 4,4,4,1: the tail block
    # exercises the masked partial-block path in every test below.
    return _shard(tmp_path_factory.mktemp("mega") / "part-0", 13 * 32)


def _dataset(p):
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.5) for s in SLOTS),
        batch_size=32)
    ds = Dataset(feed, num_reader_threads=1)
    ds.set_filelist([p])
    ds.load_into_memory()
    return feed, ds


def _run(p, k, cfg=None, passes=1, check_nan=False):
    """Train `passes` passes at steps_per_dispatch=k; returns (trainer,
    stats list, flat per-step losses across all passes)."""
    cfg = cfg or TrainerConfig(auc_num_buckets=1 << 10,
                               check_nan_inf=check_nan)
    feed, ds = _dataset(p)
    mesh = build_mesh(HybridTopology(dp=8))
    tr = CTRTrainer(DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)),
                    feed, TableConfig(dim=8, learning_rate=0.1),
                    mesh=mesh, config=cfg,
                    store_factory=lambda c: DeviceFeatureStore(
                        c, mesh=mesh))
    tr.init(seed=0)
    tr._debug_collect_losses = True
    prev = flagmod.flag("trainer_steps_per_dispatch")
    flagmod.set_flags({"trainer_steps_per_dispatch": k})
    try:
        stats = [tr.train_pass(ds) for _ in range(passes)]
    finally:
        flagmod.set_flags({"trainer_steps_per_dispatch": prev})
    losses = []
    for _base, blk, n_active in tr._debug_losses:
        arr = np.atleast_1d(np.asarray(blk))
        losses.extend(arr[:n_active].tolist())
    return tr, stats, np.asarray(losses)


def _assert_trees_bitwise(a, b, what):
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def test_k4_bitwise_matches_k1_with_partial_tail(shard_13):
    """Full-pass bit-parity at a non-multiple-of-K step count: params,
    opt_state, AUC state, and every per-step loss."""
    t1, s1, l1 = _run(shard_13, 1)
    t4, s4, l4 = _run(shard_13, 4)
    assert s1[0]["steps"] == 13 and s4[0]["steps"] == 13
    np.testing.assert_array_equal(l1, l4)
    _assert_trees_bitwise(t1.params, t4.params, "params")
    _assert_trees_bitwise(t1.opt_state, t4.opt_state, "opt_state")
    _assert_trees_bitwise(t1.auc_state, t4.auc_state, "auc_state")
    # Same tables too: the store's written-back rows must agree.
    np.testing.assert_allclose(s1[0]["auc"], s4[0]["auc"], rtol=0)


def test_k4_kstep_sync_boundary_mid_block(shard_13):
    """kstep local-SGD with interval 3 under K=4: the in-scan step
    counter must fire the pmean at global steps 3,6,9,12 — inside
    blocks, not at block edges — bit-identical to the host-computed
    per-step sync_flag."""
    cfg = dict(dense_optimizer="sgd", dense_learning_rate=0.05,
               auc_num_buckets=1 << 10, dense_sync_mode="kstep",
               dense_sync_interval=3)
    t1, _, l1 = _run(shard_13, 1, TrainerConfig(**cfg))
    t4, _, l4 = _run(shard_13, 4, TrainerConfig(**cfg))
    np.testing.assert_array_equal(l1, l4)
    _assert_trees_bitwise(t1.params, t4.params, "params (kstep)")
    _assert_trees_bitwise(t1.opt_state, t4.opt_state, "opt_state (kstep)")


def test_dispatch_and_sync_counts_drop_by_k(shard_13):
    """The acceptance pin: host dispatches AND host syncs drop from
    O(steps) to O(steps/K). check_nan_inf is ON so the sync counter
    counts the per-block finite-vector fetches."""
    _, s1, _ = _run(shard_13, 1, check_nan=True)
    _, s4, _ = _run(shard_13, 4, check_nan=True)
    assert s1[0]["steps_per_dispatch"] == 1
    assert s4[0]["steps_per_dispatch"] == 4
    assert s1[0]["dispatch_blocks"] == 13
    assert s4[0]["dispatch_blocks"] == 4        # ceil(13/4)
    assert s1[0]["host_syncs"] == 13            # one finite fetch/step
    assert s4[0]["host_syncs"] == 4             # one finite fetch/block
    # Without check_nan_inf the loop body never blocks at all.
    _, s0, _ = _run(shard_13, 4)
    assert s0[0]["host_syncs"] == 0


def test_check_nan_inf_reports_global_step_index(shard_13):
    """check_nan_inf raises from the per-block finite vector with the
    OFFENDING global step, not the block index."""
    tr, _, _ = _run(shard_13, 4, check_nan=True)  # warm + build mega fn
    orig = tr._mega_fn

    def poisoned(*args):
        out = orig(*args)
        tables, params, opt_state, auc, losses, overflows, finites = out
        # Poison in-block step 1 of the SECOND block -> global step 6
        # (1-based), leaving the first block clean.
        if int(np.asarray(args[4])) == 4:  # step0 of block 1
            import jax.numpy as jnp
            losses = losses.at[1].set(jnp.nan)
            finites = finites.at[1].set(False)
        return (tables, params, opt_state, auc, losses, overflows,
                finites)

    tr._mega_fn = poisoned
    feed, ds = _dataset(shard_13)
    prev = flagmod.flag("trainer_steps_per_dispatch")
    flagmod.set_flags({"trainer_steps_per_dispatch": 4})
    try:
        with pytest.raises(FloatingPointError, match="step 6"):
            tr.train_pass(ds)
    finally:
        flagmod.set_flags({"trainer_steps_per_dispatch": prev})
        tr._mega_fn = orig


def test_async_mode_forces_k1(shard_13):
    cfg = TrainerConfig(dense_learning_rate=3e-3,
                        auc_num_buckets=1 << 10, dense_sync_mode="async")
    tr, stats, _ = _run(shard_13, 4, cfg)
    try:
        assert stats[0]["steps_per_dispatch"] == 1
        assert stats[0]["dispatch_blocks"] == stats[0]["steps"] == 13
    finally:
        tr._async_dense.stop()


def test_eval_pass_megastep_matches_k1(shard_13):
    """Eval megastep: AUC/loss identical between K=1 and K=4 (read-only
    scan, masked tail)."""
    feed, ds = _dataset(shard_13)
    mesh = build_mesh(HybridTopology(dp=8))

    def build():
        tr = CTRTrainer(DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)),
                        feed, TableConfig(dim=8, learning_rate=0.1),
                        mesh=mesh,
                        config=TrainerConfig(auc_num_buckets=1 << 10),
                        store_factory=lambda c: DeviceFeatureStore(
                            c, mesh=mesh))
        tr.init(seed=0)
        return tr

    prev = flagmod.flag("trainer_steps_per_dispatch")
    try:
        flagmod.set_flags({"trainer_steps_per_dispatch": 1})
        e1 = build().eval_pass(ds)
        flagmod.set_flags({"trainer_steps_per_dispatch": 4})
        e4 = build().eval_pass(ds)
    finally:
        flagmod.set_flags({"trainer_steps_per_dispatch": prev})
    assert e1["steps"] == e4["steps"] == 13
    np.testing.assert_array_equal(e1["auc"], e4["auc"])
    np.testing.assert_allclose(e1["loss"], e4["loss"], rtol=1e-6)


@pytest.mark.slow  # 7 s; auto-capacity is off by default and test_auto_capacity.py stays tier-1; tier-1 keeps its 870 s window (PR 21)
def test_auto_capacity_ratchet_with_megastep(tmp_path):
    """Auto-capacity under K=4: pass 1 measures caps from the first
    STACKED block (before the scanned fn is built); a second pass over
    a hotter key mix may only ratchet caps UP (rebuild) — and results
    stay identical to the K=1 auto-capacity run throughout."""
    # Duplicate-heavy first day, wider key range second day.
    p_small = _shard(tmp_path / "d0", 8 * 32, seed=1, n_keys=12)
    p_big = _shard(tmp_path / "d1", 8 * 32, seed=2, n_keys=400)

    def run(k):
        feed = DataFeedConfig(
            slots=tuple(SlotConf(s, avg_len=1.5) for s in SLOTS),
            batch_size=32)
        mesh = build_mesh(HybridTopology(dp=8))
        tr = CTRTrainer(DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)),
                        feed, TableConfig(dim=8, learning_rate=0.1),
                        mesh=mesh,
                        config=TrainerConfig(auc_num_buckets=1 << 10),
                        store_factory=lambda c: DeviceFeatureStore(
                            c, mesh=mesh))
        tr.init(seed=0)
        flagmod.set_flags({"trainer_steps_per_dispatch": k,
                           "embedding_auto_capacity": True})
        caps = []
        stats = []
        try:
            for p in (p_small, p_big):
                ds = Dataset(feed, num_reader_threads=1)
                ds.set_filelist([p])
                ds.load_into_memory()
                stats.append(tr.train_pass(ds))
                caps.append(tr._step_caps)
        finally:
            flagmod.set_flags({"trainer_steps_per_dispatch": 1,
                               "embedding_auto_capacity": False})
        return tr, stats, caps

    t1, s1, caps1 = run(1)
    t4, s4, caps4 = run(4)
    for s in s1 + s4:
        assert s["lookup_overflow"] == 0
    assert caps4[0] is not None
    # Ratchet semantics: caps never shrink across passes.
    for c0, c1 in zip(caps4[0], caps4[1]):
        if c0 is not None and c1 is not None:
            assert c1 >= c0
    # Capacity is padding, never math: K=4 matches K=1 even while the
    # two measured different caps from their first block vs first batch.
    for a, b in zip(s1, s4):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-6)
        np.testing.assert_allclose(a["auc"], b["auc"], rtol=1e-6)
    _assert_trees_bitwise(t1.params, t4.params, "params (auto-cap)")
