"""DayRunner over the DEVICE-resident store tier: the pipelined day loop
(async feed_pass thread racing end_pass on the store lock) must produce
the same checkpoint protocol artifacts and keep training sane — the
production configuration (GPU-resident PS thesis) end to end."""

import os

import numpy as np
import pytest

from paddlebox_tpu.data import DataFeedConfig, SlotConf
from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from paddlebox_tpu.train import CTRTrainer, TrainerConfig
from paddlebox_tpu.train.day_runner import DayRunner

from tests.test_day_runner import SLOTS, _write_day


def _make_runner(data_root, out_root, mesh):
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.5) for s in SLOTS),
        batch_size=32)
    trainer = CTRTrainer(
        DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)), feed,
        TableConfig(name="emb", dim=8, learning_rate=0.1), mesh=mesh,
        config=TrainerConfig(dense_learning_rate=3e-3,
                             auc_num_buckets=1 << 10),
        store_factory=lambda cfg: DeviceFeatureStore(cfg, mesh=mesh))
    trainer.init(seed=0)
    return trainer, DayRunner(
        trainer, feed, out_root, data_root=data_root,
        split_interval=60, split_per_pass=1, hours=[0, 1, 2],
        num_reader_threads=2, pipeline_passes=True, save_xbox=True)


def test_pipelined_day_over_device_store(tmp_path):
    data_root = str(tmp_path / "data")
    out_root = str(tmp_path / "out")
    _write_day(data_root, "20260701", [0, 1, 2])
    mesh = build_mesh(HybridTopology(dp=8))
    trainer, runner = _make_runner(data_root, out_root, mesh)
    out = runner.run_days(["20260701"], resume=False)
    assert len(out["20260701"]) == 3
    assert trainer.engine.store.num_features > 0
    # Checkpoint protocol artifacts: per-pass deltas + xbox, day base in
    # the pass-0 dir (reference day/pass-addressed layout).
    day_dir = os.path.join(out_root, "20260701")
    recs = runner.ckpt.records()
    assert [(r.day, r.pass_id) for r in recs] == \
        [("20260701", 1), ("20260701", 2), ("20260701", 3),
         ("20260701", 0)]
    assert os.path.exists(os.path.join(day_dir, "0", "emb.base.npz"))
    assert os.path.exists(os.path.join(day_dir, "2", "emb.delta.npz"))
    assert os.path.exists(os.path.join(day_dir, "1", "emb.xbox.npz"))

    # The day base reloads into a FRESH device store with equal contents.
    mesh2 = build_mesh(HybridTopology(dp=8))
    fresh = DeviceFeatureStore(TableConfig(name="emb", dim=8,
                                           learning_rate=0.1), mesh=mesh2)
    fresh.load(os.path.join(day_dir, "0"), "base")
    assert fresh.num_features == trainer.engine.store.num_features
    keys = np.sort(
        trainer.engine.store._index.keys_by_row())
    a = trainer.engine.store.pull_for_pass(keys)
    b = fresh.pull_for_pass(keys)
    np.testing.assert_allclose(b["emb"], a["emb"], atol=1e-7)


def test_eval_pass_does_not_grow_device_store(tmp_path):
    data_root = str(tmp_path / "data")
    _write_day(data_root, "20260701", [0])
    mesh = build_mesh(HybridTopology(dp=8))
    trainer, _ = _make_runner(data_root, str(tmp_path / "out"), mesh)
    from paddlebox_tpu.data.dataset import Dataset
    feed = trainer.feed_config
    ds = Dataset(feed, num_reader_threads=1)
    ds.set_filelist([os.path.join(data_root, "20260701", "00",
                                  "part-00000")])
    ds.load_into_memory()
    trainer.train_pass(ds)
    n_after_train = trainer.engine.store.num_features
    # Eval over data containing UNSEEN keys must not insert them.
    _write_day(data_root, "20260702", [0], seed0=999)
    ds2 = Dataset(feed, num_reader_threads=1)
    ds2.set_filelist([os.path.join(data_root, "20260702", "00",
                                   "part-00000")])
    ds2.load_into_memory()
    stats = trainer.eval_pass(ds2)
    assert np.isfinite(stats["loss"])
    assert trainer.engine.store.num_features == n_after_train


# -- keys first: the engine is fed before the shuffle (PR 29) ----------------

def _keys_after_the_shuffle(self, day, pass_id, files, *, feed):
    """The order the day loop had: load, shuffle, then keys and feed."""
    import zlib

    from paddlebox_tpu.data.dataset import Dataset
    ds = Dataset(self.feed_config,
                 num_reader_threads=self.num_reader_threads)
    ds.set_filelist(files)
    ds.load_into_memory()
    ds.local_shuffle(seed=zlib.crc32(f"{day}:{pass_id}".encode()))
    if feed:
        self._feed_keys(ds, day, pass_id)
    return ds


def _day_with_everything_recorded(tmp_path, name, pipeline, old_order,
                                  monkeypatch):
    import jax
    data_root = str(tmp_path / "data")
    if not os.path.isdir(data_root):
        _write_day(data_root, "20260701", [0, 1, 2])
    trainer, runner = _make_runner(data_root, str(tmp_path / name),
                                   build_mesh(HybridTopology(dp=8)))
    runner.pipeline_passes = pipeline
    if old_order:
        monkeypatch.setattr(DayRunner, "_load_dataset",
                            _keys_after_the_shuffle)
    fed, rows = [], []
    feed_pass = trainer.engine.feed_pass
    train_pass = trainer.train_pass

    def spy_feed(keys, **kw):
        fed.append([np.array(k) for k in keys])
        return feed_pass(keys, **kw)

    def spy_train(ds, **kw):
        merged = ds._merge()
        rows.append({s: (merged.sparse_ids[s].copy(),
                         merged.sparse_offsets[s].copy()) for s in SLOTS})
        return train_pass(ds, **kw)
    monkeypatch.setattr(trainer.engine, "feed_pass", spy_feed)
    monkeypatch.setattr(trainer, "train_pass", spy_train)
    stats = runner.train_day("20260701")
    monkeypatch.undo()
    store = trainer.engine.store
    keys = np.sort(store.key_stats()[0])
    return {"stats": stats, "fed": fed, "rows": rows, "keys": keys,
            "vals": store.pull_for_pass(keys),
            "dense": [np.asarray(x) for x in jax.tree.leaves(
                (trainer.params, trainer.opt_state))]}


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "unpipelined"])
def test_keys_first_day_is_bit_identical_to_keys_after_shuffle(
        tmp_path, monkeypatch, pipeline):
    new = _day_with_everything_recorded(tmp_path, "new", pipeline, False,
                                        monkeypatch)
    old = _day_with_everything_recorded(tmp_path, "old", pipeline, True,
                                        monkeypatch)
    assert len(new["stats"]) == len(old["stats"]) == 3
    for a, b in zip(new["stats"], old["stats"]):
        assert (a["steps"], a["loss"], a["auc"]) == \
            (b["steps"], b["loss"], b["auc"])
    # each pass's one file is one chunk: a run a slot, nothing to fold
    assert [(s["pass_report"]["ingest_key_runs"],
             s["pass_report"]["ingest_key_runs_merged_in_load"])
            for s in new["stats"]] == [(len(SLOTS), 0)] * 3
    # the same key set to the engine, the same rows in the same order
    assert len(new["fed"]) == len(old["fed"]) == 3
    for a, b in zip(new["fed"], old["fed"]):
        for ka, kb in zip(a, b):
            np.testing.assert_array_equal(ka, kb)
    for a, b in zip(new["rows"], old["rows"]):
        for s in SLOTS:
            np.testing.assert_array_equal(a[s][0], b[s][0])
            np.testing.assert_array_equal(a[s][1], b[s][1])
    np.testing.assert_array_equal(new["keys"], old["keys"])
    for f in old["vals"]:
        np.testing.assert_array_equal(new["vals"][f], old["vals"][f])
    for a, b in zip(new["dense"], old["dense"]):
        np.testing.assert_array_equal(a, b)


def test_shuffle_fault_in_a_preload_leaves_no_pending_build(tmp_path):
    """A preload that fails in its shuffle has already fed the engine:
    the error leaves through the join and the build is cancelled."""
    from paddlebox_tpu.core import faults
    data_root = str(tmp_path / "data")
    _write_day(data_root, "20260701", [0, 1, 2])
    trainer, runner = _make_runner(data_root, str(tmp_path / "out"),
                                   build_mesh(HybridTopology(dp=8)))
    faults.configure("day_runner/shuffle:hit=2:raise=IOError")
    try:
        with pytest.raises(OSError, match="day_runner/shuffle"):
            runner.train_day("20260701")
    finally:
        faults.clear()
    assert runner._inflight_preload is None
    for g in trainer.engine.groups:
        assert g.engine._pending is None
        assert g.engine._pending_sem.acquire(blocking=False)
        g.engine._pending_sem.release()
