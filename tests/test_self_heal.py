"""Self-healing day loop drills: deterministic faults injected mid-day
must cost ONE pass retry — with checkpoint rollback making the retried
day BIT-identical to an unfailed run — the stall watchdog must abort and
retry instead of hanging, and a kill -9 at publish/save sites must
resume through ``recover()`` with no double-applied deltas.

Role of the reference recovery story being proven: donefile
resume (fleet_util.py) + elastic restart's pass-exactly-once semantics,
now exercised by deliberate breakage instead of claimed."""

import importlib.util
import os
import time

import numpy as np
import pytest

from paddlebox_tpu.core import faults, flags as flagmod, monitor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "crash_drill", os.path.join(REPO, "tools", "crash_drill.py"))
crash_drill = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(crash_drill)

DAY = "20260728"
SLOTS = ("user", "item")
HOURS = [0, 1]


@pytest.fixture(autouse=True)
def _clean_fault_state():
    keep = ("fault_spec", "pass_max_retries", "pass_retry_backoff_s",
            "pass_retry_backoff_max_s", "stall_timeout_s")
    old = {k: flagmod.flag(k) for k in keep}
    faults.clear()
    flagmod.set_flags({"pass_retry_backoff_s": 0.01})
    try:
        yield
    finally:
        faults.clear()
        flagmod.set_flags(old)


def _write_day(root):
    crash_drill.write_day(root, DAY, HOURS, rows_per_split=96)


def _make_runner(data, out, *, device_store=False):
    from paddlebox_tpu.data import DataFeedConfig, SlotConf
    from paddlebox_tpu.embedding import TableConfig
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import HybridTopology, build_mesh
    from paddlebox_tpu.train import CTRTrainer, TrainerConfig
    from paddlebox_tpu.train.day_runner import DayRunner

    mesh = build_mesh(HybridTopology(dp=8))
    feed = DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.5) for s in SLOTS),
        batch_size=32)
    store_factory = None
    if device_store:
        from paddlebox_tpu.embedding.device_store import DeviceFeatureStore
        store_factory = lambda c: DeviceFeatureStore(c, mesh=mesh)  # noqa
    trainer = CTRTrainer(
        DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)), feed,
        TableConfig(name="emb", dim=8, learning_rate=0.1), mesh=mesh,
        config=TrainerConfig(dense_learning_rate=3e-3,
                             auc_num_buckets=1 << 10),
        store_factory=store_factory)
    trainer.init(seed=0)
    return DayRunner(trainer, feed, out, data_root=data,
                     split_interval=60, split_per_pass=1,
                     hours=HOURS, num_reader_threads=2)


def _final_state(runner):
    import jax
    tr = runner.trainer
    store = tr.engine.store
    keys = np.sort(store.key_stats()[0])
    vals = store.pull_for_pass(keys)
    return {
        "params": [np.asarray(x).copy()
                   for x in jax.tree.leaves(tr.params)],
        "opt": [np.asarray(x).copy()
                for x in jax.tree.leaves(tr.opt_state)],
        "keys": keys,
        "vals": {f: np.asarray(v).copy() for f, v in vals.items()},
    }


def _assert_state_equal(got, want):
    for a, b in zip(got["params"], want["params"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got["opt"], want["opt"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got["keys"], want["keys"])
    for f in want["vals"]:
        np.testing.assert_array_equal(got["vals"][f], want["vals"][f])


@pytest.fixture(scope="module")
def day_data(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("heal_data"))
    _write_day(d)
    return d


@pytest.fixture(scope="module")
def reference(day_data, tmp_path_factory):
    """Unfailed host-store day: the bit-parity baseline."""
    out = str(tmp_path_factory.mktemp("ref_out"))
    runner = _make_runner(day_data, out)
    stats = runner.train_day(DAY)
    return {"stats": stats, "state": _final_state(runner)}


# ---------------------------------------------------------------------------
# transient-fault retry matrix: ~6 sites x {raise, delay}
# ---------------------------------------------------------------------------

# (site, hit) — hits are chosen to land in different pass phases:
# builds, write-backs, prefetch reads mid-pass-1 and mid-pass-2, and the
# post-train save/publish window (which exercises the
# no-double-applied-updates rollback: the store was already written back
# when the failure hit).
RETRY_SITES = [
    ("pass_engine/build", 2),
    ("pass_engine/write_back", 2),
    ("trainer/prefetch", 2),
    ("trainer/pack", 5),
    ("day_runner/save", 1),
    ("day_runner/publish", 2),
]


@pytest.mark.parametrize("action", ["raise=IOError", "delay_ms=120"])
@pytest.mark.parametrize("site,hit", RETRY_SITES,
                         ids=[s.replace("/", "_") for s, _ in RETRY_SITES])
def test_transient_fault_costs_one_retry_bit_parity(
        site, hit, action, day_data, reference, tmp_path):
    out = str(tmp_path / "out")
    retries0 = monitor.get("pass/retries")
    faults.configure(f"{site}:hit={hit}:{action}")
    runner = _make_runner(day_data, out)
    stats = runner.train_day(DAY)
    faults.clear()

    injected = monitor.get(f"fault/{site}_injected")
    assert injected >= 1, "fault site never traversed"
    if action.startswith("raise"):
        assert monitor.get("pass/retries") - retries0 >= 1
    else:
        # A pure delay is not a failure: no retry, just latency.
        assert monitor.get("pass/retries") - retries0 == 0

    ref = reference
    assert len(stats) == len(ref["stats"])
    for got, want in zip(stats, ref["stats"]):
        assert got["steps"] == want["steps"]
        assert got["loss"] == want["loss"], (site, got["loss"],
                                            want["loss"])
        assert got["auc"] == want["auc"]
    _assert_state_equal(_final_state(runner), ref["state"])
    # Recovery index is intact: 2 deltas + the day base, exactly once.
    recs = runner.ckpt.records()
    assert [(r.day, r.pass_id) for r in recs] == \
        [(DAY, 1), (DAY, 2), (DAY, 0)]


@pytest.mark.parametrize("device_store", [False, True],
                         ids=["host_store", "device_store"])
def test_shuffle_fault_after_the_feed_retries_bit_parity(
        device_store, day_data, reference, tmp_path, monkeypatch):
    """The loader feeds the engine BEFORE it shuffles: a transient fault
    in pass 2's shuffle finds that pass's build pending, the retry path
    cancels it, and the replay (load -> keys -> feed -> shuffle again)
    ends bit-identical to an unfailed day."""
    from paddlebox_tpu.train.day_runner import DayRunner
    if device_store:
        want = _make_runner(day_data, str(tmp_path / "ref"),
                            device_store=True)
        ref = {"stats": want.train_day(DAY), "state": _final_state(want)}
    else:
        ref = reference
    runner = _make_runner(day_data, str(tmp_path / "out"),
                          device_store=device_store)
    runner.pipeline_passes = False       # each pass loads inside its retry loop
    fed, cancelled = [], []
    feed_keys = DayRunner._feed_keys
    cancel = runner.trainer.engine.cancel_pending

    def spy_feed(self, ds, day, pass_id):
        fed.append(pass_id)
        return feed_keys(self, ds, day, pass_id)

    def spy_cancel():
        cancelled.append(any(g.engine._pending is not None
                             for g in runner.trainer.engine.groups))
        return cancel()
    monkeypatch.setattr(DayRunner, "_feed_keys", spy_feed)
    monkeypatch.setattr(runner.trainer.engine, "cancel_pending", spy_cancel)
    retries0 = monitor.get("pass/retries")
    faults.configure("day_runner/shuffle:hit=2:raise=IOError")
    stats = runner.train_day(DAY)
    faults.clear()
    assert monitor.get("fault/day_runner/shuffle_injected") >= 1
    assert monitor.get("pass/retries") - retries0 == 1
    assert fed == [1, 2, 2]
    assert cancelled[0] is True, "no build was pending at the fault"
    for got, want in zip(stats, ref["stats"]):
        assert (got["steps"], got["loss"], got["auc"]) == \
            (want["steps"], want["loss"], want["auc"])
    _assert_state_equal(_final_state(runner), ref["state"])
    assert [(r.day, r.pass_id) for r in runner.ckpt.records()] == \
        [(DAY, 1), (DAY, 2), (DAY, 0)]


def test_fatal_fault_is_not_retried(day_data, tmp_path):
    """ValueError (bad data / code bug class) must raise immediately —
    blind retry would re-fail or mask the bug."""
    retries0 = monitor.get("pass/retries")
    faults.configure("day_runner/save:raise=ValueError")
    runner = _make_runner(day_data, str(tmp_path / "out"))
    with pytest.raises(ValueError):
        runner.train_day(DAY)
    assert monitor.get("pass/retries") - retries0 == 0


def test_retry_budget_exhaustion_raises_original(day_data, tmp_path):
    """A persistent transient fault raises after FLAGS_pass_max_retries
    attempts (times=0 keeps the site hot forever)."""
    flagmod.set_flags({"pass_max_retries": 1})
    retries0 = monitor.get("pass/retries")
    faults.configure("day_runner/save:times=0:raise=IOError")
    runner = _make_runner(day_data, str(tmp_path / "out"))
    with pytest.raises(OSError):
        runner.train_day(DAY)
    assert monitor.get("pass/retries") - retries0 == 1


def test_retry_disabled_with_zero_budget(day_data, tmp_path):
    flagmod.set_flags({"pass_max_retries": 0})
    faults.configure("day_runner/save:raise=IOError")
    runner = _make_runner(day_data, str(tmp_path / "out"))
    with pytest.raises(OSError):
        runner.train_day(DAY)


def test_device_store_retry_bit_parity(day_data, tmp_path):
    """The HBM-tier store heals the same way: a transient push failure
    mid-day retries to a bit-identical final state."""
    ref = _make_runner(day_data, str(tmp_path / "ref"),
                       device_store=True)
    ref_stats = ref.train_day(DAY)

    faults.configure("device_store/push:hit=2:raise=IOError")
    runner = _make_runner(day_data, str(tmp_path / "out"),
                          device_store=True)
    stats = runner.train_day(DAY)
    faults.clear()
    assert [s["loss"] for s in stats] == [s["loss"] for s in ref_stats]
    _assert_state_equal(_final_state(runner), _final_state(ref))


# ---------------------------------------------------------------------------
# watchdog: stall -> forensic abort -> retry
# ---------------------------------------------------------------------------

def test_watchdog_stall_aborts_then_retries_bit_parity(
        day_data, reference, tmp_path):
    """An 8s wedge in the prefetch path with a 5s stall budget: the
    watchdog dumps forensics, aborts the pass via StallError, and the
    retry completes the day bit-identically. (The generous timeout keeps
    the first-dispatch XLA compile from tripping it.)"""
    flagmod.set_flags({"stall_timeout_s": 5.0, "pass_max_retries": 3})
    stalls0 = monitor.get("watchdog/stalls")
    retries0 = monitor.get("pass/retries")
    faults.configure("trainer/prefetch:hit=6:delay_ms=8000")
    t0 = time.time()
    runner = _make_runner(day_data, str(tmp_path / "out"))
    stats = runner.train_day(DAY)
    faults.clear()
    assert monitor.get("watchdog/stalls") - stalls0 >= 1
    assert monitor.get("pass/retries") - retries0 >= 1
    # It aborted at the stall budget and retried — it did NOT hang.
    assert time.time() - t0 < 120
    ref = reference
    for got, want in zip(stats, ref["stats"]):
        assert got["loss"] == want["loss"]
    _assert_state_equal(_final_state(runner), ref["state"])


# ---------------------------------------------------------------------------
# kill -9 crash drills (subprocess; fast 2-site mode is tier-1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def drill_env(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("drill"))
    ref = crash_drill.run_reference(workdir)
    return workdir, ref


# The first fast site stays in tier-1; the second (12 s of subprocess
# drill) rides the slow tier with the full matrix so tier-1 keeps its
# 870 s window (PR 21).
@pytest.mark.parametrize(
    "site,hit",
    [pytest.param(s, h, marks=() if i == 0 else pytest.mark.slow)
     for i, (s, h) in enumerate(crash_drill.FAST_SITES)],
    ids=[s.replace("/", "_") + f"_h{h}"
         for s, h in crash_drill.FAST_SITES])
def test_kill9_resumes_via_recover_fast(drill_env, site, hit):
    """SIGKILL the worker AT the site, restart with resume=True: the
    donefile chain must replay to the exact uninterrupted final state —
    same dense digest, same store digest, same records, losses a suffix
    of the reference's (no pass retrained twice = no double-applied
    deltas; the store digest would differ if show/click doubled)."""
    workdir, ref = drill_env
    r = crash_drill.run_drill(workdir, site, hit=hit, reference=ref)
    assert r["killed_rc"] == -9, r
    assert r["ok"], r["mismatch"]


@pytest.mark.slow
@pytest.mark.parametrize(
    "site,hit",
    [s for s in crash_drill.FULL_SITES if s not in crash_drill.FAST_SITES],
    ids=[s.replace("/", "_") + f"_h{h}"
         for s, h in crash_drill.FULL_SITES
         if (s, h) not in crash_drill.FAST_SITES])
def test_kill9_resumes_via_recover_full(drill_env, site, hit):
    workdir, ref = drill_env
    r = crash_drill.run_drill(workdir, site, hit=hit, reference=ref)
    assert r["killed_rc"] == -9, r
    assert r["ok"], r["mismatch"]


def test_killed_ingest_worker_retried_without_hanging_preload(tmp_path):
    """Round-13 ingest process boundary: SIGKILL an ingest worker
    MID-FILE — the pump must requeue the file on a fresh worker
    (FLAGS_ingest_file_retries) and wait_preload_done() must return the
    complete, non-duplicated dataset instead of hanging on the dead
    child; with the retry budget at 0 the death propagates as an error
    (tests/test_ingest.py covers that half)."""
    from paddlebox_tpu.data import DataFeedConfig, Dataset, SlotConf

    lines = [f"1 user:{i} item:{i + 1000}" for i in range(1, 61)]
    part = tmp_path / "part-0"
    part.write_text("\n".join(lines) + "\n")
    started = tmp_path / "started"
    feed = DataFeedConfig(
        slots=(SlotConf("user"), SlotConf("item")), batch_size=8,
        pipe_command=f"touch {started}; sleep 3; cat")
    old = flagmod.get_flags(["ingest_workers", "ingest_file_retries"])
    flagmod.set_flags({"ingest_workers": 1, "ingest_file_retries": 1})
    try:
        ds = Dataset(feed)
        ds.set_filelist([str(part)])
        ds.preload_into_memory()
        t0 = time.time()
        while not started.exists() and time.time() - t0 < 60:
            time.sleep(0.05)
        assert started.exists(), "worker never reached the file"
        time.sleep(0.2)
        assert ds._ingest_procs
        started.unlink()  # the RETRY recreates it through the same pipe
        victim = ds._ingest_procs[0]
        os.kill(victim.pid, 9)
        t0 = time.time()
        while not started.exists() and time.time() - t0 < 60:
            time.sleep(0.05)
        assert started.exists(), "no replacement worker took the file"
        ds.wait_preload_done()  # returns (pipe delay), never hangs
        assert ds.num_instances == 60  # complete, no duplicated rows
        assert monitor.get("ingest/worker_restarts") >= 1
        ds.clear()
    finally:
        flagmod.set_flags(old)
