"""Pass-report + telemetry integration over the CTR trainer.

The acceptance contract of the telemetry layer: a tiny CPU train_pass
with FLAGS_trace_path / FLAGS_metrics_path set produces a
Perfetto-loadable trace JSON, a parseable metrics JSONL, and one
structured per-pass summary covering every PrintSyncTimer stage
(read/pack/pull/fwd-bwd/push/dispatch/sync) — consistent with the K>1
megastep counters — while tracing adds ZERO ops to the jitted step
(the op-structure pins of test_step_structure must hold with telemetry
on)."""

import json
import math

import numpy as np
import pytest

from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.core import monitor, report, trace
from paddlebox_tpu.data import Dataset, DataFeedConfig, SlotConf
from paddlebox_tpu.embedding import DeviceFeatureStore, TableConfig
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import HybridTopology, build_mesh
from paddlebox_tpu.train import CTRTrainer, TrainerConfig

SLOTS = ("u", "i", "c")
N_BATCHES = 13          # K=4 -> blocks of 4,4,4,1 (tail block covered)
BATCH = 32


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
    return _shard(tmp_path_factory.mktemp("preport") / "part-0",
                  N_BATCHES * BATCH)


def _feed():
    return DataFeedConfig(
        slots=tuple(SlotConf(s, avg_len=1.5) for s in SLOTS),
        batch_size=BATCH)


def _dataset(p):
    feed = _feed()
    ds = Dataset(feed, num_reader_threads=1)
    ds.set_filelist([p])
    ds.load_into_memory()
    return ds


def _trainer():
    mesh = build_mesh(HybridTopology(dp=8))
    tr = CTRTrainer(DeepFM(slot_names=SLOTS, emb_dim=8, hidden=(16,)),
                    _feed(), TableConfig(dim=8, learning_rate=0.1),
                    mesh=mesh,
                    config=TrainerConfig(auc_num_buckets=1 << 10),
                    store_factory=lambda c: DeviceFeatureStore(
                        c, mesh=mesh))
    tr.init(seed=0)
    return tr


@pytest.fixture()
def telemetry_paths(tmp_path):
    """Arm both sinks via flags; fully disarm afterwards so the rest of
    the suite runs with telemetry default-off."""
    tpath = str(tmp_path / "run.trace.json")
    mpath = str(tmp_path / "run.metrics.jsonl")
    flagmod.set_flags({"trace_path": tpath, "metrics_path": mpath,
                       "metrics_flush_interval_s": 0.0})
    trace.clear()
    monitor.reset()
    try:
        yield tpath, mpath
    finally:
        flagmod.set_flags({"trace_path": "", "metrics_path": "",
                           "metrics_flush_interval_s": 30.0})
        trace.disable()
        trace.clear()
        monitor.stop_flush_thread()
        monitor.reset()


def test_train_pass_report_with_megastep_and_artifacts(shard_13,
                                                       telemetry_paths):
    tpath, mpath = telemetry_paths
    tr = _trainer()
    prev = flagmod.flag("trainer_steps_per_dispatch")
    flagmod.set_flags({"trainer_steps_per_dispatch": 4})
    try:
        stats = tr.train_pass(_dataset(shard_13))
    finally:
        flagmod.set_flags({"trainer_steps_per_dispatch": prev})

    # -- the structured per-pass summary ------------------------------
    rep = stats["pass_report"]
    assert rep["kind"] == "train"
    assert set(rep["stage_ms"]) == set(report.STAGES)
    for s in report.STAGES:
        assert rep["stage_ms"][s] >= 0.0
    # Host stages actually observed something on this pass.
    assert rep["stage_ms"]["read"] > 0.0
    assert rep["stage_ms"]["pull"] > 0.0
    assert rep["stage_ms"]["dispatch"] > 0.0
    # Consistency with the K=4 megastep: 13 steps -> ceil(13/4) blocks,
    # zero in-loop host syncs, global sample count.
    assert rep["steps"] == stats["steps"] == N_BATCHES
    assert rep["samples"] == N_BATCHES * BATCH
    assert rep["samples_per_s"] > 0
    assert stats["dispatch_blocks"] == math.ceil(N_BATCHES / 4)
    assert rep["dispatch_blocks"] == stats["dispatch_blocks"]
    assert rep["host_syncs"] == 0
    assert rep["steps_per_dispatch"] == 4
    assert rep["lookup_exchange_bytes"] == stats["lookup_exchange_bytes"]
    assert rep["lookup_exchange_bytes"] > 0
    assert "seg_cache_hit_rate" in rep
    # No host-side idle estimate: the device's idle share is read from
    # the device trace (benchmarks/), which the program cannot see.
    assert "bottleneck" not in rep and "bottleneck" not in stats
    dq = rep["dispatch_ms_quantiles"]
    assert dq["count"] == stats["dispatch_blocks"]
    assert dq["p50"] <= dq["p99"]

    # -- trace artifact: Perfetto/chrome-loadable ---------------------
    out = trace.export()
    assert out == tpath
    obj = json.load(open(tpath))
    names = {e["name"] for e in obj["traceEvents"]}
    assert "pass/dispatch" in names
    assert "prefetch/host_map" in names
    assert "pass_report/train" in names
    dispatches = [e for e in obj["traceEvents"]
                  if e["name"] == "pass/dispatch" and e["ph"] == "X"]
    assert len(dispatches) == stats["dispatch_blocks"]
    # Producer spans come from the prefetch thread, dispatch from the
    # consumer: at least two distinct tids in the timeline.
    assert len({e["tid"] for e in obj["traceEvents"]}) >= 2

    # -- metrics artifact: every line parses, registry is fed ---------
    lines = [json.loads(x) for x in open(mpath).read().splitlines()]
    assert lines, "pass report must append at least one snapshot"
    last = lines[-1]
    assert last["labels"] == {"event": "pass_report", "kind": "train"}
    h = last["histograms"]["trainer/dispatch_ms"]
    assert h["count"] == stats["dispatch_blocks"]
    assert sum(h["counts"]) == h["count"]
    assert last["counters"]["pass/train_passes"] == 1
    assert last["counters"]["pass/train_steps"] == N_BATCHES
    assert last["gauges"]["pass/train_samples_per_s"] > 0
    assert last["counters"]["lookup/exchange_bytes_per_step"] == \
        stats["lookup_exchange_bytes"]
    # Quantile digests ride the snapshot (mergeable across ranks).
    q = last["quantiles"]["trainer/dispatch_ms"]
    assert q["count"] == stats["dispatch_blocks"]
    assert q["p50"] is not None
    assert not [g for g in last["gauges"]
                if g.startswith("pipeline/")
                or g.endswith(("_device_idle_frac",
                               "_host_critical_share"))]


def test_eval_pass_report(shard_13, telemetry_paths):
    tr = _trainer()
    prev = flagmod.flag("trainer_steps_per_dispatch")
    flagmod.set_flags({"trainer_steps_per_dispatch": 4})
    try:
        stats = tr.eval_pass(_dataset(shard_13))
    finally:
        flagmod.set_flags({"trainer_steps_per_dispatch": prev})
    rep = stats["pass_report"]
    assert rep["kind"] == "eval"
    assert set(rep["stage_ms"]) == set(report.STAGES)
    assert stats["dispatch_blocks"] == math.ceil(N_BATCHES / 4)
    assert rep["steps"] == N_BATCHES
    # Eval pushes nothing: the push stage must be (near) zero.
    assert rep["stage_ms"]["push"] == 0.0


def test_telemetry_off_no_artifacts(shard_13, tmp_path):
    """Default-off contract: with the flags unset, a pass writes no
    files and records no trace events."""
    trace.disable()
    trace.clear()
    tr = _trainer()
    stats = tr.train_pass(_dataset(shard_13))
    assert stats["pass_report"]["steps"] == N_BATCHES  # report still built
    assert trace.snapshot() == []
    assert list(tmp_path.iterdir()) == []


def test_tracing_leaves_step_op_structure_unchanged(telemetry_paths):
    """The zero-hot-loop-cost pin: enabling telemetry must not change
    the jitted train step's op counts (host spans only — no device
    ops, no syncs)."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.data.parser import parse_lines
    from paddlebox_tpu.data.slots import SlotBatch
    from paddlebox_tpu.train.ctr_trainer import _concat_dense_host
    from paddlebox_tpu.utils import inspect as pbx_inspect

    def op_counts():
        mesh = build_mesh(HybridTopology(dp=4),
                          devices=jax.devices()[:4])
        slots = tuple(SlotConf(f"s{i}", avg_len=2.0) for i in range(3))
        feed = DataFeedConfig(slots=slots, batch_size=16)
        model = DeepFM(slot_names=tuple(f"s{i}" for i in range(3)),
                       emb_dim=8, hidden=(16, 8))
        tr = CTRTrainer(model, feed, TableConfig(dim=8), mesh=mesh,
                        config=TrainerConfig(auc_num_buckets=1 << 10),
                        store_factory=lambda c: DeviceFeatureStore(
                            c, mesh=mesh))
        tr.init(seed=0)
        rng = np.random.default_rng(0)
        lines = [f"{rng.integers(0, 2)} "
                 + " ".join(f"s{i}:{rng.integers(1, 40)}"
                            for i in range(3))
                 for _ in range(feed.batch_size)]
        batch = SlotBatch.pack_sharded(parse_lines(lines, feed), feed, 4)
        tr.engine.feed_pass([
            np.unique(np.concatenate([batch.ids[n] for n in g.slots]))
            for g in tr.engine.groups])
        step = tr._build_step()
        tables = tr.engine.begin_pass()
        rows = tr._map_batch_rows(batch)
        segs = {n: jnp.asarray(batch.segments[n]) for n in batch.ids}
        args = (tables, tr.params, tr.opt_state, tr.auc_state, rows,
                segs, jnp.asarray(batch.labels),
                jnp.asarray(batch.valid),
                jnp.asarray(_concat_dense_host(batch)),
                jnp.zeros((), jnp.int32))
        return pbx_inspect.jaxpr_summary(lambda *a: step(*a), *args)

    trace.disable()
    off = op_counts()
    assert trace.init_from_flags()  # telemetry ON via the fixture flags
    on = op_counts()
    assert on == off, (on, off)


def test_day_runner_timers_reach_registry(shard_13, tmp_path,
                                          telemetry_paths):
    """Satellite pin: the day loop publishes through the ONE report
    path (registry gauges), not a private print."""
    from paddlebox_tpu.train.day_runner import DayRunner

    tr = _trainer()
    runner = DayRunner(tr, _feed(), str(tmp_path / "out"),
                       data_root=str(tmp_path), pipeline_passes=False)
    runner.train_pass("20260804", 1, [shard_13])
    snap = monitor.snapshot()
    assert snap["day_runner/train_ms"] > 0.0
    assert snap["day_runner/passes"] == 1
    assert snap["pass/train_passes"] >= 1


# What a pass report carries (a day loop adds its own ingest counters).
EVAL_REPORT_KEYS = {
    "kind", "steps", "samples", "wall_s", "samples_per_s", "stage_ms",
    "other_ms", "auc", "bucket_error", "mae", "rmse", "actual_ctr",
    "predicted_ctr", "copc", "count", "loss", "dispatch_blocks",
    "steps_per_dispatch", "seg_cache_hit_rate", "boundary",
    "dispatch_ms_quantiles"}
TRAIN_REPORT_KEYS = EVAL_REPORT_KEYS | {
    "host_syncs", "lookup_overflow", "kernel_fallback",
    "kernel_hot_served", "lookup_exchange_bytes", "lookup_duplication",
    "scale_sparse_grad_by_batch"}


def test_train_pass_emits_report_keys_and_dispatch_quantiles(shard_13):
    """A train pass's report: every key, and dispatch-latency quantiles
    windowed to this pass's blocks, mirrored as registry gauges."""
    monitor.reset()
    tr = _trainer()
    stats = tr.train_pass(_dataset(shard_13))
    rep = stats["pass_report"]
    assert set(rep) == TRAIN_REPORT_KEYS
    assert set(rep["boundary"]) == {"end_ms", "build_ms", "feed_wait_ms",
                                    "overlap_frac"}
    dq = rep["dispatch_ms_quantiles"]
    assert dq is stats["dispatch_ms_quantiles"]
    assert dq["count"] == stats["dispatch_blocks"] == N_BATCHES
    assert dq["p50"] is not None and dq["p50"] > 0.0
    assert dq["p50"] <= dq["p90"] <= dq["p99"] <= dq["p999"]
    snap = monitor.snapshot()
    assert snap["pass/train_dispatch_ms_p99"] == dq["p99"]
    assert not [g for g in snap if g.startswith("pipeline/")]


def test_eval_pass_emits_report_keys(shard_13):
    tr = _trainer()
    stats = tr.eval_pass(_dataset(shard_13))
    rep = stats["pass_report"]
    assert set(rep) == EVAL_REPORT_KEYS
    assert rep["dispatch_ms_quantiles"]["count"] == \
        stats["dispatch_blocks"]
    # Eval writes nothing back: no end_pass half in its boundary.
    assert rep["boundary"]["end_ms"] == 0.0


def test_pass_windows_are_independent(shard_13):
    """Two consecutive passes each get their OWN window: the timers and
    the dispatch digest are cumulative, the report differences them."""
    monitor.reset()
    tr = _trainer()
    ds = _dataset(shard_13)     # reusable (in memory)
    s1 = tr.train_pass(ds)
    s2 = tr.train_pass(ds)
    for s in (s1, s2):
        assert s["dispatch_ms_quantiles"]["count"] == s["dispatch_blocks"]
    assert tr.timers["dispatch"].count == (s1["dispatch_blocks"]
                                           + s2["dispatch_blocks"])
    total = tr.timers.snapshot_ms()
    for stage in ("read", "pack", "pull", "dispatch", "push"):
        a, b = (s["pass_report"]["stage_ms"][stage] for s in (s1, s2))
        assert a > 0.0 and b > 0.0
        assert a + b == pytest.approx(total[stage], abs=0.01)


# (group, stage timer, span) of each ``with`` that opens both marks.
TIMER_SPAN_SITES = (
    ("trainer", "pull", "prefetch/keymap"),
    ("trainer", "host_map", "prefetch/host_map"),
    ("trainer", "dispatch", "pass/dispatch"),
    ("trainer", "end_pass", "pass/end_pass"),
    ("engine", "feed_pass", "build/pass_table"),
    ("engine", "feed_wait", "build/boundary_wait"),
    ("runner", "load", "day/load"),
    ("runner", "train", "day/train"),
)


@pytest.fixture(scope="module")
def traced_day(tmp_path_factory):
    """One pipelined three-pass CPU day with the ring on: the span count
    per name, and the timer groups that marked the same sites (one pass
    engine per width group under the trainer's grouped engine)."""
    from collections import Counter

    from tests.test_day_runner import _write_day
    from tests.test_day_runner_device_store import _make_runner

    root = tmp_path_factory.mktemp("timer_span_day")
    _write_day(str(root / "data"), "20260701", [0, 1, 2])
    trainer, runner = _make_runner(str(root / "data"), str(root / "out"),
                                   build_mesh(HybridTopology(dp=8)))
    trace.clear()
    trace.enable(ring_events=1 << 16)
    try:
        assert len(runner.train_day("20260701")) == 3
        ring = trace.GLOBAL.trace_object()
    finally:
        trace.disable()
        trace.clear()
    assert ring["otherData"]["dropped_events"] == 0
    spans = Counter(e["name"] for e in ring["traceEvents"]
                    if e["ph"] == "X")
    return spans, {"trainer": [trainer.timers],
                   "engine": [g.engine.timers
                              for g in trainer.engine.groups],
                   "runner": [runner.timers]}


@pytest.mark.parametrize(
    "group,stage,span", TIMER_SPAN_SITES,
    ids=[f"{stage}-{span}" for _, stage, span in TIMER_SPAN_SITES])
def test_stage_timer_and_span_mark_the_same_sites(traced_day, group,
                                                  stage, span):
    """The pass report's stage totals and the ring's spans are two marks
    on one ``with``: a site that keeps one and loses the other shows as
    a count that differs (counts, not times: this is a CPU clock)."""
    spans, timers = traced_day
    assert sum(t[stage].count for t in timers[group]) == spans[span] > 0
