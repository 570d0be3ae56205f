"""The day loop's spans (OBSERVABILITY.md "The span tracer"): a pipelined
day over the device store with the ring on names every place a thread
waits or builds — the preload join, the pass build, the feed queue — on
the thread that does it; with the ring off the same day records nothing."""

import importlib
import json
import os
import threading

import numpy as np
import pytest

from paddlebox_tpu.core import trace
from paddlebox_tpu.parallel import HybridTopology, build_mesh

from tests.test_day_runner import _write_day
from tests.test_day_runner_device_store import _make_runner

DAY = "20260701"
HOURS = [0, 1, 2]          # what ``_make_runner`` trains: three passes

TRAINER_SPANS = ("day/preload_join", "pass/begin_pass", "pass/feed_wait",
                 "pass/end_pass")
PRELOAD_SPANS = ("ingest/load", "ingest/shuffle", "ingest/pass_keys",
                 "ingest/feed_pass")
BUILD_SPANS = ("build/pass_table", "build/boundary_wait",
               "store/ensure_rows", "store/bucket")


def _run_day(tmp_path, pipeline=True):
    data_root = str(tmp_path / "data")
    _write_day(data_root, DAY, HOURS)
    trainer, runner = _make_runner(data_root, str(tmp_path / "out"),
                                   build_mesh(HybridTopology(dp=8)))
    runner.pipeline_passes = pipeline
    return runner.train_day(DAY)


@pytest.fixture(scope="module")
def day_events(tmp_path_factory):
    """X events of one pipelined three-pass day, ring on."""
    trace.clear()
    trace.enable(ring_events=1 << 16)
    try:
        stats = _run_day(tmp_path_factory.mktemp("day_on"))
        ring = trace.GLOBAL.trace_object()
    finally:
        trace.disable()
        trace.clear()
    assert len(stats) == len(HOURS)
    assert ring["otherData"]["dropped_events"] == 0
    return [e for e in ring["traceEvents"] if e["ph"] == "X"]


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


@pytest.mark.parametrize("name", TRAINER_SPANS + PRELOAD_SPANS + BUILD_SPANS)
def test_every_steady_pass_has_the_span(day_events, name):
    # passes 2 and 3 are joined from a preload; every pass builds a table
    steady = len(HOURS) - 1
    assert len(_named(day_events, name)) >= steady


@pytest.mark.parametrize("name", TRAINER_SPANS)
def test_trainer_waits_are_on_the_day_loops_thread(day_events, name):
    (driver,) = {e["tid"] for e in _named(day_events, "day/train")}
    assert {e["tid"] for e in _named(day_events, name)} == {driver}
    if name != "day/preload_join":      # the join sits between passes
        trains = _named(day_events, "day/train")
        assert all(any(_inside(e, t) for t in trains)
                   for e in _named(day_events, name))


@pytest.mark.parametrize("name", PRELOAD_SPANS)
def test_ingest_spans_are_on_the_preload_thread(day_events, name):
    (driver,) = {e["tid"] for e in _named(day_events, "day/train")}
    spans = _named(day_events, name)
    # pass 1 loads on the day loop's thread; every later pass preloads
    off_thread = [e for e in spans if e["tid"] != driver]
    assert len(off_thread) == len(HOURS) - 1
    assert sorted(e["args"]["pass_id"] for e in off_thread) == [2, 3]
    assert sorted(e["args"]["pass_id"] for e in spans) == [1, 2, 3]
    assert {e["args"]["day"] for e in spans} == {DAY}


def test_the_engine_gets_the_keys_before_the_shuffle(day_events):
    by_pass = {name: {e["args"]["pass_id"]: e
                      for e in _named(day_events, name)}
               for name in PRELOAD_SPANS}
    for pass_id in (1, 2, 3):
        load, shuffle, keys, feed = (by_pass[n][pass_id]
                                     for n in PRELOAD_SPANS)
        assert len({e["tid"] for e in (load, shuffle, keys, feed)}) == 1
        assert load["ts"] + load["dur"] <= keys["ts"]
        assert keys["ts"] + keys["dur"] <= feed["ts"]
        assert feed["ts"] < shuffle["ts"] + shuffle["dur"]
        assert feed["ts"] + feed["dur"] <= shuffle["ts"]


def test_key_merges_run_under_their_pass_load(day_events):
    merges = _named(day_events, "ingest/key_merge")
    outer = (_named(day_events, "ingest/load")
             + _named(day_events, "ingest/pass_keys"))

    def within(e, o):
        return (o["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= o["ts"] + o["dur"])
    homes = [[o for o in outer if within(e, o)] for e in merges]
    assert all(len(h) == 1 for h in homes), homes
    # every pass's load had its keys merged under it, on a helper thread
    assert {h[0]["args"]["pass_id"] for h in homes
            if h[0]["name"] == "ingest/load"} == {1, 2, 3}
    assert all(e["tid"] != h[0]["tid"] for e, h in zip(merges, homes)
               if h[0]["name"] == "ingest/load")


def _read_metric(name, events, passes):
    """A benchmark metric file through its reader, over ring events."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    spans = [(e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3, e["name"],
              e["tid"]) for e in events]
    observed = {"program_spans": spans, "passes": passes,
                "window_unix_ns": (min(s[0] for s in spans),
                                   max(s[1] for s in spans))}
    return reader.read(spec["params"], observed, None, None)


def test_keys_to_engine_metric_reads_load_end_to_feed(day_events):
    got = _read_metric("ingest.keys_to_engine_ms_per_pass", day_events,
                       len(HOURS))
    loads = {e["args"]["pass_id"]: e
             for e in _named(day_events, "ingest/load")}
    gaps = [(f["ts"] - loads[f["args"]["pass_id"]]["ts"]
             - loads[f["args"]["pass_id"]]["dur"]) / 1e3
            for f in _named(day_events, "ingest/feed_pass")]
    assert got == pytest.approx(float(np.median(gaps)), abs=1e-6)
    # what lies between holds the key tail (and no shuffle: the test above)
    assert got >= min(e["dur"] / 1e3
                      for e in _named(day_events, "ingest/pass_keys"))


def test_key_merge_metric_sums_the_helpers_spans(day_events):
    got = _read_metric("ingest.key_merge_ms_per_pass", day_events,
                       len(HOURS))
    total = sum(e["dur"] for e in _named(day_events, "ingest/key_merge"))
    assert got == pytest.approx(total / 1e3 / len(HOURS))
    # a program without the span (the parent) leaves the metric out
    rest = [e for e in day_events if e["name"] != "ingest/key_merge"]
    assert _read_metric("ingest.key_merge_ms_per_pass", rest,
                        len(HOURS)) is None


def test_preload_join_names_the_pass_joined(day_events):
    joins = sorted(_named(day_events, "day/preload_join"),
                   key=lambda e: e["ts"])
    assert [e["args"]["pass_id"] for e in joins] == [2, 3]
    loads = {e["args"]["pass_id"]: e
             for e in _named(day_events, "day/load")}
    for e in joins:                     # the join ends before day/load
        assert e["ts"] + e["dur"] <= loads[e["args"]["pass_id"]]["ts"]


def test_build_spans_name_the_pass_built_on_their_own_thread(day_events):
    (driver,) = {e["tid"] for e in _named(day_events, "day/train")}
    builds = sorted(_named(day_events, "build/pass_table"),
                    key=lambda e: e["ts"])
    # the engine counts passes from 0
    assert [e["args"]["pass_id"] for e in builds] == [0, 1, 2]
    assert all(e["tid"] != driver for e in builds)
    for wait in _named(day_events, "build/boundary_wait"):
        assert any(_inside(wait, b) for b in builds)


@pytest.mark.parametrize("name", ["store/ensure_rows", "store/bucket"])
def test_store_spans_nest_under_the_build_or_the_boundary(day_events, name):
    # the checkpoint writers read rows back through the same bucketing
    outer = [e for e in day_events if e["name"] in (
        "build/pass_table", "pass/end_pass", "day/save_delta",
        "day/save_xbox", "day/day_end")]
    spans = _named(day_events, name)
    stray = [e for e in spans if not any(_inside(e, o) for o in outer)]
    assert not stray, stray
    under_build = [e for e in spans if any(
        _inside(e, o) for o in _named(day_events, "build/pass_table"))]
    assert len(under_build) >= len(HOURS) - 1
    arg = "keys" if name == "store/ensure_rows" else "rows"
    assert all(e["args"][arg] > 0 for e in spans)


def test_unpipelined_day_feeds_before_its_shuffle_too(tmp_path):
    trace.clear()
    trace.enable(ring_events=1 << 16)
    try:
        _run_day(tmp_path, pipeline=False)
        events = [e for e in trace.snapshot() if e["ph"] == "X"]
    finally:
        trace.disable()
        trace.clear()
    # the same load -> keys -> feed -> shuffle, inside the pass's day/load
    loads = _named(events, "day/load")
    for name in PRELOAD_SPANS:
        spans = _named(events, name)
        assert sorted(e["args"]["pass_id"] for e in spans) == [1, 2, 3]
        assert all(any(_inside(e, d) for d in loads) for e in spans)
    shuffles = {e["args"]["pass_id"]: e
                for e in _named(events, "ingest/shuffle")}
    for feed in _named(events, "ingest/feed_pass"):
        assert (feed["ts"] + feed["dur"]
                <= shuffles[feed["args"]["pass_id"]]["ts"])
    assert not _named(events, "pass/feed_pass")
    assert not _named(events, "day/preload_join")


def test_ring_off_records_nothing_and_costs_a_shared_null_span(
        tmp_path, monkeypatch):
    trace.disable()
    trace.clear()
    opened = []
    real = trace.GLOBAL.span

    def spy(name, **args):
        got = real(name, **args)
        opened.append((name, got, threading.get_ident()))
        return got
    # every site calls ``trace.span``: the module attribute
    monkeypatch.setattr(trace, "span", spy)
    stats = _run_day(tmp_path)
    assert len(stats) == len(HOURS)
    assert trace.snapshot() == []
    seen = {name for name, _, _ in opened}
    assert seen >= set(TRAINER_SPANS + PRELOAD_SPANS + BUILD_SPANS
                       + ("ingest/key_merge",))
    assert all(got is trace.NULL_SPAN for _, got, _ in opened)
