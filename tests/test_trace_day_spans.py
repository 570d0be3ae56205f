"""The day loop's spans (OBSERVABILITY.md "The span tracer"): a pipelined
day over the device store with the ring on names every place a thread
waits or builds — the preload join, the pass build, the feed queue — on
the thread that does it; with the ring off the same day records nothing."""

import threading

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


def test_unpipelined_day_feeds_inside_the_pass(tmp_path):
    trace.clear()
    trace.enable(ring_events=1 << 16)
    try:
        _run_day(tmp_path, pipeline=False)
        events = [e for e in trace.snapshot() if e["ph"] == "X"]
    finally:
        trace.disable()
        trace.clear()
    feeds = _named(events, "pass/feed_pass")
    assert len(feeds) == len(HOURS)
    assert all(any(_inside(f, t) for t in _named(events, "day/train"))
               for f in feeds)
    assert not _named(events, "day/preload_join")
    assert not _named(events, "ingest/feed_pass")


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
    assert seen >= set(TRAINER_SPANS + PRELOAD_SPANS + BUILD_SPANS)
    assert all(got is trace.NULL_SPAN for _, got, _ in opened)
