"""Span tracer tests: nesting, thread safety, ring bound, Chrome-trace
validity, snapshot-on-exception, and the disabled-path contract (the
zero-hot-loop-cost requirement of the telemetry layer)."""

import json
import threading

import pytest

from paddlebox_tpu.core import flags as flagmod
from paddlebox_tpu.core import trace
from paddlebox_tpu.core.trace import Tracer


def test_span_nesting_records_both_levels():
    tr = Tracer(capacity=128)
    tr.enable()
    with tr.span("outer", k=4):
        with tr.span("inner"):
            pass
    evs = tr.snapshot()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert outer["dur"] >= inner["dur"] >= 0.0
    assert outer["tid"] == inner["tid"] == threading.get_ident()
    assert outer["args"] == {"k": 4}
    assert all(e["ph"] == "X" for e in evs)


def test_thread_safety_all_events_land():
    tr = Tracer(capacity=100_000)
    tr.enable()
    n_threads, n_spans = 8, 200
    errors = []
    # all workers alive at once: real contention, and idents that differ
    start = threading.Barrier(n_threads)

    def worker(i):
        try:
            start.wait(timeout=30)
            for j in range(n_spans):
                with tr.span(f"t{i}", j=j):
                    pass
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    evs = tr.snapshot()
    assert len(evs) == n_threads * n_spans
    # tids are OS thread idents (reused once a thread exits), so the
    # distinct count is >= 2, not necessarily n_threads.
    assert len({e["tid"] for e in evs}) >= 2


def test_ring_buffer_bound_and_drop_count():
    tr = Tracer(capacity=16)
    tr.enable()
    for i in range(100):
        tr.instant("e", i=i)
    evs = tr.snapshot()
    assert len(evs) == 16
    # Oldest dropped, newest kept.
    assert [e["args"]["i"] for e in evs] == list(range(84, 100))
    assert tr.trace_object()["otherData"]["dropped_events"] == 84


def test_export_valid_chrome_trace_json(tmp_path):
    tr = Tracer(capacity=64)
    tr.enable(str(tmp_path / "t.trace.json"))
    with tr.span("stage", table="emb"):
        pass
    tr.instant("marker")
    tr.counter("bytes", per_step=123.0)
    path = tr.export()
    obj = json.load(open(path))
    evs = obj["traceEvents"]
    assert isinstance(evs, list) and evs
    # Thread-name metadata + the three recorded events.
    phs = [e["ph"] for e in evs]
    assert "M" in phs and "X" in phs and "i" in phs and "C" in phs
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and "ts" in e
    # Args must have been clamped to JSON scalars.
    json.dumps(obj)


def test_span_records_on_exception_with_error_arg():
    tr = Tracer(capacity=8)
    tr.enable()
    with pytest.raises(ValueError):
        with tr.span("doomed", step=3):
            raise ValueError("boom")
    (ev,) = tr.snapshot()
    assert ev["name"] == "doomed"
    assert ev["args"]["step"] == 3
    assert "ValueError" in ev["args"]["error"]
    # The ring IS the crash dump: snapshot() after the exception has it.


def test_disabled_path_is_shared_noop():
    tr = Tracer(capacity=8)
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is s2  # one shared null context, zero allocation
    with s1:
        pass
    tr.instant("c")
    tr.counter("d", v=1.0)
    assert tr.snapshot() == []


def test_non_json_args_are_clamped():
    tr = Tracer(capacity=8)
    tr.enable()
    with tr.span("s", obj=object()):
        pass
    (ev,) = tr.snapshot()
    assert isinstance(ev["args"]["obj"], str)
    json.dumps(ev)


def test_global_init_from_flags(tmp_path):
    path = str(tmp_path / "flagged.trace.json")
    prev = flagmod.flag("trace_path")
    try:
        flagmod.set_flags({"trace_path": path, "trace_ring_events": 32})
        assert trace.init_from_flags() is True
        assert trace.enabled()
        with trace.span("flagged"):
            pass
        out = trace.export()
        assert out == path
        assert any(e["name"] == "flagged"
                   for e in json.load(open(out))["traceEvents"])
    finally:
        flagmod.set_flags({"trace_path": prev})
        trace.disable()
        trace.clear()


def test_init_from_flags_stays_off_without_path():
    prev = flagmod.flag("trace_path")
    try:
        flagmod.set_flags({"trace_path": ""})
        trace.disable()
        assert trace.init_from_flags() is False
        assert not trace.enabled()
    finally:
        flagmod.set_flags({"trace_path": prev})
