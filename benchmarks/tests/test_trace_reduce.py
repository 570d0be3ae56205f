"""The reduction from a trace to numbers: interval arithmetic on hand-made
cases, then the recorded v5e fixture (record_fixture.py)."""

import os

import pytest

from benchmarks.trace import reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_v5e.xplane.pb")


def test_union_merges_touching_and_nested():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 10), (6, 6.5)])
    assert cover.tolist() == [[0, 4], [5, 7]]
    assert tr.total(cover) == 6


def test_complement_and_subtract():
    cover = tr.union([(2, 4), (6, 9)])
    assert tr.complement(cover, 0, 10).tolist() == [[0, 2], [4, 6], [9, 10]]
    assert tr.complement(cover, 3, 7).tolist() == [[4, 6]]
    left = tr.subtract(tr.union([(0, 10)]), cover)
    assert left.tolist() == [[0, 2], [4, 6], [9, 10]]
    assert tr.overlap(cover, 3, 8) == 3


def test_self_intervals_give_each_span_its_own_time():
    spans = [(0, 100, "day/train", 1), (10, 30, "pass/dispatch", 1),
             (40, 60, "pass/dispatch", 1), (45, 50, "inner", 1),
             (5, 95, "prefetch/host_map", 2)]
    own = {}
    for start, end, name in tr.self_intervals(spans):
        own[name] = own.get(name, 0) + end - start
    assert own == {"day/train": 60, "pass/dispatch": 35, "inner": 5,
                   "prefetch/host_map": 90}


def _raw(window=(0, 1000), unix_ns=5000):
    """One device: busy 100-300 and 600-700, an all-to-all 300-350 that
    nothing overlaps and one 620-660 under compute."""
    ops = [(100, 300, "_sorted_gather_blocks.1", "custom-call"),
           (300, 350, "all-to-all.2", "all-to-all"),
           (600, 700, "_sorted_gather_blocks.1", "custom-call"),
           (620, 660, "all-to-all.2", "all-to-all"),
           (90, 710, "cond.3", "conditional")]
    host = [(window[0], window[1], tr.WINDOW_ANNOTATION,
             {"unix_ns": unix_ns})]
    modules = [(95, 355, "jit_step"), (595, 705, "jit_step")] + [
        (360 + 10 * i, 365 + 10 * i, "jit_convert") for i in range(5)]
    return {"devices": {0: ops}, "modules": {0: modules}, "host": host}


def test_reduce_hand_made_trace():
    # program spans in unix ns: the window starts at unix 5000
    spans = [(5000, 5600, "wait_batch", 7), (5350, 5500, "keymap", 8)]
    got = tr.reduce(_raw(), spans)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(620e-9)      # the container
    # a collective counts as exposed while only a container is open
    assert got["collective_exposed_s"] == pytest.approx(50e-9)
    seconds, calls = tr.matching(got, ["^_sorted_gather_blocks"])
    assert (seconds, calls) == (pytest.approx(300e-9), 2)
    assert tr.matching(got, ["no_such_kernel"]) == (0, 0)
    idle = dict(got["breakdown"]["idle_gaps"])
    # gaps: 0-90, 710-1000; wait_batch covers 0-600
    assert idle["wait_batch"] == pytest.approx(90e-9)
    assert "keymap" not in idle
    assert idle["(no span open)"] == pytest.approx(290e-9)
    assert got["breakdown"]["device_ops"][0] == [
        "cond.3 (conditional)", pytest.approx(620e-9)]


def test_a_trace_that_lost_its_tail_is_cut_where_it_ends():
    # the runner dispatched 5 steps in the span; the trace holds 2
    got = tr.reduce(_raw(), expected_steps=5)
    assert got["truncated"] and got["steps"] == 2
    assert got["window_s"] == pytest.approx(710e-9)    # last event's end
    assert got["busy_s"] == pytest.approx(620e-9)
    whole = tr.reduce(_raw(), expected_steps=2)
    assert not whole["truncated"] and whole["steps"] == 2
    assert whole["window_s"] == pytest.approx(1000e-9)


def test_a_boundary_program_that_ran_once_is_not_the_step():
    raw = _raw()
    raw["modules"][0].append((360, 990, "jit_boundary"))   # 630 > 260 + 110
    assert tr.reduce(raw)["steps"] == 2
    raw["modules"][0] = [(720, 990, "jit_only")]
    assert tr.reduce(raw)["steps"] == 1


def test_the_execution_the_trace_end_cut_short_is_not_a_step():
    raw = _raw()
    raw["modules"][0].append((900, 905, "jit_step"))   # in flight at the stop
    assert tr.reduce(raw, expected_steps=2)["steps"] == 2


def test_parse_op_reads_name_and_opcode_not_operands():
    text = ("%fusion.2 = f32[8,21]{0,1:T(8,128)} fusion(f32[8]{0} "
            "%all-to-all.3, s32[4]{0} %_sorted_accumulate.1), kind=kCustom")
    assert tr.parse_op(text) == ("fusion.2", "fusion")
    text = ("%cond.1 = (f32[4]{0}, (s32[]{:T(128)})) conditional(s32[] %p, "
            "(f32[4]{0}) %t), branch_computations={%a, %b}")
    assert tr.parse_op(text) == ("cond.1", "conditional")
    assert tr.parse_op("dot_general.1") == ("dot_general.1", "")


def test_reduce_returns_nothing_without_window_or_device():
    raw = _raw()
    assert tr.reduce(dict(raw, host=[])) is None
    assert tr.reduce(dict(raw, devices={0: []})) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="fixture not recorded")
def test_recorded_v5e_fixture():
    raw = tr.read(FIXTURE)
    assert sorted(raw["devices"]) == [0]
    got = tr.reduce(raw)
    assert got is not None and got["devices"] == 1
    # three calls of one small program, a 2 ms sleep after each
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["window_s"] > 3 * 0.002
    assert got["longest_gap_s"] >= 0.002
    # The program is one fusion around the matmul, 11.842 us a call. The
    # trace puts the device's clock about 0.3 ms before the host's, so the
    # first of the three calls lies 56 us before the window mark: two
    # calls are inside. (Windows here are seconds long; 0.3 ms is noise.)
    assert sorted(got["ops"]) == ["convolution_reduce_fusion (fusion)",
                                  "copy-done (copy-done)",
                                  "copy-start (copy-start)"]
    seconds, calls = tr.matching(got, [r"^convolution_reduce_fusion "])
    assert calls == 2 and seconds == pytest.approx(2 * 11.842e-6)
    assert got["busy_s"] == pytest.approx(23.714e-6)
    assert got["window_s"] == pytest.approx(9.224719e-3)
    assert got["steps"] == 2 and not got["truncated"]   # jit_program x 2
