"""The metrics that read the day loop's inside spans and the flash
kernels' names: the ``span_sum`` reader on hand-made spans, and every
metric file this tree's ``BENCHMARK.json`` lists (a reader that exists,
cells that exist, a span or a kernel name the program really has)."""

import importlib
import json
import os
import re

import pytest

from benchmarks.readers import span_duration, span_sum

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
MS = 1e6                                # ns


def _observed(spans, window=(0, 100 * MS), **counts):
    return {"program_spans": spans, "window_unix_ns": window, **counts}


def test_span_sum_divides_the_total_by_passes_or_steps():
    spans = [(1 * MS, 4 * MS, "store/bucket", 7),
             (10 * MS, 30 * MS, "store/bucket", 8),     # another thread
             (40 * MS, 41 * MS, "store/bucket", 7),
             (50 * MS, 90 * MS, "pass/end_pass", 7)]    # another name
    seen = _observed(spans, passes=2, steps=8)
    assert span_sum.read({"span": "store/bucket", "per": "passes"},
                         seen, None, None) == pytest.approx(12.0)
    assert span_sum.read({"span": "store/bucket", "per": "steps"},
                         seen, None, None) == pytest.approx(3.0)
    # one long instance among short ones: what a median hides
    assert span_duration.read({"span": "store/bucket"}, seen, None,
                              None) == pytest.approx(3.0)


def test_span_sum_drops_spans_that_straddle_the_window():
    spans = [(-5 * MS, 5 * MS, "pass/feed_wait", 1),    # opens before
             (10 * MS, 12 * MS, "pass/feed_wait", 1),
             (95 * MS, 105 * MS, "pass/feed_wait", 1)]  # closes after
    seen = _observed(spans, passes=1)
    assert span_sum.read({"span": "pass/feed_wait", "per": "passes"},
                         seen, None, None) == pytest.approx(2.0)


@pytest.mark.parametrize("seen", [
    _observed([], passes=3),
    _observed([(1 * MS, 2 * MS, "pass/end_pass", 1)], passes=3),
    _observed([(-9 * MS, 2 * MS, "store/bucket", 1)], passes=3),
    _observed([(1 * MS, 2 * MS, "store/bucket", 1)], passes=0),
    _observed([(1 * MS, 2 * MS, "store/bucket", 1)]),
])
def test_span_sum_reads_nothing_where_nothing_is(seen):
    assert span_sum.read({"span": "store/bucket", "per": "passes"}, seen,
                         None, None) is None


def _program_text():
    out = []
    for top, _, files in os.walk(os.path.join(ROOT, "paddlebox_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(top, name)) as f:
                    out.append(f.read())
    return "\n".join(out)


@pytest.fixture(scope="module")
def program_text():
    return _program_text()


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_metric_file_names_a_reader_cells_and_a_source(name, program_text):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    cells = {w["name"] for w in MANIFEST["workloads"]}
    entry = PER_LAYER[name]
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
    params = spec.get("params", {})
    # a span the metric reads is a span the program opens
    spans = [params.get("span")] + [
        params.get(k, {}).get("span") for k in ("from", "to")]
    for span in filter(None, spans):
        assert f'"{span}"' in program_text, span
    if spec["reader"] in ("span_duration", "span_sum", "span_gap"):
        assert entry["source"] == "program_span"
    if spec["reader"] == "span_sum":
        assert params["per"] in ("passes", "steps")
    # a kernel is found by the name of a function of the program
    for patterns in params.get("parts", {}).values():
        for pattern in patterns:
            assert "closed_call" not in pattern
            helper = re.match(r"\^(\w+?)\(", pattern).group(1)
            assert f"def {helper}(" in program_text, helper
            assert re.search(pattern, f"{helper}.12 (custom-call)")
            assert re.search(pattern, f"{helper} (custom-call)")
            assert not re.search(pattern, f"{helper}.12 (fusion)")


def test_flash_roofline_reads_the_three_named_kernels():
    reader = importlib.import_module(
        "benchmarks.readers.trace_kernel_roofline")
    with open(os.path.join(BENCH, "metrics",
                           "flash_attention_roofline.json")) as f:
        params = json.load(f)["params"]
    with open(os.path.join(BENCH, "trace", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    shapes = {"batch_per_chip": 2, "seq": 1024, "n_head": 16,
              "head_dim": 64, "dtype_bytes": 4}
    # ten steps of 24 layers, per-call times of the size PR 22 measured
    ops = {"_flash_fwd_call.8 (custom-call)": (0.061, 240.0),
           "_flash_dq_call.12 (custom-call)": (0.032, 240.0),
           "_flash_dkv_call.12 (custom-call)": (0.051, 240.0),
           "fusion.124 (fusion)": (0.5, 10.0)}
    got = reader.read(params, {"shapes": shapes}, {"ops": ops}, peaks)
    assert 0.0 < got < 100.0
    # an unnamed part (the parent's closed_call.N) makes it absent
    del ops["_flash_dq_call.12 (custom-call)"]
    ops["closed_call.335 (custom-call)"] = (0.032, 240.0)
    assert reader.read(params, {"shapes": shapes}, {"ops": ops},
                       peaks) is None
