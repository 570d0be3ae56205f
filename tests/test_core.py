"""Core runtime tests: flags, monitor, timers (SURVEY.md §2.7 config core)."""

import os

import pytest

from paddlebox_tpu.core import flags, monitor, timers


def test_flag_define_get_set():
    flags.define_flag("test_flag_a", 7, "test int flag")
    assert flags.get_flags("test_flag_a") == {"test_flag_a": 7}
    flags.set_flags({"test_flag_a": 11})
    assert flags.flag("test_flag_a") == 11


def test_flag_env_override():
    os.environ["FLAGS_test_flag_env"] = "42"
    flags.define_flag("test_flag_env", 1, "env-overridable")
    assert flags.flag("test_flag_env") == 42
    # Explicit set wins over env after the fact.
    flags.set_flags({"test_flag_env": 5})
    assert flags.flag("test_flag_env") == 5


def test_flag_bool_parse():
    os.environ["FLAGS_test_flag_bool"] = "true"
    flags.define_flag("test_flag_bool", False, "bool flag")
    assert flags.flag("test_flag_bool") is True


def test_flag_type_check():
    flags.define_flag("test_flag_typed", 1.5)
    flags.set_flags({"test_flag_typed": 2})  # int coerced to float
    assert flags.flag("test_flag_typed") == 2.0
    with pytest.raises(flags.FlagError):
        flags.set_flags({"test_flag_typed": [1]})


def test_builtin_flags_present():
    vals = flags.get_flags(["check_nan_inf", "auc_num_buckets",
                            "padbox_max_shuffle_wait_count"])
    assert vals["auc_num_buckets"] == 1 << 20
    assert vals["check_nan_inf"] is False


def test_resolved_kernels_records_what_ran():
    flags.resolved_kernels(reset=True)
    flags.note_kernel("site_a", "xla")
    flags.note_kernel("site_a", "pallas")
    flags.note_kernel("site_a", "xla")
    flags.note_kernel("site_b", "xla:width>128")
    assert flags.resolved_kernels(reset=True) == {
        "site_a": ["pallas", "xla"], "site_b": ["xla:width>128"]}
    assert flags.resolved_kernels() == {}


def test_compilation_cache_dir_rule(monkeypatch):
    """The environment's directory wins and nothing else is set; unset,
    the cache is one fixed path inside the checkout."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert flags.compilation_cache_dir() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        d = flags.compilation_cache_dir()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert d == os.path.join(repo, ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == d
        assert jax.config.jax_compilation_cache_dir == d
        assert flags.compilation_cache_dir() == d       # and stays put
    finally:
        # Tests keep the persistent cache off (CPU executables).
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


def test_monitor_counters():
    monitor.reset()
    monitor.add("ins_num", 100)
    monitor.add("ins_num", 28)
    monitor.set_stat("epoch", 3)
    snap = monitor.snapshot()
    assert snap["ins_num"] == 128
    assert snap["epoch"] == 3


def test_timer_accumulates():
    t = timers.Timer()
    with t.scope():
        pass
    with t.scope():
        pass
    assert t.count == 2
    assert t.elapsed_sec >= 0.0


def test_timer_group_report():
    g = timers.TimerGroup()
    with g.scope("pull"):
        pass
    with g.scope("push"):
        pass
    rep = g.report()
    assert "pull=" in rep and "push=" in rep


def test_monitor_float_gauges_do_not_truncate():
    monitor.reset()
    monitor.set_gauge("ratio", 0.75)
    monitor.add("float_counter", 0.5)   # float deltas survive too
    monitor.add("float_counter", 0.25)
    assert monitor.get_gauge("ratio") == 0.75
    snap = monitor.snapshot()           # flat back-compat view
    assert snap["ratio"] == 0.75
    assert snap["float_counter"] == 0.75


def test_monitor_histogram_fixed_buckets():
    monitor.reset()
    monitor.define_histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        monitor.observe("lat_ms", v)
    h = monitor.snapshot_all()["histograms"]["lat_ms"]
    assert h["counts"] == [1, 1, 1, 1]  # one per bucket + overflow
    assert h["count"] == 4 and h["min"] == 0.5 and h["max"] == 500.0
    # Redefining with different buckets must fail loudly.
    with pytest.raises(ValueError):
        monitor.define_histogram("lat_ms", buckets=(2.0, 4.0))


def test_monitor_snapshot_all_labeled_and_jsonl(tmp_path):
    import json
    monitor.reset()
    monitor.add("c", 3)
    monitor.set_gauge("g", 1.25)
    monitor.observe("h", 7.0)
    snap = monitor.snapshot_all({"kind": "test"})
    assert snap["labels"] == {"kind": "test"}
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.25
    path = str(tmp_path / "m.jsonl")
    monitor.flush_jsonl(path, {"n": 1})
    monitor.flush_jsonl(path, {"n": 2})
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert len(lines) == 2
    assert lines[1]["labels"] == {"n": 2}
    assert lines[0]["histograms"]["h"]["count"] == 1


def test_monitor_flush_thread(tmp_path):
    import time as _time
    monitor.reset()
    monitor.add("tick", 1)
    path = str(tmp_path / "bg.jsonl")
    try:
        assert monitor.start_flush_thread(path, interval_s=0.05)
        _time.sleep(0.2)
    finally:
        monitor.stop_flush_thread()
    assert len(open(path).read().splitlines()) >= 1
    # Disarmed after stop: flush with no explicit path is a no-op.
    assert monitor.flush_jsonl() is None


def test_timer_group_publishes_into_registry():
    monitor.reset()
    g = timers.TimerGroup()
    with g.scope("train"):
        pass
    g["fwd_bwd"].add_elapsed(0.25)
    g.publish("day")
    snap = monitor.snapshot()
    assert snap["day/train_ms"] >= 0.0
    assert snap["day/train_count"] == 1
    assert abs(snap["day/fwd_bwd_ms"] - 250.0) < 1e-6
    assert g.report_dict()["fwd_bwd"]["count"] == 1
