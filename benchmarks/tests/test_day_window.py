"""The CTR day cells' window: it closes after ``window_passes`` whole
passes whatever their wall time, every pass in a run trains its own set of
files, and ``plan`` refuses a mix whose warm-up and window need more sets
than the mix writes. A stub day loop stands in for the program's: it hands
the runner's hook each pass boundary on a clock the test moves."""

import contextlib
import json
import os
import types

import pytest

from benchmarks.generators import ctr_pass_files as gen
from benchmarks.runners import ctr_day

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MIXES = ("day_uniform", "day_zipf", "day_uniform_dp4")
CHIPS = {"day_uniform": 1, "day_zipf": 1, "day_uniform_dp4": 4}
# seconds a pass: a program far faster than today's (the window would have
# held dozens of passes and wrapped the files), one far slower (it would
# have closed after one), and dp4's cycle of short, medium and long passes
WALLS = {"fast": lambda k: 0.5, "slow": lambda k: 60.0,
         "cycling": lambda k: (13.0, 25.0, 40.0)[k % 3]}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def perf_counter(self):
        return self.t


class _Job:
    """What ``ctr_day.run`` reads of ``run.Job``, with the device trace
    recorded by the pass it spans."""

    def __init__(self, mix, work_dir, trace=False):
        self.config = _load("configs", "deepfm_criteo.json")
        self.traffic = _load("traffic", mix + ".json")
        self.chips, self.seed, self.trace = CHIPS[mix], 2 ** 31 + 11, trace
        self.seconds = 40.0
        self.workload, self.work_dir = {}, str(work_dir)
        self.config_name = "deepfm_criteo"
        self.traced, self._tracing = [], None
        self.boundary = 0               # pass whose end the hook last saw
        # a sound pass's AUC: the planted signal's ceiling
        self.auc = gen.auc_ceiling(
            gen.plan(self.traffic, self.config, self.chips), self.seed)

    @contextlib.contextmanager
    def span(self, name):
        yield

    def compiles(self):
        return 0

    def tracing_now(self):
        return self._tracing is not None

    def start_device_trace(self):
        self._tracing = self.boundary + 1       # the pass that starts now

    def stop_device_trace(self):
        self.traced.append(self._tracing)
        self._tracing = None

    def program_spans(self):
        return []

    def unix_ns(self, t):
        return t * 1e9


def _day_loop(job, clock, wall, trained):
    """A ``DayRunner`` that trains nothing: each pass takes ``wall(pass)``
    seconds on ``clock``, leaves a pass report and calls the hook."""
    from paddlebox_tpu.checkpoint.protocol import get_online_pass_interval
    from paddlebox_tpu.core import report

    class DayLoop:
        def __init__(self, trainer, feed, out_dir, *, split_interval,
                     pass_boundary_hook, filelist_fn, **_):
            self.hook, self.files = pass_boundary_hook, filelist_fn
            self.splits = get_online_pass_interval(range(24),
                                                   split_interval, 1)
            self.batches = job.traffic["pass_batches"] * job.chips

        def train_day(self, day):
            for pass_id, splits in enumerate(self.splits, start=1):
                files = self.files(day, splits)
                trained.append(os.path.basename(os.path.dirname(files[0])))
                clock.t += wall(pass_id)
                report.LAST_PASS_REPORT = {
                    "steps": self.batches, "loss": 0.5, "auc": job.auc,
                    "lookup_overflow": 0, "kernel_fallback": 0,
                    "lookup_exchange_bytes": 0}
                job.boundary = pass_id
                self.hook(day, pass_id)
    return DayLoop


def _run(monkeypatch, job, wall):
    """``ctr_day.run`` over the stub loop; returns its result, the pass
    file sets in the order they trained and the held-out check's pass."""
    import paddlebox_tpu.train.day_runner as day_runner
    from paddlebox_tpu.core import report, trace

    clock, trained, held_pass = _Clock(), [], []
    batch = job.config["batch_per_chip"] * job.chips
    pool = types.SimpleNamespace(
        map_async=lambda fn, tasks, chunksize: types.SimpleNamespace(
            get=lambda: None),
        close=lambda: None, join=lambda: None)
    monkeypatch.setattr(ctr_day, "multiprocessing", types.SimpleNamespace(
        get_context=lambda kind: types.SimpleNamespace(
            Pool=lambda n: pool)))
    monkeypatch.setattr(ctr_day, "time", clock)
    monkeypatch.setattr(ctr_day, "_trainer", lambda c, n, s: (
        types.SimpleNamespace(engine=types.SimpleNamespace(groups=[
            types.SimpleNamespace(engine=types.SimpleNamespace(
                store=None))])),
        types.SimpleNamespace(batch_size=batch)))
    monkeypatch.setattr(ctr_day, "_fill_store", lambda *a: None)
    tol = {k: 1.0 for k in ctr_day.EVAL_TOL}

    def held_out(job, gen, trainer, store, feed, p, pass_idx, data_dir):
        held_pass.append(pass_idx)
        return {"ok": True, "diff": {k: 0.0 for k in tol}, "tol": tol}
    monkeypatch.setattr(ctr_day, "_held_out_check", held_out)
    monkeypatch.setattr(day_runner, "DayRunner",
                        _day_loop(job, clock, wall, trained))
    monkeypatch.setattr(report, "LAST_PASS_REPORT", None)
    monkeypatch.setattr(trace.GLOBAL, "enable", lambda **_: None)
    return ctr_day.run(job), trained, held_pass


@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("mix", MIXES)
def test_window_closes_after_its_passes(monkeypatch, tmp_path, mix, walls):
    job = _Job(mix, tmp_path)
    warmup = job.traffic["warmup_passes"]
    window = job.traffic["window_passes"]
    result, trained, held_pass = _run(monkeypatch, job, WALLS[walls])

    detail = result["detail"]
    assert detail["passes"] == window == result["observed"]["passes"]
    want = [WALLS[walls](k) for k in range(warmup + 1, warmup + window + 1)]
    assert detail["pass_walls_s"] == pytest.approx(want)
    assert detail["wall_s"] == pytest.approx(sum(want))
    # the day stopped at the boundary that closed the window
    assert len(trained) == warmup + window
    # every pass of the run trained a set of files of its own
    assert len(set(trained)) == len(trained)
    assert held_pass == [warmup + window - 1]
    batches = job.traffic["pass_batches"] * job.chips
    assert result["attempted"] == window * batches
    rate = (window * batches * job.config["batch_per_chip"] * job.chips
            / sum(want) / job.chips)
    assert result["end_to_end"]["ctr_samples_per_s_per_chip"] == \
        pytest.approx(rate)
    assert result["correct"] is True
    assert list(result["compared"]) == [
        "auc_last_pass", "failed_steps", "lookup_overflow",
        *(f"held_out_{k}_gap" for k in ctr_day.EVAL_TOL)]


@pytest.mark.parametrize("mix", MIXES)
def test_traced_pass_is_the_windows_second(monkeypatch, tmp_path, mix):
    job = _Job(mix, tmp_path, trace=True)
    _run(monkeypatch, job, WALLS["cycling"])
    assert job.traced == [job.traffic["warmup_passes"] + 2]


@pytest.mark.parametrize("mix", MIXES)
def test_plan_refuses_a_window_the_files_cannot_hold(mix):
    traffic = _load("traffic", mix + ".json")
    config = _load("configs", "deepfm_criteo.json")
    gen.plan(traffic, config, CHIPS[mix])       # the mix as committed
    n, warmup = traffic["distinct_passes"], traffic["warmup_passes"]
    over = dict(traffic, window_passes=n - warmup + 1)
    with pytest.raises(ValueError) as raised:
        gen.plan(over, config, CHIPS[mix])
    said = str(raised.value)
    assert (f"warmup_passes {warmup} + window_passes {n - warmup + 1} = "
            f"{n + 1} passes, more than distinct_passes {n}") in said
    with pytest.raises(ValueError, match="window_passes 1 at least 2"):
        gen.plan(dict(traffic, window_passes=1), config, CHIPS[mix])
