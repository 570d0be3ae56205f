"""One benchmark cell, one run, one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads, warms up, measures for ``--seconds`` (the CTR day cells: for a
count of whole passes their traffic mix sets), checks the outputs and
prints one JSON object as the last line of standard output. Fails,
printing no result, where jax finds no TPU or fewer chips than the cell
asks for.
``--rehearse`` is the benchmark's own switch for control flow: the cell's
``rehearse`` sizes on the CPU backend, ``"metrics": {}``, never a device
number.

Everything that belongs to one cell is found by name (README.md):

    BENCHMARK.json                workloads[name] -> config, traffic, chips
    configs/<config>.json         sizes, and "runner": runners/<runner>.py
    traffic/<traffic>.json        parameters of the mix, read by the runner
    workloads/<cell>.json         program flags (and configuration keys) this
                                  deployment sets
    metrics/<metric>.json         "reader": readers/<reader>.py + parameters
    reference/<config>.py         the plain reference the runner compares to
"""

import argparse
import contextlib
import importlib
import json
import multiprocessing
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def overlay(base: dict, patch: dict) -> dict:
    """``base`` with ``patch`` laid over it, nested groups merged."""
    out = dict(base)
    for key, value in patch.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = overlay(out[key], value)
        else:
            out[key] = value
    return out


class Job:
    """What a runner is handed: the cell's files, the run's arguments, a
    scratch directory inside the checkout, and the harness's clocks,
    compile counter and device trace."""

    def __init__(self, args, cell):
        self.cell_name = cell["name"]
        self.config_name = cell["config"]
        self.chips = int(cell["chips"])
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.config = load_json("configs", cell["config"] + ".json")
        self.traffic = load_json("traffic", cell["traffic"] + ".json")
        flags_file = os.path.join(HERE, "workloads", cell["name"] + ".json")
        self.workload = (load_json(flags_file)
                         if os.path.exists(flags_file) else {})
        if self.rehearse:
            for part in (self.config, self.traffic, self.workload):
                part.update(overlay(part, part.get("rehearse", {})))
        # what this deployment sets differently from its configuration
        self.config = overlay(self.config, self.workload.get("config", {}))
        self.work_dir = os.path.join(HERE, ".cache", "run-" + self.cell_name)
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self.setup_spans = []           # (name, seconds) of set-up phases
        self._compiles = 0
        self._annotation = None
        self._clock = (time.perf_counter(), time.time_ns())

    def unix_ns(self, perf_counter: float) -> float:
        """A ``time.perf_counter()`` reading on the wall clock, which is
        where the program's spans and the device trace meet."""
        perf0, unix0 = self._clock
        return unix0 + (perf_counter - perf0) * 1e9

    def program_spans(self):
        """The program's span ring (``core/trace.py``) as ``(start, end,
        name, thread)`` in unix ns; empty unless the runner enabled it."""
        from paddlebox_tpu.core import trace
        ring = trace.GLOBAL.trace_object()
        anchor = ring["otherData"]["wall_anchor_ns"]
        return [(anchor + e["ts"] * 1e3, anchor + (e["ts"] + e["dur"]) * 1e3,
                 e["name"], e["tid"])
                for e in ring["traceEvents"] if e.get("ph") == "X"]

    # -- set-up phases, on the host clock -------------------------------
    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_spans.append((name, time.perf_counter() - t0))

    # -- programs made since the process started ------------------------
    def count_compiles(self):
        import jax.monitoring

        def on_duration(event, duration_secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self._compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles(self) -> int:
        """Executables compiled or loaded from the cache so far."""
        return self._compiles

    # -- the device trace of a short span --------------------------------
    def start_device_trace(self):
        import jax.profiler
        from benchmarks.trace.reduce import WINDOW_ANNOTATION
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans come from
        options.host_tracer_level = 2       # annotations, not the tracer
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(
            WINDOW_ANNOTATION, unix_ns=time.time_ns())
        self._annotation.__enter__()

    def tracing_now(self) -> bool:
        return self._annotation is not None

    def stop_device_trace(self):
        import jax.profiler
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()


def device_facts(jax, chips, traced, program_temp_bytes=None):
    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    # The runtime's peak leaves out the temporaries XLA allocates inside a
    # program; where the runner could ask the compiler for its step's
    # (``program_temp_bytes``), the larger of the two is the better floor.
    peaks = [p for p in peaks if p is not None]
    if peaks and program_temp_bytes:
        peaks.append(program_temp_bytes)
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices),
             "memory_peak_bytes": max(peaks, default=None)}
    if traced is not None:
        facts["busy_s"] = traced["busy_s"]
        facts["window_s"] = traced["window_s"]
    return facts


def read_metrics(job, result, traced, peaks, entries):
    """Per-layer metrics: each entry's reader, by the name in its file. A
    reader that finds nothing to read returns None, and the metric is left
    out of the line."""
    out = {}
    for entry in entries:
        cells = entry.get("workloads")
        if cells is not None and job.cell_name not in cells:
            continue
        spec = load_json("metrics", entry["name"] + ".json")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(spec.get("params", {}), result["observed"],
                            traced, peaks)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        else:
            # The driver refuses a line that lacks a metric BENCHMARK.json
            # lists for the cell: say which, where its log will show it.
            print(f"run.py: {entry['name']}: reader {spec['reader']!r} found "
                  f"nothing to read in {job.cell_name}; left out of the line",
                  file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend; no metric printed")
    ap.add_argument("--detail", default=None,
                    help="also write everything the run saw to this file")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    cell = cells[args.workload]
    sys.path.insert(0, ROOT)
    job = Job(args, cell)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={job.chips}").strip()
    else:
        # The program's own rule: JAX_COMPILATION_CACHE_DIR if set, else
        # .jax_cache/ at the root of this checkout. Programs that compile
        # in under a second are cached too: a run is a new process, and
        # there are dozens of them.
        from paddlebox_tpu.core import flags
        flags.compilation_cache_dir()
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import jax
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < job.chips):
        print(f"run.py: {args.workload} needs {job.chips} TPU chip(s); jax "
              f"found {len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    job.count_compiles()

    shutil.rmtree(job.work_dir, ignore_errors=True)   # a killed run's rest
    os.makedirs(job.work_dir)
    runner = importlib.import_module(
        "benchmarks.runners." + job.config["runner"])
    try:
        result = runner.run(job)
        traced = structure = None
        if job.trace:
            from benchmarks.trace import reduce as tr
            path = tr.find_xplane(job.trace_dir)
            if path is not None:
                traced = tr.reduce(
                    tr.read(path), result["observed"]["program_spans"],
                    result["observed"].get("traced_steps"))
                if args.detail:
                    structure = tr.structure(path)
        facts = device_facts(jax, job.chips, traced,
                             result.get("program_temp_bytes"))
        result["observed"]["memory_peak_bytes"] = facts["memory_peak_bytes"]
    finally:
        shutil.rmtree(job.work_dir, ignore_errors=True)
        for child in multiprocessing.active_children():
            child.terminate()           # nothing outlives the run
            child.join()

    setup_s = result["window_open"] - T_START
    if args.rehearse:
        metrics = {}
    elif job.trace:
        peaks = load_json("trace", "peaks.json").get(facts["kind"])
        if peaks is None:
            print(f"run.py: no peaks recorded for device_kind "
                  f"{facts['kind']!r} in trace/peaks.json", file=sys.stderr)
            return 2
        metrics = read_metrics(job, result, traced, peaks,
                               manifest["per_layer"])
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        metrics = {}
        for entry in manifest["end_to_end"]:
            if entry["name"] in values:
                metrics[entry["name"]] = {"value": values[entry["name"]],
                                          "unit": entry["unit"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": facts}
    if traced is not None:
        line["breakdown"] = traced["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    compared = result.get("compared")
    if compared:
        # each number that decided ``correct`` beside its limit: the last
        # lines of standard error and the last key of the line
        for name, c in compared.items():
            print(f"run.py: compared {name} = {c['value']!r}, limit "
                  f"{c['limit']!r}", file=sys.stderr)
        line["compared"] = compared
    if args.detail:
        os.makedirs(os.path.dirname(os.path.abspath(args.detail)),
                    exist_ok=True)
        with open(args.detail, "w") as f:
            json.dump({"line": line, "setup_s": setup_s,
                       "setup_spans": job.setup_spans,
                       "end_to_end": result["end_to_end"],
                       "detail": result["detail"],
                       "counters": result["observed"]["counters"],
                       "trace_structure": structure,
                       "traced": None if traced is None else {
                           k: v for k, v in traced.items()
                           if k != "ops"},
                       "top_ops": None if traced is None else sorted(
                           traced["ops"].items(),
                           key=lambda kv: -kv[1][0])[:60]},
                      f, indent=1, default=str)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
