"""``run.py`` end to end: every cell's rehearsal on the CPU backend, the
refusal without a TPU, and that a configuration, a traffic mix and a
per-layer metric are found when they are added as new files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _run(root, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(cell, trace, tmp_path):
    if trace and cell != CELLS[0] and not cell.startswith("gpt2"):
        pytest.skip("one traced rehearsal per runner")
    detail = tmp_path / "detail.json"
    done = _run(ROOT, "--workload", cell, "--seed", "3", "--seconds", "2",
                "--trace", str(trace), "--rehearse", "--detail", str(detail))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    chips = {w["name"]: w["chips"] for w in MANIFEST["workloads"]}[cell]
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": None}
    with open(detail) as f:
        seen = json.load(f)
    if cell.startswith("deepfm"):
        assert seen["detail"]["held_out"]["ok"], seen["detail"]["held_out"]
        assert seen["detail"]["passes"] >= 1
    else:
        assert abs(seen["detail"]["first_step_loss"]
                   - seen["detail"]["reference_loss"]) \
            <= seen["detail"]["loss_tol"]
    assert not os.path.exists(os.path.join(BENCH, ".cache", "run-" + cell))


def test_fails_and_prints_nothing_without_a_tpu():
    done = _run(ROOT, "--workload", CELLS[-1], "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode not in (0, None)
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr


def test_unknown_workload_is_refused():
    done = _run(ROOT, "--workload", "no.such_cell", "--rehearse")
    assert done.returncode == 2 and done.stdout.strip() == ""


def test_new_files_are_found_without_editing_any(tmp_path):
    """A later PR's configuration, traffic mix, workload flags, reference
    and per-layer metric with its reader, added as files plus
    BENCHMARK.json entries, in a copy of the tree."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "paddlebox_tpu"), root / "paddlebox_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    bench = root / "benchmarks"
    with open(bench / "configs" / "gpt2_medium.json") as f:
        config = json.load(f)
    config.update(name="made_up_gpt", sequences_per_chip=3)
    config["rehearse"].update(n_layer=1, n_embd=32, n_inner=64)
    (bench / "configs" / "made_up_gpt.json").write_text(json.dumps(config))
    (bench / "reference" / "made_up_gpt.py").write_text(
        "from benchmarks.reference.gpt2_medium import loss  # noqa: F401\n")
    (bench / "traffic" / "train_s64.json").write_text(json.dumps(
        {"sequence_length": 1024, "traced_steps": 2,
         "rehearse": {"sequence_length": 64}}))
    (bench / "workloads" / "made_up_gpt.train_s64.json").write_text(
        json.dumps({"flags": {"flash_block_q": 256}}))
    (bench / "readers" / "window_steps.py").write_text(
        "def read(params, observed, traced, peaks):\n"
        "    return observed['steps'] * params['scale']\n")
    (bench / "metrics" / "made_up.steps_x10.json").write_text(json.dumps(
        {"reader": "window_steps", "params": {"scale": 10}}))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(
        {"name": "made_up_gpt", "source": "test", "reduced": [], "why": "t",
         "file": "benchmarks/configs/made_up_gpt.json"})
    manifest["workloads"].append(
        {"name": "made_up_gpt.train_s64", "config": "made_up_gpt",
         "traffic": "train_s64", "chips": 1, "why": "t"})
    manifest["per_layer"].append(
        {"name": "made_up.steps_x10", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "entry",
         "moves": "dense_tokens_per_s_per_chip",
         "workloads": ["made_up_gpt.train_s64"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    done = _run(str(root), "--workload", "made_up_gpt.train_s64", "--seed",
                "1", "--seconds", "1", "--trace", "0", "--rehearse",
                "--detail", str(tmp_path / "d.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0

    # the metric's reader, found by the name in its file
    sys.path.insert(0, str(root))
    try:
        for name in [m for m in sys.modules if m.split(".")[0]
                     in ("benchmarks", "run")]:
            del sys.modules[name]
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "made_up_run", str(bench / "run.py"))
        run = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run)

        class FakeJob:
            cell_name = "made_up_gpt.train_s64"
        got = run.read_metrics(FakeJob(), {"observed": {"steps": 7}}, None,
                               {}, manifest["per_layer"])
        assert got == {"made_up.steps_x10": {"value": 70, "unit": "steps"}}
    finally:
        sys.path.remove(str(root))
        for name in [m for m in sys.modules
                     if m.split(".")[0] == "benchmarks"]:
            del sys.modules[name]
    after = {p: p.read_bytes() for p in before}
    assert after == before              # nothing that was there changed
