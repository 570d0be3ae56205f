"""chip_smoke.py, the chip bring-up check, rehearsed on the CPU.

The real run needs a TPU and is made through the builder's chip tool;
here the explicit rehearsal argument drives every phase at toy size
(Pallas kernels interpreted, 2 virtual devices) in a subprocess, and the
no-argument form must refuse to run without a TPU before doing any work.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_rehearsal_runs_every_phase(tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, "--rehearse-cpu", "--out", str(tmp_path)],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    report = json.loads(lines[-2])
    with open(tmp_path / "chip_smoke_rehearsal.json") as f:
        assert json.load(f)["ok"] is True
    assert report["ok"] is True and report["rehearsal"] is True
    assert report["platform"] == "cpu"
    assert list(report["phases"]) == [
        "identity", "kernels", "deepfm", "predict", "gpt", "nemotron"]
    assert all(p["ok"] for p in report["phases"].values())
    deepfm = report["phases"]["deepfm"]
    assert deepfm["boundary_fused"] == 1
    assert deepfm["resolved_kernels"] == {
        "sparse_gather": ["interpret"], "sparse_scatter": ["interpret"]}
    assert deepfm["xla_run_a"]["resolved_kernels"]["sparse_gather"] == [
        "xla"]
    assert all(p["kernel_fallback"] == 0 and p["lookup_overflow"] == 0
               for p in deepfm["passes"])
    assert report["phases"]["predict"]["bit_identical_to_direct_predict"]
    nemotron = report["phases"]["nemotron"]
    assert nemotron["resolved_kernels"]["nemotron_ssd"] == ["interpret"]
    assert nemotron["losses"][1] < nemotron["losses"][0]
    assert set(nemotron["ssd_scan"]["rel_err"]) == {
        "y", "dx", "ddt", "da", "db", "dc", "dd"}
    # The rehearsal never uses the persistent compile cache.
    assert report["phases"]["identity"]["compile_cache_dir"] is None


def test_refuses_to_run_without_a_tpu(tmp_path):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path)], env=_env(),
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line
    assert "needs a TPU" in proc.stderr
    assert not os.listdir(tmp_path)             # no report, no work
    assert time.monotonic() - t0 < 60
