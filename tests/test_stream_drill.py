"""Streaming kill -9 crash drill (ONLINE.md crash-window table): a real
training process dies at each ``stream/*`` faultpoint, restarts, and
must converge to BYTE-identical state with a never-killed reference —
resume-from-cursor loses no event and trains none twice."""

import json
import os
import subprocess
import sys

import pytest

import tests.stream_drill_worker as worker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SITES = [("stream/source_poll", 1),
         ("stream/cursor_commit", 2),
         ("stream/delta_publish", 1)]


def _run_worker(log, out, result, *, fault_spec="", timeout=240.0,
                log_path="", mode="segments"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["FLAGS_fault_spec"] = fault_spec
    logf = open(log_path, "ab") if log_path else subprocess.DEVNULL
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tests", "stream_drill_worker.py"),
             log, out, result, mode],
            env=env, cwd=REPO, timeout=timeout,
            stdout=logf, stderr=subprocess.STDOUT)
    finally:
        if log_path:
            logf.close()
    return proc.returncode


@pytest.fixture(scope="module")
def drill_env(tmp_path_factory):
    """Fixed event log + the uninterrupted reference run."""
    workdir = tmp_path_factory.mktemp("stream_drill")
    log = str(workdir / "events")
    worker.write_events(log)
    result = str(workdir / "ref.json")
    rc = _run_worker(log, str(workdir / "ref_out"), result,
                     log_path=str(workdir / "ref.log"))
    assert rc == 0, f"reference run failed rc={rc} (see {workdir}/ref.log)"
    with open(result) as f:
        return workdir, log, json.load(f)


# One kill point stays in tier-1; the others (7-12 s of subprocess drill
# each) ride the slow tier so tier-1 keeps its 870 s window.
@pytest.mark.parametrize(
    "site,hit",
    [pytest.param(s, h, marks=() if i == 1 else pytest.mark.slow)
     for i, (s, h) in enumerate(SITES)],
    ids=[f"{s.replace('/', '_')}_h{h}" for s, h in SITES])
def test_kill9_stream_resumes_exactly_once(drill_env, site, hit):
    workdir, log, ref = drill_env
    tag = site.replace("/", "_") + f"_h{hit}"
    out = str(workdir / f"out_{tag}")
    result = str(workdir / f"result_{tag}.json")
    logp = str(workdir / f"{tag}.log")

    rc = _run_worker(log, out, result,
                     fault_spec=f"{site}:hit={hit}:kill", log_path=logp)
    assert rc == -9, f"faultpoint {site} hit={hit} never killed (rc={rc})"
    assert not os.path.exists(result)  # died before finishing

    rc2 = _run_worker(log, out, result, log_path=logp)
    assert rc2 == 0, f"resume run failed rc={rc2} (see {logp})"
    with open(result) as f:
        drilled = json.load(f)

    # Byte-identical final model: a lost event would change params, a
    # double-trained one would change optimizer state/show counts.
    for k in ("num_features", "store_digest", "dense_digest", "records"):
        assert drilled[k] == ref[k], (site, hit, k)
    # Exactly-once event accounting from the durable cursor: every log
    # file in exactly one manifest, total events == the written log.
    files = [f for m in drilled["manifests"] for f in m["files"]]
    assert len(files) == len(set(files)) == worker.FILES
    assert sum(m["events"] for m in drilled["manifests"]) == \
        worker.FILES * worker.BS
    assert drilled["manifests"] == ref["manifests"]


@pytest.mark.slow  # 12 s subprocess drill per kill point (see above)
@pytest.mark.parametrize("site,hit",
                         [("stream/cursor_commit", 2),
                          ("stream/delta_publish", 1)],
                         ids=["cursor_commit_h2", "delta_publish_h1"])
def test_kill9_tail_mode_mid_file_cut(tmp_path, site, hit):
    """Byte-offset cursor drill (FLAGS_stream_tail_bytes): ONE growing
    file consumed in mid-file byte ranges; kill -9 at a cut, resume —
    no event lost or duplicated at the cut, final state byte-identical
    to a never-killed run over the same append schedule."""
    from paddlebox_tpu.data.dataset import split_byte_range

    log = str(tmp_path / "events")
    ref_result = str(tmp_path / "ref.json")
    rc = _run_worker(log, str(tmp_path / "ref_out"), ref_result,
                     mode="tail", log_path=str(tmp_path / "ref.log"))
    assert rc == 0
    with open(ref_result) as f:
        ref = json.load(f)

    log2 = str(tmp_path / "events2")
    out = str(tmp_path / "out")
    result = str(tmp_path / "result.json")
    logp = str(tmp_path / "drill.log")
    rc = _run_worker(log2, out, result, mode="tail",
                     fault_spec=f"{site}:hit={hit}:kill", log_path=logp)
    assert rc == -9, f"{site} hit={hit} never killed (rc={rc})"
    rc2 = _run_worker(log2, out, result, mode="tail", log_path=logp)
    assert rc2 == 0, f"resume failed rc={rc2} (see {logp})"
    with open(result) as f:
        drilled = json.load(f)

    for k in ("num_features", "store_digest", "dense_digest", "records"):
        assert drilled[k] == ref[k], (site, hit, k)
    # The manifests tile the file's bytes EXACTLY once: contiguous
    # disjoint [start, end) ranges from 0 to the final size, and the
    # event totals are exact — nothing lost or duplicated at the cut.
    ranges = sorted(split_byte_range(f)[1:]
                    for m in drilled["manifests"] for f in m["files"])
    assert ranges[0][0] == 0
    for (s0, e0), (s1, _e1) in zip(ranges, ranges[1:]):
        assert e0 == s1, f"gap/overlap at byte {e0}->{s1}"
    assert ranges[-1][1] == os.path.getsize(
        os.path.join(log2, "live.log"))
    assert sum(m["events"] for m in drilled["manifests"]) == \
        worker.TAIL_STAGES * worker.BS
