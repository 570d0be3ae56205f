"""The CPU multichip dry run, in-process on the suite's 8 virtual devices.

Runs the REAL ``dryrun_multichip`` — sparse CTR, hybrid GPT, MoE,
multislice and remote-PS steps on an 8-device mesh — and pins that it
prints its pre-entry beacon and, when asked for a record, writes every
sub-dryrun's outcome to the path it was given (and nowhere else: the
suite leaves no file in the checkout).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_records_where_told(devices8, capsys, tmp_path):
    path = tmp_path / "multichip.json"
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as graft

        graft.dryrun_multichip(8, record_path=path)
    finally:
        sys.path.remove(REPO)

    out = capsys.readouterr().out
    assert "dryrun_multichip: entered (pid=" in out

    with open(path) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert rec["platform"] == "cpu"
    assert rec["n_devices"] == 8
    names = [s["name"] for s in rec["subs"]]
    assert names == ["ctr", "gpt-hybrid", "moe", "multislice", "remote-ps"]
    assert all(s["ok"] for s in rec["subs"])
    assert not os.path.exists(os.path.join(REPO, "MULTICHIP_LOCAL.json"))
