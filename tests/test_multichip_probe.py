"""The multichip probe's stage runner (``__graft_entry__._run_stages``): it
must emit MULTICHIP lines + a JSON summary and write an outage-style ledger
event on failure."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft
from swiftsnails_tpu.telemetry.ledger import Ledger


# ----------------------------------------------- multichip probe harness ---


def test_multichip_stage_runner_success_prints_summary(capsys):
    summary = graft._run_stages(
        [("a", lambda: None), ("b", lambda: "not applicable here")], 4)
    out = capsys.readouterr().out
    assert "MULTICHIP stage=a ok" in out
    assert "MULTICHIP stage=b skip (not applicable here)" in out
    line = [l for l in out.splitlines() if l.startswith("MULTICHIP_SUMMARY ")][-1]
    parsed = json.loads(line.split(" ", 1)[1])
    assert parsed == summary
    assert parsed["ok"] is True and parsed["stages_ok"] == ["a"]
    assert parsed["stages_skipped"] == {"b": "not applicable here"}


def test_multichip_stage_runner_failure_writes_ledger_event(
        tmp_path, monkeypatch, capsys):
    ledger_path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("SSN_LEDGER_PATH", str(ledger_path))

    def boom():
        raise RuntimeError("collective exploded")

    with pytest.raises(RuntimeError):
        graft._run_stages([("ok_stage", lambda: None), ("bad_stage", boom)], 8)
    out = capsys.readouterr().out
    line = [l for l in out.splitlines() if l.startswith("MULTICHIP_SUMMARY ")][-1]
    parsed = json.loads(line.split(" ", 1)[1])
    assert parsed["ok"] is False and parsed["failed_stage"] == "bad_stage"
    assert "collective exploded" in parsed["error"]
    ev = Ledger(str(ledger_path)).latest("outage")
    assert ev is not None and ev["probe"] == "multichip"
    assert ev["failed_stage"] == "bad_stage"
    assert "collective exploded" in ev["error"]
