"""Run ledger: atomic append/replay, the report, and the CLI way in."""

import json
import os

import pytest

from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    atomic_write_json,
    config_hash,
    env_fingerprint,
    render_report,
)

RUN = {"model": "word2vec", "steps": 5, "items": 1280, "config_hash": "abcd"}


# ------------------------------------------------------------ append/replay


def test_append_replay_roundtrip(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    r1 = led.append("run", dict(RUN), env={"jax": "x"})
    led.append("outage", {"probe_duration_s": 12.5, "rc": 1, "error": "e"})
    assert r1["schema"] == 1 and r1["kind"] == "run" and "ts" in r1
    records, bad = led.replay()
    assert bad == []
    assert [r["kind"] for r in records] == ["run", "outage"]
    assert records[0]["env"] == {"jax": "x"}
    assert led.latest("outage")["probe_duration_s"] == 12.5
    assert led.latest("blackbox") is None
    # every line on disk is independently parseable (atomic rewrite)
    for line in open(led.path):
        json.loads(line)


def test_replay_skips_corrupt_lines_and_heals_torn_tail(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = Ledger(path)
    led.append("run", dict(RUN))
    # simulate a legacy torn write: garbage + a line without trailing newline
    with open(path, "a") as f:
        f.write('{"broken\n{"kind": "outage"')
    records, bad = led.replay()
    assert len(records) == 1 and len(bad) == 2
    # the next append heals the torn tail instead of concatenating onto it
    led.append("outage", {"error": "x"})
    records, bad = led.replay()
    assert [r["kind"] for r in records] == ["run", "outage"]


def test_append_is_atomic_no_tmp_litter(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    for i in range(5):
        led.append("run", {"steps": i})
    leftover = [f for f in os.listdir(tmp_path) if f != "ledger.jsonl"]
    assert leftover == []
    assert len(led.records("run")) == 5


# ------------------------------------------------------- fingerprint/hash


def test_env_fingerprint_has_identity_fields():
    fp = env_fingerprint()
    assert "jax" in fp and "python" in fp
    assert "devices" not in fp  # never touches the backend by default
    fp_dev = env_fingerprint(include_devices=True)
    assert fp_dev["devices"]["count"] >= 1  # conftest pins 8 CPU devices
    assert fp_dev["devices"]["platform"] == "cpu"


def test_config_hash_stable_and_order_independent():
    h1 = config_hash({"a": 1, "b": "x"})
    h2 = config_hash({"b": "x", "a": 1})
    h3 = config_hash({"a": 2, "b": "x"})
    assert h1 == h2 != h3
    assert len(h1) == 16


# ------------------------------------------------------- atomic writes


def test_atomic_write_json_replaces_not_appends(tmp_path):
    p = str(tmp_path / "f.json")
    atomic_write_json(p, {"v": 1})
    atomic_write_json(p, {"v": 2})
    assert json.load(open(p)) == {"v": 2}


# ------------------------------------------------------- outage + report


def test_render_report_covers_all_kinds(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    led.append("run", {"model": "word2vec", "steps": 5, "items": 1280,
                       "config_hash": "abcd",
                       "goodput": {"mfu": 0.41, "decomposition":
                                   {"compute_frac": 0.7, "h2d_frac": 0.1,
                                    "host_blocked_frac": 0.05,
                                    "other_frac": 0.01}}})
    led.append("outage", {"probe_duration_s": 300.0, "rc": None, "error": "x"})
    led.append("blackbox", {"reason": "nan-loss", "dump_path": "/tmp/bb.json",
                            "first_step": 3, "last_step": 7})
    out = render_report(led)
    for needle in ("training runs", "outages",
                   "black-box dumps", "mfu=0.41", "nan-loss",
                   "config_hash=abcd", "compute_frac"):
        assert needle in out, f"missing {needle!r} in report:\n{out}"
    assert render_report(Ledger(str(tmp_path / "nope.jsonl"))).endswith(
        "empty or missing ledger")


# -------------------------------------------------------------- the CLI


def test_ledger_report_cli_roundtrip(tmp_path, capsys):
    from swiftsnails_tpu.cli import main

    path = str(tmp_path / "ledger.jsonl")
    led = Ledger(path)
    led.append("run", dict(RUN))
    led.append("outage", {"probe_duration_s": 12.5, "rc": 1, "error": "e"})
    assert main(["ledger-report", path]) == 0
    out = capsys.readouterr().out
    assert "training runs" in out and "outages (1 recorded" in out
    assert main(["ledger-report", path, "--failures"]) == 0
    assert "failure timeline" in capsys.readouterr().out
    # the gates went with the records they judged: their flags are refused
    with pytest.raises(SystemExit):
        main(["ledger-report", path, "--baseline-file", "x.json"])
