"""Run ledger: atomic append/replay, schema validation, and the regression
gate."""

import json
import os

import pytest

from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    atomic_write_json,
    check_regression,
    config_hash,
    env_fingerprint,
    render_report,
    validate_bench_payload,
)


def bench_payload(value=100.0, **over):
    p = {
        "metric": "word2vec_words_per_sec_per_chip",
        "value": value,
        "unit": "words/sec/chip",
        "config": {"vocab": 1000, "dim": 8},
        "path": "dense",
        "platform": "tpu",
    }
    p.update(over)
    return p


# ------------------------------------------------------------ append/replay


def test_append_replay_roundtrip(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    r1 = led.append("bench", {"payload": bench_payload()}, env={"jax": "x"})
    r2 = led.append("outage", {"probe_duration_s": 12.5, "rc": 1, "error": "e"})
    assert r1["schema"] == 1 and r1["kind"] == "bench" and "ts" in r1
    records, bad = led.replay()
    assert bad == []
    assert [r["kind"] for r in records] == ["bench", "outage"]
    assert records[0]["env"] == {"jax": "x"}
    assert led.latest("outage")["probe_duration_s"] == 12.5
    assert led.latest("run") is None
    # every line on disk is independently parseable (atomic rewrite)
    for line in open(led.path):
        json.loads(line)


def test_replay_skips_corrupt_lines_and_heals_torn_tail(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = Ledger(path)
    led.append("bench", {"payload": bench_payload()})
    # simulate a legacy torn write: garbage + a line without trailing newline
    with open(path, "a") as f:
        f.write('{"broken\n{"kind": "outage"')
    records, bad = led.replay()
    assert len(records) == 1 and len(bad) == 2
    # the next append heals the torn tail instead of concatenating onto it
    led.append("outage", {"error": "x"})
    records, bad = led.replay()
    assert [r["kind"] for r in records] == ["bench", "outage"]


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


# ------------------------------------------------------- payload schema


def test_validate_bench_payload():
    assert validate_bench_payload(bench_payload()) == []
    assert validate_bench_payload([1, 2]) != []
    assert any("metric" in p for p in validate_bench_payload({"value": 1.0}))
    assert validate_bench_payload(bench_payload(value=0.0)) != []
    assert validate_bench_payload(bench_payload(value="fast")) != []


def test_atomic_write_json_replaces_not_appends(tmp_path):
    p = str(tmp_path / "f.json")
    atomic_write_json(p, {"v": 1})
    atomic_write_json(p, {"v": 2})
    assert json.load(open(p)) == {"v": 2}


# ------------------------------------------------------- outage + report


def test_render_report_covers_all_kinds(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    led.append("bench", {"payload": bench_payload(), "cacheable": True,
                         "config_hash": "abcd"})
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
    for needle in ("bench records", "training runs", "outages",
                   "black-box dumps", "mfu=0.41", "nan-loss",
                   "config_hash=abcd", "compute_frac"):
        assert needle in out, f"missing {needle!r} in report:\n{out}"
    assert render_report(Ledger(str(tmp_path / "nope.jsonl"))).endswith(
        "empty or missing ledger")


# --------------------------------------------------------- regression gate


def _measured(led, value, cached=False, reconstructed=False):
    led.append("bench", {"payload": bench_payload(
        value=value, cached=cached, reconstructed=reconstructed)})


def test_check_regression_gate(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    rc, msg = check_regression(led, 10.0)
    assert rc == 2  # nothing measured at all

    _measured(led, 100.0)
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "single measured" in msg

    _measured(led, 95.0)
    assert check_regression(led, 10.0)[0] == 0  # -5% within tolerance
    _measured(led, 80.0)
    rc, msg = check_regression(led, 10.0)
    assert rc == 1 and "REGRESSION" in msg
    # explicit pinned baseline overrides the ledger-derived one
    assert check_regression(led, 10.0, baseline=85.0)[0] == 0
    # cached/reconstructed emissions and CPU smoke runs never count
    _measured(led, 200.0, cached=True)
    _measured(led, 200.0, reconstructed=True)
    led.append("bench", {"payload": bench_payload(value=1.0, platform="cpu")})
    assert check_regression(led, 10.0)[0] == 1  # newest measured is still 80


def test_ledger_report_cli_roundtrip(tmp_path, capsys):
    from swiftsnails_tpu.telemetry.ledger import main

    path = str(tmp_path / "ledger.jsonl")
    led = Ledger(path)
    _measured(led, 100.0)
    _measured(led, 50.0)
    assert main([path]) == 0
    assert "bench records" in capsys.readouterr().out
    assert main([path, "--check-regression", "10"]) == 1
    assert main([path, "--check-regression", "60"]) == 0
    # --baseline-file: pin via a preserved last-good payload
    base = tmp_path / "pin.json"
    base.write_text(json.dumps(bench_payload(value=55.0)))
    assert main([path, "--check-regression", "10",
                 "--baseline-file", str(base)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{")
    assert main([path, "--check-regression", "10",
                 "--baseline-file", str(bad)]) == 2
    # parseable but not a bench payload: rejected by the schema, with the
    # missing keys named
    incomplete = tmp_path / "incomplete.json"
    incomplete.write_text(json.dumps({"metric": "m"}))
    capsys.readouterr()
    assert main([path, "--check-regression", "10",
                 "--baseline-file", str(incomplete)]) == 2
    assert "missing required key 'value'" in capsys.readouterr().out
    assert main([path, "--check-regression", "10", "--baseline-file",
                 str(tmp_path / "missing.json")]) == 2
