"""bench.py's device rule and its ledger record: no chip -> non-zero exit and
no result line; an explicit JAX_PLATFORMS=cpu run says ``platform: cpu`` on
every line; the deadline watchdog fails the run; a completed run appends one
bench record and nothing ever reads a result back out to print it again."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from swiftsnails_tpu.telemetry.ledger import Ledger


@pytest.fixture()
def isolated_bench(tmp_path, monkeypatch):
    """Point bench's ledger at a tmp dir and reset the one-shot emit latch,
    the error list and the device fields."""
    monkeypatch.setattr(bench, "LEDGER_PATH", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setattr(bench, "_emitted", False)
    monkeypatch.setitem(bench._state, "errors", [])
    for key in ("platform", "device_kind", "device_count"):
        monkeypatch.setitem(bench._state, key, None)
    # the cache rule is tested in test_chip_smoke; keep this process's jax
    # config untouched here
    from swiftsnails_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    return tmp_path


def test_no_chip_and_no_explicit_cpu_fails_without_a_result(
        isolated_bench, monkeypatch, capsys):
    # the test process runs on CPU devices; with JAX_PLATFORMS unset that is
    # exactly "no chip found", which must not fall through to a CPU number
    monkeypatch.delenv("JAX_PLATFORMS")
    rc = bench.main(["--lane", "serve"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""  # no result line, so no "value"
    assert "value" not in out
    assert "platform is 'cpu'" in err and "refusing to measure" in err
    # nothing was recorded as a run either
    assert Ledger(bench.LEDGER_PATH).latest("bench") is None


def test_require_device_accepts_explicit_cpu_and_names_it(
        isolated_bench, monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.require_device()
    assert bench._state["platform"] == "cpu"
    assert bench._state["device_count"] >= 8
    bench._say("hello")
    assert "platform: cpu" in capsys.readouterr().err
    payload = json.loads(bench._result_json())
    assert payload["platform"] == "cpu"
    assert payload["device"] == {
        "platform": "cpu", "kind": bench._state["device_kind"],
        "count": bench._state["device_count"]}


def test_no_replay_path_left():
    # the probe child, the cached emission and the reconstructed file are
    # gone: nothing in bench.py can print a number it did not just measure
    for name in ("probe_accelerator", "_emit_cached_fallback",
                 "_save_last_good", "LAST_GOOD_PATH", "PROBE_DEADLINE_S"):
        assert not hasattr(bench, name), name
    src = open(bench.__file__).read()
    assert "cached" not in src and "reconstructed" not in src
    assert "subprocess" not in src  # one process per chip: no children
    root = os.path.dirname(bench.__file__)
    assert not os.path.exists(os.path.join(root, "BENCH_LAST_GOOD.json"))


def test_deadline_watchdog_fails_the_run(isolated_bench, monkeypatch, capsys):
    exits = []
    monkeypatch.setattr(bench.os, "_exit", lambda rc: exits.append(rc))
    monkeypatch.setitem(bench._state, "paths", {"dense": 1234.5})
    monkeypatch.setitem(bench._state, "best", 1234.5)
    bench._deadline()
    out, err = capsys.readouterr()
    assert exits == [1]  # even with a best-so-far in hand
    assert out.strip() == ""
    assert "deadline" in err and "dense" in err


def test_record_run_appends_one_bench_record(isolated_bench, monkeypatch):
    monkeypatch.setitem(bench._state, "best", 999999.0)
    monkeypatch.setitem(bench._state, "best_path", "dense")
    monkeypatch.setitem(bench._state, "platform", "cpu")
    monkeypatch.setitem(bench._state, "device_kind", "cpu")
    bench._record_run()
    led = Ledger(bench.LEDGER_PATH)
    recs = led.records("bench")
    assert len(recs) == 1
    rec = recs[0]
    assert rec["payload"]["value"] == 999999.0
    assert rec["payload"]["platform"] == "cpu"
    assert "measured_at" in rec["payload"]
    assert "cacheable" not in rec
    assert "env" in rec and len(rec["config_hash"]) == 16
    assert rec["env"]["devices"]["platform"] == "cpu"
