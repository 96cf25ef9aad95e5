"""bench.py scaling lane + multichip probe structure + the extended CI gate.

The forced-8-device scaling smoke (tier-1, bounded steps): the lane must
populate a ``scaling`` block with per-comm_dtype aggregate words/sec,
efficiency, audited exchange bytes meeting the payload-reduction bar, and
loss parity; a single device must produce a structured skip reason; the
multichip stage runner must emit MULTICHIP lines + a JSON summary and write
an outage-style ledger event on failure; ``ledger-report
--check-regression`` must gate the scaling aggregate alongside the
headline.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import __graft_entry__ as graft
from swiftsnails_tpu.telemetry.ledger import Ledger, check_regression


@pytest.fixture()
def isolated_bench(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "LEDGER_PATH", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setitem(bench._state, "errors", [])
    monkeypatch.setitem(bench._state, "scaling", None)
    return tmp_path


def _small_workload(vocab=512, tokens=30_000):
    ids = bench.synth_corpus(tokens, vocab, seed=5)
    counts = np.maximum(np.bincount(ids, minlength=vocab), 1).astype(np.int64)
    return counts, ids


def test_scaling_lane_smoke(isolated_bench):
    counts, ids = _small_workload()
    bench.measure_scaling(
        counts, ids, n_devices=8, dim=16, batch_per_shard=64,
        steps_per_call=2, measure_steps=2,
    )
    block = bench._state["scaling"]
    assert block and "skipped" not in block
    assert block["n_devices"] == 8
    assert block["mesh"] == {"data": 2, "model": 4}
    per = block["per_dtype"]
    assert set(per) == {"float32", "bfloat16", "int8", "int4"}
    for entry in per.values():
        assert entry["aggregate_words_per_sec"] > 0
        assert entry["scaling_efficiency"] > 0
        assert entry["exchange_bytes_per_step"] > 0
    # the acceptance bars: >=1.9x payload cut for bf16, >=3x for int8,
    # >=6x for int4 (block-wise codes+scales on the packed grouped plane),
    # and short-run loss parity within 1% of f32 on the CPU-smoke config
    assert per["bfloat16"]["payload_reduction_vs_f32"] >= 1.9
    assert per["int8"]["payload_reduction_vs_f32"] >= 3.0
    assert per["int4"]["payload_reduction_vs_f32"] >= 6.0
    assert per["bfloat16"]["loss_parity_vs_f32"] <= 0.01
    assert per["int8"]["loss_parity_vs_f32"] <= 0.02
    assert per["int4"]["loss_parity_vs_f32"] <= 0.01
    # gateable headline numbers mirror the f32 lane
    assert block["aggregate_words_per_sec"] == \
        per["float32"]["aggregate_words_per_sec"]
    # the overlap lane rode along
    assert block["overlap"]["aggregate_words_per_sec"] > 0
    # and the block reaches the emitted JSON line (-> ledger payload)
    payload = json.loads(bench._result_json())
    assert payload["scaling"]["aggregate_words_per_sec"] == \
        block["aggregate_words_per_sec"]


def test_scaling_lane_single_device_records_skip(isolated_bench):
    counts, ids = _small_workload(vocab=128, tokens=5_000)
    bench.measure_scaling(counts, ids, n_devices=1)
    block = bench._state["scaling"]
    assert "skipped" in block and "single" in block["skipped"]
    assert any("scaling lane skipped" in e for e in bench._state["errors"])


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


# ------------------------------------------------- scaling CI gate ---------


def _bench_record(value, scaling_agg=None):
    payload = {
        "metric": "word2vec_words_per_sec_per_chip", "value": value,
        "unit": "words/sec/chip", "platform": "tpu", "config": {},
    }
    if scaling_agg is not None:
        payload["scaling"] = {"aggregate_words_per_sec": scaling_agg,
                              "scaling_efficiency": 0.9}
    return {"payload": payload}


def test_check_regression_gates_scaling_aggregate(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0, scaling_agg=800_000.0))
    led.append("bench", _bench_record(101_000.0, scaling_agg=300_000.0))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1
    assert "scaling REGRESSION" in msg
    # headline itself was fine
    assert msg.splitlines()[0].startswith("ok:")


def test_check_regression_scaling_ok_and_headline_still_gates(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0, scaling_agg=800_000.0))
    led.append("bench", _bench_record(99_000.0, scaling_agg=820_000.0))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "scaling ok" in msg
    # a headline regression still fails even with healthy scaling
    led.append("bench", _bench_record(10_000.0, scaling_agg=830_000.0))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1 and "REGRESSION" in msg.splitlines()[0]


def test_check_regression_without_scaling_blocks_is_headline_only(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0))
    led.append("bench", _bench_record(99_000.0))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "scaling" not in msg
