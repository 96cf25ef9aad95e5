"""bench.py tiered lane structure + the tiered CI gate.

Mirror of ``test_scaling_lane.py`` for ``--lane tiered``: the lane must
populate a ``tiered`` block with equal-vocab words/sec vs the resident
store, the bit-parity verdict, and an over-budget (vocab 4x the HBM budget)
train -> checkpoint -> serve round trip; the block must reach the emitted
JSON line; ``ledger-report --check-regression`` must gate the tiered
words/sec floor AND hard-fail any record whose parity or round trip broke.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from swiftsnails_tpu.telemetry.ledger import Ledger, check_regression


@pytest.fixture()
def isolated_bench(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "LEDGER_PATH", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setattr(bench, "_SMALL", True)  # CI-sized corpora + vocab
    monkeypatch.setitem(bench._state, "errors", [])
    monkeypatch.setitem(bench._state, "tiered", None)
    return tmp_path


def test_tiered_lane_smoke(isolated_bench):
    bench.measure_tiered()
    block = bench._state["tiered"]
    assert block is not None
    # equal-vocab leg: tiered throughput measured against the resident store
    assert block["words_per_sec"] > 0
    assert block["resident_words_per_sec"] > 0
    assert block["tiered_over_resident"] > 0
    assert block["parity_bit_identical"] is True
    # over-budget leg: vocab 4x the synthetic HBM budget, full round trip
    ob = block["over_budget"]
    assert ob["vocab_units"] >= 4 * ob["budget_slots"]
    assert ob["evictions"] > 0  # the budget actually bound
    assert ob["flushed_rows"] > 0  # dirty write-back on the training path
    assert ob["parity_bit_identical"] is True
    assert ob["serve_pull_ok"] is True
    assert ob["round_trip_ok"] is True
    assert block["round_trip_ok"] is True
    # the step-time breakdown block (plan/fault/flush/remap/h2d + queue depth)
    bd = block["breakdown"]
    for key in ("plan_ns", "fault_ns", "flush_ns", "remap_ns", "h2d_ns",
                "flush_wait_ns", "flush_queue_depth"):
        assert key in bd, bd
    # the block reaches the emitted JSON line (-> ledger payload)
    payload = json.loads(bench._result_json())
    assert payload["tiered"]["words_per_sec"] == block["words_per_sec"]
    # and the lane appended its own ledger record
    rec = Ledger(bench.LEDGER_PATH).latest("tiered_lane")
    assert rec is not None and rec["words_per_sec"] == block["words_per_sec"]


# ------------------------------------------------- tiered CI gate ----------


def _bench_record(value, tiered=None, platform="tpu"):
    payload = {
        "metric": "word2vec_words_per_sec_per_chip", "value": value,
        "unit": "words/sec/chip", "platform": platform, "config": {},
    }
    if tiered is not None:
        payload["tiered"] = tiered
    return {"payload": payload}


def _tiered_block(wps, parity=True, round_trip=True, ratio=None):
    block = {"words_per_sec": wps, "parity_bit_identical": parity,
             "round_trip_ok": round_trip}
    if ratio is not None:
        block["tiered_over_resident"] = ratio
    return block


def test_check_regression_gates_tiered_words_per_sec(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0, _tiered_block(50_000.0)))
    led.append("bench", _bench_record(101_000.0, _tiered_block(20_000.0)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1
    assert "tiered REGRESSION" in msg
    # headline itself was fine
    assert msg.splitlines()[0].startswith("ok:")


def test_check_regression_tiered_parity_failure_is_fatal_any_platform(tmp_path):
    # correctness gate: a parity/round-trip failure fails the gate even with
    # no baseline to compare against and even on CPU
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(
        100_000.0, _tiered_block(50_000.0, parity=False)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1 and "correctness gate" in msg

    # CPU records don't count as measured perf (rc 2 path) but the tiered
    # correctness verdict must still surface and fail CI
    led2 = Ledger(str(tmp_path / "l2.jsonl"))
    led2.append("bench", _bench_record(
        100_000.0, _tiered_block(50_000.0, round_trip=False), platform="cpu"))
    rc, msg = check_regression(led2, 10.0)
    assert rc != 0 and "tiered REGRESSION" in msg


def test_check_regression_tiered_ok_and_single_record(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0, _tiered_block(50_000.0)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "tiered: single" in msg
    led.append("bench", _bench_record(99_000.0, _tiered_block(48_000.0)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "tiered ok" in msg
    # a headline regression still fails even with a healthy tiered lane
    led.append("bench", _bench_record(10_000.0, _tiered_block(49_000.0)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1 and "REGRESSION" in msg.splitlines()[0]


def test_check_regression_gates_tiered_resident_ratio(tmp_path):
    """The equal-vocab tiered/resident speed ratio has a hard floor: a
    newest record below 0.95x resident fails the gate even when absolute
    words/sec looks healthy."""
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(
        100_000.0, _tiered_block(50_000.0, ratio=1.01)))
    led.append("bench", _bench_record(
        101_000.0, _tiered_block(51_000.0, ratio=0.88)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 1 and "resident speed" in msg

    # at or above the floor the ratio passes
    led.append("bench", _bench_record(
        102_000.0, _tiered_block(52_000.0, ratio=0.96)))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "tiered ok" in msg

    # records predating the ratio field are not ratio-gated
    led2 = Ledger(str(tmp_path / "l2.jsonl"))
    led2.append("bench", _bench_record(100_000.0, _tiered_block(50_000.0)))
    led2.append("bench", _bench_record(99_000.0, _tiered_block(49_000.0)))
    rc, msg = check_regression(led2, 10.0)
    assert rc == 0 and "tiered ok" in msg


def test_check_regression_without_tiered_blocks_is_headline_only(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("bench", _bench_record(100_000.0))
    led.append("bench", _bench_record(99_000.0))
    rc, msg = check_regression(led, 10.0)
    assert rc == 0 and "tiered" not in msg
