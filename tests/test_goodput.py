"""Goodput accounting: MFU / decomposition / roofline math on a synthetic
trace + audit fixture, plus the real TrainLoop wiring on CPU."""

import math

import pytest

from swiftsnails_tpu.telemetry.goodput import (
    goodput_report,
    peaks_for,
    roofline_step_seconds,
    step_time_decomposition,
)


def span(name, ts_us, dur_us):
    return {"name": name, "ts_us": ts_us, "dur_us": dur_us, "tid": 1,
            "depth": 0, "args": {}}


# synthetic 2-step trace: wall 10ms; per step 3ms compute, 1ms h2d,
# 0.5ms prefetch-wait
EVENTS = [
    span("prefetch-wait", 0, 500),
    span("h2d", 500, 1000),
    span("step", 1500, 3000),
    span("prefetch-wait", 5000, 500),
    span("h2d", 5500, 1000),
    span("step", 6500, 3000),
    span("metrics-flush", 9500, 500),
]

AUDIT = {
    "cost": {"flops": 2.0e9, "bytes_accessed": 1.0e8},
    "total_bytes": 4.0e6,  # collective traffic
    "ops": {"all-reduce": {"count": 1, "bytes": int(4.0e6)}},
}

PEAKS = {  # round numbers so the expected values are exact
    "flops_per_s": 1.0e12,
    "hbm_bytes_per_s": 1.0e11,
    "ici_bytes_per_s": 1.0e10,
    "source": "test",
}


def test_step_time_decomposition_sums_and_fracs():
    dec = step_time_decomposition(EVENTS)
    assert dec["steps"] == 2
    assert dec["wall_s"] == pytest.approx(10e-3)
    assert dec["compute_s"] == pytest.approx(6e-3)
    assert dec["h2d_s"] == pytest.approx(2e-3)
    assert dec["host_blocked_s"] == pytest.approx(1e-3)
    assert dec["other_s"] == pytest.approx(0.5e-3)
    assert dec["compute_frac"] == pytest.approx(0.6)
    assert dec["unaccounted_frac"] == pytest.approx(0.05)
    assert step_time_decomposition([]) ["wall_s"] == 0.0


def test_mfu_exact():
    rep = goodput_report(events=EVENTS, audit=AUDIT, peaks=PEAKS)
    # step_seconds derived from spans: 6ms / 2 steps = 3ms
    assert rep["step_seconds"] == pytest.approx(3e-3)
    # MFU = 2e9 FLOP / 3e-3 s / 1e12 FLOP/s = 2/3
    assert rep["mfu"] == pytest.approx(2.0 / 3.0)
    # goodput = compute 6ms of wall 10ms
    assert rep["goodput"] == pytest.approx(0.6)


def test_roofline_bounds_and_ratio():
    # compute bound 2ms, HBM bound 1ms, ICI bound 0.4ms -> compute-bound
    ideal = roofline_step_seconds(2.0e9, 1.0e8, 4.0e6, PEAKS)
    assert ideal == pytest.approx(2e-3)
    rep = goodput_report(
        events=EVENTS, audit=AUDIT, peaks=PEAKS, items=2048, steps=2,
    )
    assert rep["roofline_step_seconds"] == pytest.approx(2e-3)
    # measured 3ms vs ideal 2ms -> 2/3 of roofline throughput
    assert rep["vs_roofline"] == pytest.approx(2.0 / 3.0)
    assert rep["items_per_sec"] == pytest.approx(1024 / 3e-3)
    assert rep["roofline_items_per_sec"] == pytest.approx(1024 / 2e-3)


def test_n_chips_divides_flops():
    rep1 = goodput_report(audit=AUDIT, step_seconds=1e-3, peaks=PEAKS)
    rep4 = goodput_report(audit=AUDIT, step_seconds=1e-3, peaks=PEAKS, n_chips=4)
    assert rep4["mfu"] == pytest.approx(rep1["mfu"] / 4)


def test_unknown_peaks_degrade_to_none():
    rep = goodput_report(events=EVENTS, audit=AUDIT, peaks=peaks_for("cpu"))
    assert rep["mfu"] is None
    assert rep["roofline_step_seconds"] is None
    assert "vs_roofline" not in rep
    # decomposition and goodput still fully populated (span-only metrics)
    assert rep["goodput"] == pytest.approx(0.6)
    assert rep["decomposition"]["steps"] == 2


def test_peaks_table_lookup():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == pytest.approx(197e12)
    assert v5e["hbm_bytes_per_s"] == pytest.approx(819e9)
    assert peaks_for(None)["flops_per_s"] is None
    assert peaks_for("TPU v4")["flops_per_s"] == pytest.approx(275e12)


def test_audit_without_cost_still_reports():
    rep = goodput_report(events=EVENTS, audit={"total_bytes": 0, "cost": {}},
                         peaks=PEAKS)
    assert rep["mfu"] is None
    assert rep["flops_per_step"] is None


def test_unknown_tpu_kind_is_an_error_not_a_neighbours_peak():
    from swiftsnails_tpu.telemetry.goodput import UnknownDeviceError

    # exact keys only: the old substring table gave any "...v5..." kind
    # v5p's 459 TFLOP/s
    for kind in ("TPU v5", "TPU v5e", "TPU v7x", "tpu v5 lite"):
        with pytest.raises(UnknownDeviceError, match="PEAKS"):
            peaks_for(kind, "tpu")
    # off the chip there is no denominator, and no error
    assert peaks_for("cpu", "cpu")["flops_per_s"] is None
    assert peaks_for("TPU v5", None)["flops_per_s"] is None


# ---------------------------------------------- TrainLoop end-to-end (CPU)


def test_trainloop_emits_goodput_and_ledger_record(tmp_path):
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_word2vec import make_trainer

    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.telemetry.ledger import Ledger
    from swiftsnails_tpu.utils.metrics import MetricsLogger

    ledger_path = str(tmp_path / "ledger.jsonl")
    metrics_path = str(tmp_path / "metrics.jsonl")
    trainer = make_trainer(
        telemetry="1",
        ledger_path=ledger_path,
        blackbox_dir=str(tmp_path / "bb"),
    )
    loop = TrainLoop(trainer, metrics=MetricsLogger(path=metrics_path),
                     log_every=2)
    state = loop.run(max_steps=5)
    assert state is not None
    loop.metrics.close()

    # the durable run record: env fingerprint + config hash + goodput block
    recs = Ledger(ledger_path).records("run")
    assert len(recs) == 1
    rec = recs[0]
    assert rec["model"] == "word2vec"
    assert rec["steps"] == 5
    assert len(rec["config_hash"]) == 16
    assert rec["env"]["devices"]["platform"] == "cpu"
    assert "jax" in rec["env"]
    g = rec["goodput"]
    assert "mfu" in g
    assert g["mfu"] is None  # CPU has no peak, and no key invents one
    assert g["decomposition"]["steps"] == 5
    assert g["flops_per_step"] > 0  # the compile-only audit ran
    assert 0 < g["goodput"] <= 1

    # the goodput block also lands in the metrics JSONL summary output
    records = [json.loads(l) for l in open(metrics_path)]
    assert any("goodput" in r for r in records)
