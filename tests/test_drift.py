"""Drift sentinel + regression attribution: EWMA/CUSUM detector math,
transition-edged ``drift`` ledger events, atomic incident bundles (and
the drift + NaN same-window interplay — two distinct bundles, never one
clobbered dir), the TrainLoop wiring under a ``slow_step`` chaos
injection, and ``--diff`` throughput attribution (the drift drill itself is
in ``test_drills.py``)."""

import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.framework.trainer import Trainer, TrainLoop
from swiftsnails_tpu.telemetry.drift import (
    DriftSentinel,
    EwmaCusum,
    build_incident_bundle,
    bundle_complete,
)
from swiftsnails_tpu.telemetry.goodput import (
    _record_rate,
    throughput_attribution,
)
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    _resolve_diff_record,
    render_diff,
    render_failures,
)
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.metrics import MetricsLogger


# ------------------------------------------------------------- detector ----


def test_cusum_trips_on_persistent_shift_not_noise():
    det = EwmaCusum("step_ms", warmup=8)
    edges = []
    for i in range(30):
        if det.update(10.0 + 0.01 * (-1) ** i, step=i):
            edges.append(i)
    assert edges == [] and not det.drifted
    # a sustained 5x shift confirms exactly once (the False->True edge)
    for i in range(30, 45):
        if det.update(50.0, step=i):
            edges.append(i)
    assert len(edges) == 1 and det.drifted
    assert det.drift_step == edges[0]
    st = det.state()
    assert st["drifted"] and st["signal"] == "step_ms"
    assert st["peak"] >= det.h


def test_cusum_discards_the_cold_start_sample():
    # sample 1 is the jit-compile step: orders of magnitude off. It must
    # not poison the seeded location/scale — detection of a later real
    # shift lands within a couple of samples, not dozens.
    det = EwmaCusum("step_ms", warmup=4)
    det.update(2000.0, step=0)  # compile outlier, discarded
    assert det.mean == 2000.0 and det.var == 0.0  # only location staged
    for i in range(1, 10):
        det.update(10.0 + 0.01 * (-1) ** i, step=i)
    assert abs(det.mean - 10.0) < 1.0  # the outlier left no trace
    trip = None
    for i in range(10, 16):
        if det.update(80.0, step=i):
            trip = i
            break
    assert trip is not None and trip <= 12


def test_cusum_ignores_non_finite_and_resets():
    det = EwmaCusum("loss", warmup=2)
    assert det.update(float("nan")) is False
    assert det.n == 0  # non-finite never counts as a sample
    for i in range(20):
        det.update(1.0 + 0.01 * (-1) ** i, step=i)
    for i in range(20, 40):
        det.update(9.0, step=i)
    assert det.drifted
    det.reset()
    assert not det.drifted and det.stat == 0.0 and det.drift_step is None
    # learned location survives the reset (re-arm, not amnesia)
    assert det.mean > 1.0


def test_flat_signal_never_divides_by_zero():
    det = EwmaCusum("gauge", warmup=4, k=1.0)
    for i in range(20):
        assert det.update(5.0, step=i) is False  # sigma 0: unit shocks, z-k=0


# ------------------------------------------------------------- sentinel ----


def test_sentinel_transition_edge_is_one_ledger_event(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    s = DriftSentinel(["step_ms", "loss"], warmup=4, ledger=led,
                      context={"model": "toy"})
    for i in range(12):
        assert s.observe(
            i, {"step_ms": 10.0 + 0.01 * (-1) ** i, "loss": 1.0}) == []
    confirmed = []
    for i in range(12, 30):
        confirmed += s.observe(i, {"step_ms": 90.0, "loss": 1.0})
    assert confirmed == ["step_ms"]
    assert s.drifted and s.events == 1 and s.tripped == ["step_ms"]
    events = led.records("drift")
    assert len(events) == 1  # edge only — no storm while drifted
    ev = events[0]
    assert ev["signals"] == ["step_ms"] and ev["model"] == "toy"
    assert ev["detectors"][0]["signal"] == "step_ms"
    # the drift event renders in the failure timeline
    assert "DRIFT" in render_failures(led)
    # reset closes the incident and re-arms: a second shift is a second event
    s.reset()
    assert not s.drifted and s.tripped == []
    for i in range(30, 60):
        s.observe(i, {"step_ms": 400.0, "loss": 1.0})
    assert s.events == 2 and len(led.records("drift")) == 2


def test_sentinel_accepts_partial_signal_rows():
    s = DriftSentinel(warmup=2)
    # a run without tiering never feeds tier_hit_rate — no KeyError, no trip
    for i in range(10):
        assert s.observe(i, {"step_ms": 1.0}) == []
    assert s.summary()["drifted"] is False


# ------------------------------------------------------ incident bundles ----


class _FakeRing:
    def snapshot(self):
        return [{"step": 7, "step_ms": 1.0}, {"step": 8, "step_ms": 2.0}]


def test_bundle_contents_and_completeness(tmp_path):
    from swiftsnails_tpu.telemetry.timeseries import TimeSeriesStore

    ts = TimeSeriesStore(window=8)
    ts.sample(7, {"step_ms": 1.0})
    path = build_incident_bundle(
        str(tmp_path / "inc"), "drift-step_ms",
        blackbox=_FakeRing(), timeseries=ts,
        context={"model": "toy", "config_hash": "abc"})
    assert os.path.basename(path).startswith("incident-")
    assert bundle_complete(path)
    man = json.load(open(os.path.join(path, "manifest.json")))
    assert man["reason"] == "drift-step_ms"
    assert man["first_step"] == 7 and man["last_step"] == 8
    assert man["timeseries_samples"] == 1
    fp = json.load(open(os.path.join(path, "fingerprint.json")))
    assert fp["context"]["model"] == "toy" and fp["env"] is not None
    # no stray staging dirs left behind
    assert not [d for d in os.listdir(tmp_path / "inc") if d.startswith(".")]


def test_same_second_bundles_land_distinct(tmp_path):
    # the drift + NaN interplay at the primitive level: two bundles in the
    # same second (same UTC stamp) must be two directories, never a clobber
    a = build_incident_bundle(str(tmp_path), "drift-step_ms",
                              blackbox=_FakeRing())
    b = build_incident_bundle(str(tmp_path), "drift-step_ms",
                              blackbox=_FakeRing())
    assert a != b and os.path.isdir(a) and os.path.isdir(b)
    assert b.endswith("-2")


def test_bundle_without_sources_is_incomplete(tmp_path):
    path = build_incident_bundle(str(tmp_path), "nan-loss")
    assert os.path.isdir(path)
    assert not bundle_complete(path)  # no blackbox/timeseries captured


# ------------------------------------------------- TrainLoop integration ----


class ToyTrainer(Trainer):
    name = "toy"

    def __init__(self, config, nan_from=None, n_batches=64):
        super().__init__(config, mesh=None)
        self.nan_from = nan_from
        self.n_batches = n_batches

    def init_state(self):
        return {"w": jnp.zeros((4,), jnp.float32)}

    def batches(self):
        for i in range(self.n_batches):
            yield {"x": np.full((8, 4), 1.0, np.float32)}

    def train_step(self, state, batch, rng):
        w = state["w"] + batch["x"].mean(0)
        loss = (w * 0).sum() + 1.0  # flat loss: only step_ms can drift
        if self.nan_from is not None:
            loss = loss / 0.0 * 0.0  # inf * 0 -> NaN, every step
        return {"w": w}, {"loss": loss}


def _drift_loop(tmp_path, **trainer_kw):
    cfg = Config({
        "telemetry": "1",
        "profile_cadence": "1",
        "profile_window": "64",
        "drift_detect": "1",
        # the detectors arm on the band's first sample (chaos step 16 is
        # sample 17): a hiccup of a loaded host before the band cannot then
        # confirm a drift and use up the one transition-edged event
        "drift_warmup": "15",
        "blackbox_dir": str(tmp_path / "bb"),
        "incident_dir": str(tmp_path / "incidents"),
        "ledger_path": str(tmp_path / "ledger.jsonl"),
        # an 80ms sleep against sub-ms toy steps: a shift no hiccup seen
        # during warm-up hides
        "chaos_spec": "slow_step@16-40",
        "chaos_slow_step_ms": "80",
    })
    trainer = ToyTrainer(cfg, **trainer_kw)
    return TrainLoop(trainer, metrics=MetricsLogger(echo=False), log_every=1)


def test_trainloop_detects_slow_step_drift_and_bundles(tmp_path):
    loop = _drift_loop(tmp_path)
    loop.run(max_steps=48)
    assert loop.drift is not None and loop.drift.events == 1
    det = loop.drift.detectors["step_ms"]
    assert det.drifted and 16 <= det.drift_step <= 40
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    events = led.records("drift")
    assert len(events) == 1 and "step_ms" in events[0]["signals"]
    # one complete bundle, recorded on the loop and on disk
    assert len(loop.incidents) == 1
    assert bundle_complete(loop.incidents[0])
    # the run record carries the sentinel summary for ops/ledger-report
    run = led.latest("run")
    assert run["drift"]["events"] == 1 and run["drift"]["drifted"]


def test_drift_and_nan_in_same_window_make_two_distinct_bundles(tmp_path):
    # ISSUE 17 satellite: a NaN guardrail trip and a confirmed drift in the
    # same window must land as two distinct incident bundles
    loop = _drift_loop(tmp_path, nan_from=0)
    loop.run(max_steps=48)
    assert loop.drift.events == 1  # NaN loss is non-finite: ignored by CUSUM
    assert len(loop.incidents) == 2
    reasons = set()
    for path in loop.incidents:
        assert bundle_complete(path)
        reasons.add(json.load(
            open(os.path.join(path, "manifest.json")))["reason"])
    assert reasons == {"nan-loss", "drift-step_ms"}
    assert len(set(loop.incidents)) == 2  # distinct directories


def test_incident_dir_untouched_without_profiler_or_sentinel(tmp_path):
    cfg = Config({
        "telemetry": "1",
        "blackbox_dir": str(tmp_path / "bb"),
        "incident_dir": str(tmp_path / "incidents"),
    })
    loop = TrainLoop(ToyTrainer(cfg, nan_from=0),
                     metrics=MetricsLogger(echo=False), log_every=1)
    loop.run(max_steps=4)
    # the blackbox still dumps, but a bare-telemetry run bundles nothing
    assert loop.incidents == []
    assert not os.path.exists(tmp_path / "incidents")


# ---------------------------------------------------- diff + attribution ----


def _run_record(wall_s, host_blocked_s, items=10_000, steps=100,
                comm=None):
    rec = {
        "goodput": {
            "items": items,
            "steps": steps,
            "items_per_sec": 123456.0,  # span-based decoy — must lose
            "decomposition": {
                "wall_s": wall_s,
                "compute_s": 8.0,
                "h2d_s": 1.0,
                "host_blocked_s": host_blocked_s,
                "other_s": 0.0,
                "steps": steps,
            },
        },
    }
    if comm is not None:
        rec["comm_by_scope"] = comm
    return rec


def test_record_rate_prefers_wall_clock_over_span_rate():
    rec = _run_record(wall_s=10.0, host_blocked_s=0.5)
    # items / wall_s, NOT the span-based goodput.items_per_sec: a run
    # slowed by sleeps must not look faster
    assert _record_rate(rec) == pytest.approx(1000.0)
    # explicit top-level fields still win outright
    assert _record_rate({"words_per_sec": 42.0}) == 42.0
    assert _record_rate({"items_per_sec": 7.0}) == 7.0
    # no decomposition: the span rate is the best remaining estimate
    assert _record_rate({"goodput": {"items_per_sec": 9.0}}) == 9.0
    assert _record_rate({}) is None


def test_throughput_attribution_names_the_dominant_component():
    a = _run_record(wall_s=10.0, host_blocked_s=0.5,
                    comm={"pull": {"bytes": 100.0}})
    b = _run_record(wall_s=15.0, host_blocked_s=5.0,
                    comm={"pull": {"bytes": 300.0}})
    att = throughput_attribution(a, b)
    assert att["dominant"] == "host_blocked"
    assert att["delta_pct"] == pytest.approx(-33.33, abs=0.1)
    hb = att["components"]["host_blocked"]
    assert hb["delta_s"] == pytest.approx(0.045)  # (5 - 0.5) / 100 steps
    assert att["components"]["compute"]["delta_s"] == pytest.approx(0.0)
    assert att["comm_bytes"]["pull"]["delta_bytes"] == 200.0
    assert 0.0 < att["dominant_share"] <= 1.1
    # partial records degrade, not crash
    assert throughput_attribution({}, {})["dominant"] == "insufficient-data"


def test_render_diff_marks_dominant_and_rates():
    a = _run_record(wall_s=10.0, host_blocked_s=0.5)
    b = _run_record(wall_s=15.0, host_blocked_s=5.0)
    out = render_diff(a, b, label_a="before", label_b="after")
    assert "A = before" in out and "B = after" in out
    assert "items/sec: 1,000" in out
    assert "host_blocked" in out and "<-- dominant" in out
    assert "dominant contributor: host_blocked" in out


def test_resolve_diff_record_index_and_file(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("run", {"model": "m1", "steps": 10})
    led.append("run", {"model": "m2", "steps": 20})
    led.append("bench", {"payload": {}})  # non-run records never index
    rec, label = _resolve_diff_record(led, "-1")
    assert rec["model"] == "m2" and "run[-1]" in label
    rec0, _ = _resolve_diff_record(led, "0")
    assert rec0["model"] == "m1"
    # a path: plain JSON object
    p = tmp_path / "rec.json"
    p.write_text(json.dumps({"model": "file", "steps": 1}))
    rec_f, label_f = _resolve_diff_record(led, str(p))
    assert rec_f["model"] == "file" and label_f == str(p)
    # a JSONL file: last parseable line wins
    pl = tmp_path / "rec.jsonl"
    pl.write_text('{"model": "first"}\nnot-json\n{"model": "last"}\n')
    rec_l, _ = _resolve_diff_record(led, str(pl))
    assert rec_l["model"] == "last"
    with pytest.raises(ValueError, match="out of range"):
        _resolve_diff_record(led, "7")
    with pytest.raises(ValueError, match="neither"):
        _resolve_diff_record(led, str(tmp_path / "missing.json"))
    empty = Ledger(str(tmp_path / "empty.jsonl"))
    with pytest.raises(ValueError, match="no run records"):
        _resolve_diff_record(empty, "-1")
