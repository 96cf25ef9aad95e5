"""SLO engine: multi-window burn-rate math under a fake clock, error-budget
accounting, transition-edged ``slo_burn`` ledger events, the autoscaler
hook, and the new failure-timeline lines.

The alerting contract (ISSUE 16): a kernel pages only when *both* the
short (window/12) and long windows burn at ``alert_burn`` or faster — a
sudden fire alerts within seconds of sustained evidence, while a single
stray request (short window spikes, long window doesn't) never does.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.telemetry.ledger import (
    FAILURE_KINDS,
    Ledger,
    render_failures,
)
from swiftsnails_tpu.telemetry.slo import SloObjective, SloTracker
from swiftsnails_tpu.utils.config import Config


# ------------------------------------------------------------ burn math ----


def test_burn_math_and_budget_with_fake_clock():
    t = [0.0]
    trk = SloTracker({"pull": SloObjective(10.0, availability=0.9)},
                     window_s=60.0, clock=lambda: t[0])
    for _ in range(8):
        trk.record("pull", 5.0)  # good
    trk.record("pull", 50.0)  # over the latency SLO -> bad
    trk.record("pull", 5.0, ok=False)  # typed failure -> bad, same budget
    # 2 bad of 10 against a 0.1 budget: burning at exactly 2x
    br = trk.burn_rates("pull")
    assert br["short"] == pytest.approx(2.0)
    assert br["long"] == pytest.approx(2.0)
    # allowed = 0.1 * 10 = 1 bad; 2 happened: the budget is gone
    assert trk.error_budget_remaining("pull") == 0.0
    assert trk.should_scale()
    snap = trk.snapshot()["pull"]
    assert snap["total"] == 10 and snap["bad"] == 2 and snap["alerting"]
    assert snap["budget_remaining_pct"] == 0.0
    # the window rolls past everything: budget refills, burns go quiet
    t[0] = 120.0
    assert trk.burn_rates("pull") == {"short": 0.0, "long": 0.0}
    assert trk.error_budget_remaining("pull") == 1.0


def test_short_window_spike_alone_does_not_page():
    t = [0.0]
    trk = SloTracker({"pull": SloObjective(10.0, availability=0.9)},
                     window_s=60.0, alert_burn=2.0, clock=lambda: t[0])
    for _ in range(40):
        trk.record("pull", 1.0)  # a long healthy history
    t[0] = 57.0
    for _ in range(3):
        trk.record("pull", 99.0)  # sudden fire in the 5s short window
    br = trk.burn_rates("pull")
    assert br["short"] > 2.0  # the fast window is screaming...
    assert br["long"] < 2.0  # ...but the evidence isn't sustained yet
    assert not trk.snapshot()["pull"]["alerting"]
    for _ in range(7):
        trk.record("pull", 99.0)  # now 10 bad of 50: long burn hits 2.0
    assert trk.burn_rates("pull")["long"] >= 2.0
    assert trk.snapshot()["pull"]["alerting"]


def test_slo_burn_ledger_event_is_transition_edged(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    t = [0.0]
    trk = SloTracker({"pull": SloObjective(10.0, availability=0.9)},
                     window_s=60.0, ledger=led, source="fleet",
                     clock=lambda: t[0])
    for _ in range(20):
        trk.record("pull", 99.0)  # sustained hard burn
    evs = led.records("slo_burn")
    assert len(evs) == 1  # one line for the whole episode, not 20
    ev = evs[0]
    assert ev["kernel"] == "pull" and ev["source"] == "fleet"
    assert ev["burn_short"] >= 2.0 and ev["burn_long"] >= 2.0
    assert ev["slo_latency_ms"] == 10.0 and ev["alert_burn"] == 2.0
    assert trk.stats() == {"recorded": 20, "burn_events": 1,
                           "scale_hints": 0}
    # recover, then burn again: a second episode is a second line
    t[0] = 200.0
    for _ in range(20):
        trk.record("pull", 1.0)
    assert not trk.snapshot()["pull"]["alerting"]
    t[0] = 210.0
    for _ in range(20):
        trk.record("pull", 99.0)
    assert len(led.records("slo_burn")) == 2
    # and the failure timeline renders it
    out = render_failures(led)
    assert "SLO-BURN" in out and "kernel=pull" in out
    assert "slo=10.0ms@0.9" in out


def test_from_config_and_unknown_kernels():
    assert SloTracker.from_config(Config({})) is None
    assert SloTracker.from_config(Config({"slo_latency_ms": "0"})) is None
    trk = SloTracker.from_config(Config({
        "slo_latency_ms": "25", "slo_availability": "0.99",
        "slo_window_s": "120"}))
    assert set(trk.objectives) == {"pull", "topk", "score"}
    assert trk.window_s == 120.0
    assert trk.objectives["pull"].latency_ms == 25.0
    assert trk.objectives["pull"].budget == pytest.approx(0.01)
    # an unseen kernel is adopted against the default objective
    trk.record("delta_apply", 5.0)
    assert "delta_apply" in trk.snapshot()
    # without a default, unknown kernels are ignored, not crashed on
    bare = SloTracker({"pull": 10.0})
    bare.record("mystery", 1.0)
    assert "mystery" not in bare.snapshot()
    with pytest.raises(ValueError):
        SloObjective(10.0, availability=1.5)


# ----------------------------------------------------- failure timeline ----


def test_new_failure_kinds_registered_and_render(tmp_path):
    assert "slo_burn" in FAILURE_KINDS and "trace_anomaly" in FAILURE_KINDS
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("trace_anomaly", {
        "source": "fleet", "trace_id": "00c0ffee00c0ffee", "kernel": "pull",
        "anomalies": ["hedge", "slo_violation"], "dur_ms": 18.25,
        "anomalies_total": 101,
    })
    out = render_failures(led)
    assert "TRACE-ANOMALY" in out
    assert "trace=00c0ffee00c0ffee" in out
    assert "kinds=hedge,slo_violation" in out and "total=101" in out
