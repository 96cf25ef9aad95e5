"""The training-plane drift drill: a scripted slow-step band against the
run's own drift sentinel.

Used by ``tools/chaos_drill.py --drift`` and the tier-1 tests, as
:mod:`swiftsnails_tpu.resilience.drill` is for the fault matrix.
:func:`drift_drill` runs a control run and a ``slow_step@A-B`` chaos run
that share one ledger; the chaos run must *detect* the injected drift
inside the band (step-time EWMA/CUSUM), emit exactly one transition-edged
``drift`` ledger event, leave a complete incident bundle behind, and the
before/after run records' ``--diff`` attribution must name host-blocked as
the dominant contributor. :func:`drift_drill_checks` is the verdict on the
result.

Everything is deterministic (fixed seeds, fixed fault schedule) and
CPU-sized: the drill runs in seconds under ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, Optional

# the drill's fault schedule: slow_step on a late contiguous band, long
# enough that host-blocked dominates the A->B delta over compile jitter
DRILL_STEPS = 48
INJECT_FIRST = 16
INJECT_LAST = 43
SLOW_STEP_MS = 80.0


def _workdir(workdir: Optional[str]) -> str:
    if workdir:
        os.makedirs(workdir, exist_ok=True)
        return workdir
    return tempfile.mkdtemp(prefix="ssn-drift-")


def drift_drill(workdir: Optional[str] = None) -> Dict:
    """Run the before/after drift drill; returns what it found (detection,
    event count, bundle, attribution)."""
    from swiftsnails_tpu.resilience.drill import make_trainer, run_loop
    from swiftsnails_tpu.telemetry.drift import bundle_complete
    from swiftsnails_tpu.telemetry.goodput import throughput_attribution
    from swiftsnails_tpu.telemetry.ledger import Ledger

    base = _workdir(workdir)
    ledger_path = os.path.join(base, "DRILL_LEDGER.jsonl")
    incident_dir = os.path.join(base, "incidents")
    common = {
        "telemetry": 1,
        "profile_cadence": 1,
        "profile_window": 256,
        "num_iters": 8,
        "ledger_path": ledger_path,
        "incident_dir": incident_dir,
    }

    # before: the undisturbed control run (drift sentinel off — its run
    # record is the --diff baseline, not a detection subject)
    ctrl_dir = os.path.join(base, "before")
    os.makedirs(ctrl_dir, exist_ok=True)
    tr = make_trainer(ctrl_dir, **dict(
        common, blackbox_dir=os.path.join(ctrl_dir, "blackbox")))
    run_loop(tr, max_steps=DRILL_STEPS)

    # after: same work + slow_step@A-B chaos, sentinel on and arming on the
    # band's first sample (chaos step A is sample A+1): before it the host's
    # own hiccups seed the baseline, they cannot confirm a drift
    drift_dir = os.path.join(base, "after")
    os.makedirs(drift_dir, exist_ok=True)
    tr2 = make_trainer(drift_dir, **dict(
        common,
        blackbox_dir=os.path.join(drift_dir, "blackbox"),
        drift_detect=1,
        drift_warmup=INJECT_FIRST - 1,
        chaos_spec=f"slow_step@{INJECT_FIRST}-{INJECT_LAST}",
        chaos_slow_step_ms=SLOW_STEP_MS,
    ))
    loop, _state, _steps = run_loop(tr2, max_steps=DRILL_STEPS)

    ledger = Ledger(ledger_path)
    runs = ledger.records("run")
    drift_events = ledger.records("drift")
    det = (loop.drift.detectors.get("step_ms")
           if loop.drift is not None else None)
    detect_step = det.drift_step if det is not None else None
    detected = (detect_step is not None
                and INJECT_FIRST <= detect_step <= INJECT_LAST)
    bundle = loop.incidents[0] if loop.incidents else None
    attribution = (throughput_attribution(runs[-2], runs[-1])
                   if len(runs) >= 2 else {"dominant": "insufficient-data"})
    return {
        "detected": bool(detected),
        "detect_step": detect_step,
        "inject_step": INJECT_FIRST,
        "inject_last": INJECT_LAST,
        "slow_step_ms": SLOW_STEP_MS,
        "window_steps": DRILL_STEPS,
        "drift_events": len(drift_events),
        "signals": list(loop.drift.tripped) if loop.drift else [],
        "bundle": bundle,
        "bundle_complete": bool(bundle and bundle_complete(bundle)),
        # which component moved, not by how much: the seconds are this
        # host's, the name is the drill's finding
        "attribution": {k: attribution.get(k)
                        for k in ("dominant", "dominant_share")},
        "ledger": ledger_path,
    }


def drift_drill_checks(res: Dict) -> Dict[str, bool]:
    """The drift drill's verdict, by name, from its result."""
    return {
        "detected_in_band": bool(res["detected"]),
        "single_drift_event": res["drift_events"] == 1,
        "bundle_complete": bool(res["bundle_complete"]),
        "attribution_host_blocked": (
            (res.get("attribution") or {}).get("dominant") == "host_blocked"),
    }
