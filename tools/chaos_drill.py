#!/usr/bin/env python
"""Run a fault-injection drill matrix on the CPU; exit nonzero on any check
a drill's verdict names as failed.

The default matrix (``swiftsnails_tpu/resilience/drill.py``) injects every
fault the resilience stack claims to survive — NaN/Inf gradient bursts, a
poisoned parameter row, a transient data-stream I/O error, checkpoint bit
rot, a simulated preemption, and tiered-master bit rot over both f32 and
int8 (quantized) host masters, where the flip may land in a code plane or a
scale sideband — and asserts the run *recovers*: guardrail rollback with
zero non-finite values reaching the master tables, retry instead of crash,
manifest-verified walk-back, digest-detected quarantine-and-heal, and a
resumed run whose final loss matches an undisturbed one.

    python tools/chaos_drill.py            # the full matrix
    python tools/chaos_drill.py --fast     # the tier-1 subset
    python tools/chaos_drill.py --json     # machine-readable results
    python tools/chaos_drill.py --serve    # the serving availability matrix
    python tools/chaos_drill.py --cluster  # the membership drill matrix
    python tools/chaos_drill.py --fleet    # the replica-fleet drill matrix
    python tools/chaos_drill.py --freshness  # the delta-pipeline drill matrix
    python tools/chaos_drill.py --drift    # the training-plane drift drill
    python tools/chaos_drill.py --net      # the TCP transport drill matrix

Each plane keeps its drill and the drill's verdict (a dictionary of named
checks computed from the drill's result) in one module; this tool runs the
drill, prints the result beside the checks, and exits by the verdict:

- ``--serve`` (``serving/drill.py``): a seeded fault matrix against a live
  Servant with circuit breakers + degraded stale-LRU reads must hold the
  availability floor while the unprotected control leg hard-fails, a
  corrupt checkpoint must be rejected by the shadow-verify reload, and the
  tiered bit-flip drill must detect + rebuild with loss parity.
- ``--fleet`` (``serving/drill.py``): one replica of a 2-replica
  :class:`Fleet` is killed (``serve_io_error``: its breaker trips, the
  router walks around it) or slowed (``serve_slow``: tail hedges rescue the
  stragglers); the fleet must hold the floor and every anomaly must leave a
  complete trace tree (attempt -> reroute -> attempt under one root; both
  racing hedge attempts).
- ``--freshness`` (``freshness/drill.py``): a 2-replica fleet subscribed to
  a delta log loses its publisher, reads a bit-flipped batch (CRC), hits a
  deleted segment (gap); each must fall back to a full checkpoint reload
  and end on one shared version at parity 0.0 with a complete ``fallback``
  trace (detect -> reload -> resubscribe).
- ``--drift`` (``telemetry/drill.py``): a control run and a ``slow_step@A-B``
  chaos run share one ledger; the run's own sentinel must confirm the
  drift inside the band, emit exactly one ``drift`` event, leave a complete
  incident bundle, and the before/after attribution must name host-blocked.
- ``--net`` (``net/drill.py``): ``proc_kill`` / ``net_partition`` /
  ``net_slow`` against REAL spawned ``replica_server`` processes behind a
  :class:`NetFleet` (lease-expiry respawn + rejoin at parity 0.0, a stale
  write refused typed on heal, a bounded typed deadline), then the delta
  publisher's loss over the TCP stream.
- ``--cluster`` (``cluster/drill.py``): a simulated virtual-clock fleet
  under worker kill, straggler, partition and the composed storm must keep
  the exactly-once batch accounting *exact*, detect every loss and reassign
  its range, flag the straggler, and hold loss parity with a control.

The default matrix and ``--drift`` write a ledger
(``<workdir>/<drill>/LEDGER.jsonl``, ``<workdir>/DRILL_LEDGER.jsonl``);
inspect one with ``python -m swiftsnails_tpu ledger-report --failures
<ledger>``.

No accelerator required (or touched): the harness pins JAX_PLATFORMS=cpu
unless the caller already pinned a platform.
"""

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _report(args, title: str, results: dict, checks: dict) -> int:
    """Print one drill's result beside its named checks; the exit code is
    the verdict (nonzero when any check failed)."""
    failed = [k for k, ok in checks.items() if not ok]
    if args.json:
        print(json.dumps({"results": results, "checks": checks,
                          "failed": failed}))
        return 1 if failed else 0
    rows = results if all(isinstance(v, dict) for v in results.values()) \
        else {title: results}
    width = max(len(k) for k in rows)
    for name, res in rows.items():
        detail = ", ".join(f"{k}={v}" for k, v in res.items()
                           if not isinstance(v, (dict, list)))
        print(f"{name:<{width}}  {detail}")
    for name, ok in checks.items():
        print(f"{name:<40}  {'PASS' if ok else 'FAIL'}")
    print(f"{title}: {len(checks) - len(failed)}/{len(checks)} checks passed"
          + (f"; FAILED: {', '.join(failed)}" if failed else ""))
    return 1 if failed else 0


# flag -> (the plane's drill module, its drill, the drill's verdict)
DRILLS = {
    "serve": ("swiftsnails_tpu.serving.drill",
              "serve_chaos_drill", "serve_drill_checks"),
    "cluster": ("swiftsnails_tpu.cluster.drill",
                "run_cluster_drills", "cluster_drill_checks"),
    "fleet": ("swiftsnails_tpu.serving.drill",
              "fleet_chaos_drill", "fleet_drill_checks"),
    "drift": ("swiftsnails_tpu.telemetry.drill",
              "drift_drill", "drift_drill_checks"),
    "freshness": ("swiftsnails_tpu.freshness.drill",
                  "freshness_chaos_drill", "freshness_drill_checks"),
    "net": ("swiftsnails_tpu.net.drill",
            "net_chaos_drill", "net_drill_checks"),
}


def _run_drill(args, name: str) -> int:
    module, drill, checks = DRILLS[name]
    mod = importlib.import_module(module)
    res = getattr(mod, drill)(workdir=args.workdir)
    return _report(args, f"{name} drill", res, getattr(mod, checks)(res))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chaos_drill",
        description="deterministic fault-injection drill matrix (CPU)",
    )
    p.add_argument("--fast", action="store_true",
                   help="run the tier-1 fast subset only")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON object instead of the table")
    p.add_argument("--workdir", default=None,
                   help="keep drill artifacts (ledgers, checkpoints) here")
    p.add_argument("--serve", action="store_true",
                   help="run the serving availability matrix instead "
                        "(breakers + degraded reads vs the fault schedule; "
                        "nonzero exit on a missed availability floor)")
    p.add_argument("--cluster", action="store_true",
                   help="run the cluster membership drill matrix instead "
                        "(kill/straggle/partition vs the supervisor; nonzero "
                        "exit on lost/duplicated batches or missed recovery)")
    p.add_argument("--fleet", action="store_true",
                   help="run the replica-fleet drill matrix instead (kill/"
                        "slow one replica mid-storm; the fleet must hold the "
                        "availability floor via re-route + hedging)")
    p.add_argument("--drift", action="store_true",
                   help="run the training-plane drift drill instead "
                        "(slow_step injection vs the online sentinel: "
                        "detection + one drift event + complete incident "
                        "bundle + host-blocked --diff attribution)")
    p.add_argument("--freshness", action="store_true",
                   help="run the delta-pipeline drill matrix instead "
                        "(publisher kill / corrupt delta / forced gap vs a "
                        "subscribed fleet; each must fall back to a full "
                        "checkpoint reload and converge to parity 0.0)")
    p.add_argument("--net", action="store_true",
                   help="run the TCP transport drill matrix instead "
                        "(proc_kill / net_partition / net_slow against real "
                        "spawned replica processes: lease-expiry respawn + "
                        "rejoin, stale-write refusal on heal, bounded typed "
                        "timeouts; nonzero exit on any unrecovered fault)")
    args = p.parse_args(argv)

    for name in DRILLS:
        if getattr(args, name):
            return _run_drill(args, name)

    from swiftsnails_tpu.resilience.drill import run_drill_matrix

    results = run_drill_matrix(fast=args.fast, workdir=args.workdir)
    failed = [k for k, v in results.items() if not v.get("recovered")]
    if args.json:
        print(json.dumps({"results": results, "failed": failed}))
    else:
        width = max(len(k) for k in results)
        for name, res in results.items():
            status = "RECOVERED" if res.get("recovered") else "UNRECOVERED"
            detail = res.get("error") or ", ".join(
                f"{k}={v}" for k, v in res.items()
                if k not in ("recovered", "error") and not isinstance(v, dict)
            )
            print(f"{name:<{width}}  {status:<11}  {detail}")
        print(
            f"{len(results) - len(failed)}/{len(results)} drills recovered"
            + (f"; FAILED: {', '.join(failed)}" if failed else "")
        )
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
