"""Membership drills: kill/slow/partition faults against a simulated
N-worker fleet, judged on exactly-once accounting + loss parity.

Used by ``tools/chaos_drill.py --cluster`` and the tier-1 tests. Each drill
runs three legs, same trainer, same seeded fault schedule:

* an **undisturbed control** applies every batch in index order on one
  worker — the loss-parity reference;
* the **protected leg** runs the fleet under the supervisor: the storm
  kills a worker (lease expiry → reassignment), slows one (EWMA straggler →
  shrunk share + backup substeps), and partitions one (stale re-claims
  refused). It must finish with the accountant's proof *exact* — zero lost,
  zero double-applied — and eval loss within ``LOSS_PARITY_BAR`` of the
  control;
* the **unprotected control leg** runs the same storm with static shards
  and no supervisor: the dead worker's range is demonstrably lost. If it
  weren't, the storm is too weak to prove anything and the drill fails
  itself.

The fleet is simulated under a virtual clock, so every count is exact on
any host. :func:`cluster_drill_checks` is the verdict on the result.
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, Optional

from swiftsnails_tpu.resilience.drill import (
    LOSS_PARITY_BAR, eval_loss, make_trainer, tables_finite,
)

# the storm: one silent death, a straggler window, one partition — scheduled
# by cluster-wide applied-batch tick (deterministic under the virtual clock)
STORM_SPEC = "worker_dead@10,worker_slow@16-26,partition@30"
WORKERS = 3
TOTAL_BATCHES = 48


def _run_leg(trainer, spec: str, supervised: bool) -> Dict:
    from swiftsnails_tpu.cluster.sim import simulate_cluster
    from swiftsnails_tpu.resilience.chaos import ChaosPlan, parse_chaos_spec

    res = simulate_cluster(
        trainer, TOTAL_BATCHES, workers=WORKERS,
        chaos=ChaosPlan(parse_chaos_spec(spec), seed=7),
        supervised=supervised,
    )
    res["loss"] = eval_loss(trainer, res["state"])
    res["finite"] = tables_finite(res["state"])
    return res


def run_cluster_drill(spec: str, workdir: Optional[str] = None) -> Dict:
    """Run the three legs under one fault ``spec``; returns its counts."""
    owned = workdir is None
    if owned:
        tmp = tempfile.TemporaryDirectory(prefix="chaos-cluster-")
        workdir = tmp.name
    else:
        os.makedirs(workdir, exist_ok=True)
    trainer = make_trainer(workdir)

    from swiftsnails_tpu.cluster.sim import run_inorder_control

    control_state = run_inorder_control(trainer, TOTAL_BATCHES)
    control_loss = eval_loss(trainer, control_state)

    protected = _run_leg(trainer, spec, supervised=True)
    unprotected = _run_leg(trainer, spec, supervised=False)

    acct = protected["accounting"]
    status = protected.get("status", {})
    parity = abs(protected["loss"] - control_loss) / max(abs(control_loss),
                                                         1e-9)
    unprotected_lost = unprotected["accounting"]["lost_count"] > 0
    block = {
        "workers": WORKERS,
        "spec": spec,
        "total_batches": TOTAL_BATCHES,
        "committed": acct["committed"],
        "lost_count": acct["lost_count"],
        "duplicated_count": acct["duplicated_count"],
        "dup_discarded": acct["dup_discarded"],
        "stale_rejected": protected["stale_rejected"],
        "workers_lost": status.get("workers_lost", 0),
        "reassignments": status.get("reassignments", 0),
        "stragglers_flagged": status.get("stragglers_flagged", 0),
        "accounting_exact": bool(acct["exact"]),
        "finite": bool(protected["finite"]),
        "loss": round(float(protected["loss"]), 6),
        "control_loss": round(float(control_loss), 6),
        "loss_parity": round(float(parity), 6),
        "unprotected_lost_count": unprotected["accounting"]["lost_count"],
        "unprotected_lost": unprotected["accounting"]["lost"],
        "unprotected_hard_failure": bool(unprotected_lost),
        "virtual_s": protected["virtual_s"],
    }
    if owned:
        tmp.cleanup()
    return block


# ------------------------------------------------------------ drill matrix --

CLUSTER_DRILL_SPECS = {
    "worker_kill": "worker_dead@10",
    "straggler": "worker_slow@12-24",
    "partition": "partition@10",
    "storm": STORM_SPEC,
}


def run_cluster_drills(workdir: Optional[str] = None) -> Dict[str, Dict]:
    """The kill/slow/partition drill matrix (``chaos_drill.py --cluster``).

    Each drill isolates one fault kind; ``storm`` composes all three. A
    drill *recovers* when every check :func:`cluster_drill_checks` names for
    it holds — lost or duplicated batches, a missed detection, or a blown
    parity all fail it."""
    return {
        name: run_cluster_drill(
            spec, workdir=os.path.join(workdir, name) if workdir else None)
        for name, spec in CLUSTER_DRILL_SPECS.items()
    }


def cluster_drill_checks(results: Dict[str, Dict]) -> Dict[str, bool]:
    """The membership drills' verdict, ``<drill>.<check>`` by name."""
    checks: Dict[str, bool] = {}
    for name, block in results.items():
        row = {
            "accounting_exact": bool(
                block["accounting_exact"] and block["lost_count"] == 0
                and block["duplicated_count"] == 0),
            "finite": bool(block["finite"]),
            "loss_parity": block["loss_parity"] <= LOSS_PARITY_BAR,
        }
        if name in ("worker_kill", "partition", "storm"):
            row["worker_lost_detected"] = block["workers_lost"] >= 1
            row["range_reassigned"] = block["reassignments"] >= 1
        if name in ("worker_kill", "storm"):
            row["unprotected_loses_range"] = bool(
                block["unprotected_hard_failure"])
        if name in ("straggler", "storm"):
            row["straggler_flagged"] = block["stragglers_flagged"] >= 1
        checks.update({f"{name}.{k}": v for k, v in row.items()})
    return checks
