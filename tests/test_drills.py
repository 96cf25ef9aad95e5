"""The fault drills of every plane, as tests.

Each drill (``swiftsnails_tpu/<plane>/drill.py``) runs ONCE for the whole
test run (``drill_once`` in ``conftest.py``, with the drill's own time
limit) and one parametrised test has a case per check its
verdict names. A case asserts a count, a parity or an event, never a time.

The verdict functions are what ``tools/chaos_drill.py`` exits by, so "a
missed check fails loudly" is tested on them directly: a result broken by
hand must be named by the verdict and must turn the tool's exit code.
"""

import copy
import importlib.util
import json
import os
from typing import Callable, NamedTuple

import pytest

from swiftsnails_tpu.cluster.drill import (
    CLUSTER_DRILL_SPECS,
    cluster_drill_checks,
    run_cluster_drills,
)
from swiftsnails_tpu.freshness.drill import (
    freshness_chaos_drill,
    freshness_drill_checks,
)
from swiftsnails_tpu.net.drill import net_chaos_drill, net_drill_checks
from swiftsnails_tpu.serving.drill import (
    fleet_chaos_drill,
    fleet_drill_checks,
    serve_chaos_drill,
    serve_drill_checks,
)
from swiftsnails_tpu.telemetry.drill import drift_drill, drift_drill_checks
from swiftsnails_tpu.telemetry.ledger import Ledger

# ------------------------------------------------------------- the drills ---


class Drill(NamedTuple):
    run: Callable       # the drill, in a workdir
    checks: Callable    # its verdict: result -> {check: bool}
    limit_s: int        # its own time limit


def _serve(workdir):
    res = serve_chaos_drill(
        workdir=os.path.join(workdir, "w"),
        ledger=Ledger(os.path.join(workdir, "LEDGER.jsonl")))
    res["ledger"] = os.path.join(workdir, "LEDGER.jsonl")
    return res


DRILLS = {
    "serve": Drill(_serve, serve_drill_checks, 240),
    "fleet": Drill(fleet_chaos_drill, fleet_drill_checks, 240),
    "freshness": Drill(freshness_chaos_drill, freshness_drill_checks, 240),
    "net": Drill(net_chaos_drill, net_drill_checks, 420),
    "cluster": Drill(run_cluster_drills, cluster_drill_checks, 240),
    "drift": Drill(drift_drill, drift_drill_checks, 240),
}

# every check each verdict names; ``test_verdict_names_every_check`` holds
# the verdicts to these lists, so a check cannot go without a test noticing
CHECKS = {
    "serve": (
        "availability_floor", "io_error_storm_injected", "breaker_tripped",
        "breaker_recovered", "degraded_reads_served",
        "unprotected_hard_failure", "reload_corrupt_rejected",
        "tier_bitflip_recovered",
    ),
    "fleet": tuple(
        [f"{d}.{c}" for d in ("kill_replica", "slow_replica")
         for c in ("availability_floor", "trace_trees_complete",
                   "signature_trace_kept")]
        + ["kill_replica.breaker_tripped", "kill_replica.rerouted",
           "slow_replica.hedged"]),
    "freshness": tuple(
        f"{d}.{c}" for d in ("publisher_kill", "corrupt_delta", "forced_gap")
        for c in ("fell_back", "shared_version", "parity_zero",
                  "fallback_trace_complete")),
    "net": (
        "proc_kill.respawned", "proc_kill.rejoined",
        "proc_kill.fresh_incarnation", "proc_kill.parity_zero",
        "net_partition.missed_write", "net_partition.stale_write_refused",
        "net_partition.shared_version",
        "net_slow.timed_out_typed", "net_slow.stall_bounded",
        "net_slow.serves_after_heal",
        "publisher_kill.fell_back", "publisher_kill.converged",
        "publisher_kill.parity_zero",
    ),
    "cluster": tuple(
        [f"{d}.{c}" for d in CLUSTER_DRILL_SPECS
         for c in ("accounting_exact", "finite", "loss_parity")]
        + [f"{d}.{c}" for d in ("worker_kill", "partition", "storm")
           for c in ("worker_lost_detected", "range_reassigned")]
        + ["worker_kill.unprotected_loses_range",
           "storm.unprotected_loses_range",
           "straggler.straggler_flagged", "storm.straggler_flagged"]),
    "drift": (
        "detected_in_band", "single_drift_event", "bundle_complete",
        "attribution_host_blocked",
    ),
}

# ``net_slow.stall_bounded`` compares an elapsed time with the read timeout:
# the verdict keeps it (a hang must fail the drill), no case asserts it
TIMED = {("net", "net_slow.stall_bounded")}


@pytest.fixture(scope="session")
def drill_result(drill_once):
    def get(name):
        return drill_once(f"drill-{name}", DRILLS[name].run,
                          DRILLS[name].limit_s)

    return get


@pytest.mark.parametrize("name", sorted(DRILLS))
def test_verdict_names_every_check(drill_result, name):
    assert set(DRILLS[name].checks(drill_result(name))) == set(CHECKS[name])


@pytest.mark.parametrize(
    "name,check",
    [(n, c) for n in sorted(CHECKS) for c in CHECKS[n] if (n, c) not in TIMED])
def test_drill_check_holds(drill_result, name, check):
    res = drill_result(name)
    assert DRILLS[name].checks(res)[check], (check, res)


# ------------------------------------------------- what else the drills left


def test_serve_drill_control_leg_fails_with_the_injected_error(drill_result):
    res = drill_result("serve")
    assert "OSError" in res["control_first_error"]
    assert res["control_availability_pct"] < res["availability_pct"]


@pytest.mark.parametrize("kind", ["breaker", "degraded", "chaos"])
def test_serve_drill_writes_its_events_to_the_ledger(drill_result, kind):
    assert Ledger(drill_result("serve")["ledger"]).latest(kind) is not None


def test_ledger_report_renders_the_drift_drills_ledger(drill_result, capsys):
    from swiftsnails_tpu.cli import main

    assert main(["ledger-report", drill_result("drift")["ledger"],
                 "--failures"]) == 0
    out = capsys.readouterr().out
    assert "slow_step" in out and "DRIFT" in out


# ------------------------------------------- a missed check fails loudly ---
# (drill, how to break its result, the check the verdict must then name)


def _set(path, value):
    def breaker(res):
        node = res
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value

    return breaker


BROKEN = [
    ("serve", _set(("availability_pct",), 92.0), "availability_floor"),
    ("serve", _set(("unprotected_hard_failure",), False),
     "unprotected_hard_failure"),
    ("serve", _set(("reload_corrupt_rejected",), False),
     "reload_corrupt_rejected"),
    ("serve", _set(("tier_bitflip",), {"recovered": False}),
     "tier_bitflip_recovered"),
    ("serve", _set(("recovered",), False), "breaker_recovered"),
    ("fleet", _set(("kill_replica", "availability_pct"), 97.5),
     "kill_replica.availability_floor"),
    ("fleet", _set(("kill_replica", "trace_trees_complete"), False),
     "kill_replica.trace_trees_complete"),
    ("fleet", _set(("slow_replica", "signature_traces"), 0),
     "slow_replica.signature_trace_kept"),
    ("fleet", _set(("kill_replica", "reroutes"), 0), "kill_replica.rerouted"),
    ("fleet", _set(("slow_replica", "hedged"), 0), "slow_replica.hedged"),
    ("freshness", _set(("forced_gap", "parity"), 0.25),
     "forced_gap.parity_zero"),
    ("freshness", _set(("forced_gap", "fallbacks"), 0),
     "forced_gap.fell_back"),
    ("freshness", _set(("corrupt_delta", "fallback_traces"), 0),
     "corrupt_delta.fallback_trace_complete"),
    ("freshness", _set(("publisher_kill", "replica_versions"),
                       {"r0": 3, "r1": 4}), "publisher_kill.shared_version"),
    ("net", _set(("proc_kill", "respawns"), 0), "proc_kill.respawned"),
    ("net", _set(("proc_kill", "rejoined"), False), "proc_kill.rejoined"),
    ("net", _set(("proc_kill", "parity"), 0.01), "proc_kill.parity_zero"),
    ("net", _set(("net_partition", "stale_write_refused"), False),
     "net_partition.stale_write_refused"),
    ("net", _set(("net_partition", "versions"), {"r1": 1, "r2": 0}),
     "net_partition.shared_version"),
    ("net", _set(("net_slow", "timed_out_typed"), False),
     "net_slow.timed_out_typed"),
    ("net", _set(("publisher_kill", "parity"), 0.5),
     "publisher_kill.parity_zero"),
    ("cluster", _set(("storm", "lost_count"), 3), "storm.accounting_exact"),
    ("cluster", _set(("worker_kill", "duplicated_count"), 1),
     "worker_kill.accounting_exact"),
    ("cluster", _set(("partition", "loss_parity"), 0.2),
     "partition.loss_parity"),
    ("cluster", _set(("storm", "unprotected_hard_failure"), False),
     "storm.unprotected_loses_range"),
    ("cluster", _set(("straggler", "stragglers_flagged"), 0),
     "straggler.straggler_flagged"),
    ("cluster", _set(("worker_kill", "reassignments"), 0),
     "worker_kill.range_reassigned"),
    ("drift", _set(("detected",), False), "detected_in_band"),
    ("drift", _set(("drift_events",), 0), "single_drift_event"),
    ("drift", _set(("drift_events",), 3), "single_drift_event"),
    ("drift", _set(("bundle_complete",), False), "bundle_complete"),
    ("drift", _set(("attribution", "dominant"), "compute"),
     "attribution_host_blocked"),
    ("drift", _set(("attribution",), None), "attribution_host_blocked"),
]


@pytest.mark.parametrize(
    "name,breaker,check", BROKEN,
    ids=[f"{n}-{c}-{i}" for i, (n, _, c) in enumerate(BROKEN)])
def test_verdict_names_the_broken_check(drill_result, name, breaker, check):
    res = copy.deepcopy(drill_result(name))
    before = {k for k, ok in DRILLS[name].checks(res).items() if not ok}
    breaker(res)
    after = {k for k, ok in DRILLS[name].checks(res).items() if not ok}
    assert check in after and after - before <= {check}


# ----------------------------------------------- the tool exits by verdict ---


@pytest.fixture(scope="module")
def tool():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "chaos_drill.py")
    spec = importlib.util.spec_from_file_location("chaos_drill_tool", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(DRILLS))
def test_tool_exit_code_is_the_verdict(drill_result, tool, name, monkeypatch,
                                       capsys):
    """``chaos_drill.py --<drill>`` exits 0 on the drill's own result and
    nonzero, naming the check, on the same result broken by hand (the drill
    itself ran once already: the tool is handed its result)."""
    res = drill_result(name)
    target = ".".join(tool.DRILLS[name][:2])  # what --<name> calls
    monkeypatch.setattr(target, lambda *a, **kw: res)
    sound = [k for k, ok in DRILLS[name].checks(res).items() if not ok]
    assert tool.main([f"--{name}", "--json"]) == (1 if sound else 0)
    assert json.loads(capsys.readouterr().out)["failed"] == sound

    _, breaker, check = next(b for b in BROKEN if b[0] == name)
    broken = copy.deepcopy(res)
    breaker(broken)
    monkeypatch.setattr(target, lambda *a, **kw: broken)
    assert tool.main([f"--{name}", "--json"]) == 1
    assert check in json.loads(capsys.readouterr().out)["failed"]
    assert tool.main([f"--{name}"]) == 1
    out = capsys.readouterr().out
    assert "FAILED: " in out and f"{check:<40}  FAIL" in out
