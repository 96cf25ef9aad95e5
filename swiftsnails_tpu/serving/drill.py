"""Availability drills on the read path: one Servant, then a replica fleet.

Used by ``tools/chaos_drill.py --serve`` / ``--fleet`` and the tier-1 tests.
Both drills load a tiny verified word2vec checkpoint and fire a seeded
:class:`~swiftsnails_tpu.resilience.chaos.ChaosPlan` fault schedule at it
through the Servant's ``fault_hook``.

:func:`serve_chaos_drill` runs the schedule (``serve_io_error`` storms +
``serve_slow`` stalls) against one live :class:`Servant` twice:

* **protected leg** — circuit breakers + degraded stale-LRU reads on: the
  share of requests served (fresh or degraded) must hold the floor and the
  breaker must close again;
* **unprotected control leg** — breakers and degraded mode disabled; the
  same schedule must produce a *hard failure* (an unhandled dispatch error
  reaching the caller). A control that survives means the matrix is not
  exercising the serve path.

Two more drills ride along: ``reload_corrupt`` (the newest checkpoint is
corrupted on disk, then a live reload is requested — the shadow-verify swap
must reject it and keep the old version serving) and the ``tier_bitflip``
recovery drill from :mod:`swiftsnails_tpu.resilience.drill`.

:func:`fleet_chaos_drill` makes one replica of a 2-replica
:class:`~swiftsnails_tpu.serving.fleet.Fleet` sick mid-storm: killed with
``serve_io_error`` (its breaker trips, the router walks around it) or slowed
with ``serve_slow`` (tail hedges rescue the stragglers). The fleet must hold
the floor and every anomaly must leave a complete trace tree.

Each drill's verdict is a dictionary of named checks
(:func:`serve_drill_checks`, :func:`fleet_drill_checks`) computed from the
result it returned; the tool exits by it and the tests assert it. The
drills count requests, events and versions; they run on the CPU and report
no time or rate.
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Callable, Dict, Optional

import numpy as np

SERVE_SEED = 11
FLEET_SEED = 13
AVAILABILITY_FLOOR_PCT = 99.0
SERVICE_FLOOR_MS = 6.0  # the fleet replicas' stand-in for a dispatch's device time
BATCH = 8
ZIPF_A = 1.1
_SLOW_MS = 25.0


def build_word2vec_checkpoint(root: str, dim: int, capacity: int):
    """Init (no training needed: serving is layout + lookup) and save a
    verified packed word2vec checkpoint; returns the serving config and the
    state (the reload drill writes a second, newer checkpoint from it)."""
    from swiftsnails_tpu.framework.checkpoint import save_checkpoint
    from swiftsnails_tpu.framework.quality import paired_corpus
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    ids, vocab = paired_corpus(n_pairs=32, reps=4, seed=SERVE_SEED)
    cfg = Config({
        "dim": str(dim), "capacity": str(capacity), "packed": "1",
        "seed": str(SERVE_SEED), "subsample": "0",
    })
    trainer = Word2VecTrainer(cfg, mesh=None, corpus_ids=ids, vocab=vocab)
    state = trainer.init_state()
    save_checkpoint(root, state, step=1, wait=True)
    return cfg, state


def _fault_hook(plan, injected: Dict[str, int], slow_ms: float = _SLOW_MS):
    """Servant ``fault_hook`` driven by the plan's serve schedule: the hook
    fires once per dispatched batch, indexed per kernel, and counts what it
    injected by kind."""

    def hook(kernel: str, index: int) -> None:
        kind = plan.serve_fault(index)
        if kind is not None:
            injected[kind] = injected.get(kind, 0) + 1
        if kind == "serve_io_error":
            raise OSError(f"chaos: injected {kernel} read error @{index}")
        if kind == "serve_slow":
            time.sleep(slow_ms / 1e3)

    return hook


def _drive_leg(servant, plan, hot: np.ndarray, requests: int,
               cooldown_ms: float) -> Dict:
    """Fire ``requests`` pulls over the ``hot`` id set under the plan's
    fault schedule; every request is tallied as served or failed. The
    stale-LRU inventory was warmed (and version-bumped) by the caller, so
    each pull goes through dispatch — and through the fault hook — unless
    the breaker short-circuits it to a degraded serve."""
    from swiftsnails_tpu.serving.breaker import Unavailable

    injected: Dict[str, int] = {}
    servant.fault_hook = _fault_hook(plan, injected)
    reg = servant.registry
    degraded0 = int(reg.counter("serve.degraded_hits").value)
    served = failed = 0
    first_error: Optional[str] = None
    br = servant.breakers.get("pull")
    for _ in range(requests):
        try:
            servant.pull(hot)
            served += 1
        except (Unavailable, OSError, RuntimeError) as e:
            failed += 1
            if first_error is None:
                first_error = f"{type(e).__name__}: {e}"
    # recovery phase: faults exhausted — wait out the cooldown and keep
    # pulling until the half-open probe closes the breaker again
    recovered = br is None or br.state == "closed"
    if br is not None and not recovered:
        deadline = time.perf_counter() + 50 * (cooldown_ms / 1e3)
        while time.perf_counter() < deadline:
            time.sleep(cooldown_ms / 1e3 / 4)
            try:
                servant.pull(hot)
                served += 1
            except (Unavailable, OSError, RuntimeError):
                failed += 1
            if br.state == "closed":
                recovered = True
                break
    servant.fault_hook = None
    total = served + failed
    degraded_hits = int(reg.counter("serve.degraded_hits").value) - degraded0
    return {
        "requests": total,
        "served": served,
        "failed": failed,
        "availability_pct": round(100.0 * served / max(total, 1), 3),
        "degraded_share_pct": round(
            100.0 * degraded_hits / max(total * len(hot), 1), 3),
        "injected": injected,
        "first_error": first_error,
        "recovered": bool(recovered),
        "breaker_trips": br.trips if br is not None else 0,
    }


def serve_chaos_drill(workdir: Optional[str] = None, ledger=None) -> Dict:
    """Run the availability drill on one Servant; :func:`serve_drill_checks`
    is the verdict on what it returns."""
    from swiftsnails_tpu.framework.checkpoint import save_checkpoint
    from swiftsnails_tpu.resilience.chaos import (
        ChaosPlan, corrupt_checkpoint_dir, parse_chaos_spec,
    )
    from swiftsnails_tpu.resilience.drill import drill_tier_bitflip
    from swiftsnails_tpu.serving.engine import Servant

    dim, capacity = 16, 1 << 9
    requests = 24
    cooldown_ms = 60.0
    hot = np.arange(32, dtype=np.int32)
    # storm of read errors early (trips the breaker), a second burst after
    # the first recovery window, and a couple of stalls in between
    spec = ("serve_io_error@0-5,serve_slow@8-9,"
            f"serve_io_error@{requests // 2}-{requests // 2 + 3}")

    own_tmp = None
    if workdir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="ssn-chaos-serve-")
        workdir = own_tmp.name
    try:
        root = os.path.join(workdir, "ckpt")
        cfg, state = build_word2vec_checkpoint(root, dim, capacity)

        def _open(protected: bool) -> Servant:
            sv = Servant.from_checkpoint(
                root, cfg, ledger=ledger if protected else None,
                cache_rows=max(len(hot) * 2, 128),
                breaker_threshold=3 if protected else 0,
                breaker_cooldown_ms=cooldown_ms,
                degraded=protected,
            )
            # warm the stale-LRU inventory, then bump the version so every
            # drill pull goes through dispatch (where the faults live) while
            # the warmed rows stay available for degraded serves
            sv.pull(hot)
            sv.reload(dict(sv._tables), manifest=sv.manifest)
            return sv

        with _open(protected=True) as served:
            protected = _drive_leg(
                served, ChaosPlan(parse_chaos_spec(spec), seed=SERVE_SEED,
                                  ledger=ledger),
                hot, requests, cooldown_ms)
            health = served.health()

            # reload_corrupt drill against the SAME live servant: write a
            # newer checkpoint, corrupt it on disk, ask for a live reload —
            # the shadow verify must reject it and keep the version serving
            plan = ChaosPlan(parse_chaos_spec("reload_corrupt@0"),
                             seed=SERVE_SEED, ledger=ledger)
            save_checkpoint(root, state, step=2, wait=True)
            if plan.wants_reload_corrupt(0):
                corrupt_checkpoint_dir(root, step=2, rng=plan.rng,
                                       ledger=ledger)
            kept = served.version
            reload_rejected = False
            reload_error = None
            try:
                served.reload_from_checkpoint(root, cfg, step=2)
            except Exception as e:  # noqa: BLE001 — the rejection IS the pass
                reload_rejected = True
                reload_error = f"{type(e).__name__}: {str(e)[:90]}"
            still_serving = bool(
                served.version == kept
                and len(served.pull(hot[:4])) == 4)

        with _open(protected=False) as bare:
            control = _drive_leg(
                bare, ChaosPlan(parse_chaos_spec(spec), seed=SERVE_SEED),
                hot, requests, cooldown_ms)

        out = {
            "spec": spec,
            "seed": SERVE_SEED,
            "requests": protected["requests"],
            "availability_pct": protected["availability_pct"],
            "degraded_share_pct": protected["degraded_share_pct"],
            "injected": protected["injected"],
            "breaker_trips": protected["breaker_trips"],
            "recovered": protected["recovered"],
            "health": {"status": health["status"],
                       "degraded_hits": health["degraded_hits"]},
            "unprotected_hard_failure": control["failed"] > 0,
            "control_availability_pct": control["availability_pct"],
            "control_first_error": control["first_error"],
            "reload_corrupt_rejected": bool(
                reload_rejected and still_serving),
            "reload_corrupt_error": reload_error,
        }
        try:
            out["tier_bitflip"] = drill_tier_bitflip(
                os.path.join(workdir, "tier-drill"))
        except Exception as e:  # noqa: BLE001 — an unrecovered drill
            out["tier_bitflip"] = {
                "recovered": False, "error": f"{type(e).__name__}: {e}"}
        return out
    finally:
        if own_tmp is not None:
            own_tmp.cleanup()


def serve_drill_checks(res: Dict) -> Dict[str, bool]:
    """The serve drill's verdict, by name, from its result."""
    return {
        "availability_floor": (
            res["availability_pct"] >= AVAILABILITY_FLOOR_PCT),
        "io_error_storm_injected": bool(
            res["injected"].get("serve_io_error")),
        "breaker_tripped": res["breaker_trips"] >= 1,
        "breaker_recovered": bool(res["recovered"]),
        "degraded_reads_served": res["degraded_share_pct"] > 0.0,
        "unprotected_hard_failure": bool(res["unprotected_hard_failure"]),
        "reload_corrupt_rejected": bool(res["reload_corrupt_rejected"]),
        "tier_bitflip_recovered": bool(res["tier_bitflip"].get("recovered")),
    }


# ------------------------------------------------------------ fleet drill ---


def _floor_hook(floor_ms: float) -> Callable[[str, int], None]:
    """A healthy replica's dispatch: a GIL-free stall on the dispatcher
    thread, so each replica is a single-server queue whatever the host."""
    floor_s = floor_ms / 1e3

    def hook(kernel: str, index: int) -> None:
        time.sleep(floor_s)

    return hook


def _quiesce(fleet, timeout_s: float = 10.0) -> None:
    """Wait for every queue to empty before the counters are read."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        busy = any(
            rep.inflight > 0 or any(rep.servant.queue_depths().values())
            for rep in fleet.replicas()
        )
        if not busy:
            break
        time.sleep(0.05)
    time.sleep(0.05)


def _prewarm_healthy(fleet, capacity: int, exclude: str) -> None:
    ids = np.arange(BATCH, dtype=np.int32) % capacity
    for rep in fleet.replicas():
        if rep.id != exclude:
            rep.servant.pull(ids)


def fleet_chaos_drill(workdir: Optional[str] = None) -> Dict[str, Dict]:
    """``tools/chaos_drill.py --fleet``: one replica gets sick mid-storm;
    the fleet must hold the availability floor via re-route + hedging.

    Two drills, reusing the serving chaos kinds against exactly one
    replica: ``kill_replica`` storms it with ``serve_io_error`` dispatch
    faults (breaker trips, routing walks around it), ``slow_replica``
    storms it with ``serve_slow`` stalls (hedges rescue the stragglers).
    :func:`fleet_drill_checks` is the verdict on what it returns.
    """
    from swiftsnails_tpu.resilience.chaos import ChaosPlan, parse_chaos_spec
    from swiftsnails_tpu.serving.fleet import Fleet
    from swiftsnails_tpu.serving.loadgen import run_open_loop
    from swiftsnails_tpu.telemetry.request_trace import (
        RequestTracer,
        tree_complete,
    )

    dim, capacity = 16, 1 << 11
    duration_s = 1.2
    qps = 80.0

    own_tmp = None
    if workdir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="ssn-fleet-chaos-")
        workdir = own_tmp.name
    try:
        root = os.path.join(workdir, "ckpt-w2v")
        cfg, _ = build_word2vec_checkpoint(root, dim, capacity)
        results: Dict[str, Dict] = {}
        for drill, kind, stall_ms in (
            ("kill_replica", "serve_io_error", 0.0),
            ("slow_replica", "serve_slow", 90.0),
        ):
            # storm the victim's first ~60 dispatches (the whole run, at
            # this rate, is ~100 dispatches on that replica)
            spec = ",".join(f"{kind}@{i}" for i in range(0, 60))
            plan = ChaosPlan(parse_chaos_spec(spec), seed=FLEET_SEED)
            # tail-keep only (rate 0): every hedged / re-routed / degraded
            # request must still land in the ring as a complete span tree
            tracer = RequestTracer(0.0, anomaly_keep=True, seed=FLEET_SEED)
            with Fleet.from_checkpoint(
                root, cfg, replicas=2,
                batch_buckets=(BATCH,), cache_rows=256, queue_depth=64,
                breaker_threshold=3, breaker_cooldown_ms=400.0,
                request_tracer=tracer,
            ).configure(hedge_budget_pct=30.0) as fleet:
                reps = fleet.replicas()
                for rep in reps[:-1]:
                    rep.servant.fault_hook = _floor_hook(SERVICE_FLOOR_MS)
                victim = reps[-1]

                def sick_hook(kernel: str, index: int,
                              _plan=plan) -> None:
                    time.sleep(SERVICE_FLOOR_MS / 1e3)
                    k = _plan.serve_fault(index)
                    if k == "serve_io_error":
                        raise OSError("chaos: injected serve I/O error")
                    if k == "serve_slow":
                        time.sleep(stall_ms / 1e3)

                victim.servant.fault_hook = sick_hook
                _prewarm_healthy(fleet, capacity, exclude=victim.id)
                res = run_open_loop(
                    lambda anchor, ids: fleet.pull(ids),
                    qps=qps, duration_s=duration_s, seed=FLEET_SEED,
                    id_space=capacity, batch=BATCH, zipf_a=ZIPF_A,
                )
                _quiesce(fleet)
                reg = fleet.registry
                victim_breaker = \
                    victim.servant.breakers["pull"].snapshot()
                # every anomaly trace must be a complete tree, and the
                # drill's signature anomaly must be drillable end to end:
                # a re-route hop (kill) / both hedge attempts (slow)
                anomalies = [c.to_dict() for c in tracer.anomaly_traces()]
                trees_ok = bool(anomalies) and all(
                    tree_complete(t, require=("attempt", "request"))
                    for t in anomalies)
                if drill == "kill_replica":
                    sig = [t for t in anomalies
                           if "reroute" in t["anomalies"]
                           and tree_complete(t, require=(
                               "attempt", "reroute", "request"))]
                else:
                    sig = [t for t in anomalies
                           if "hedge" in t["anomalies"]
                           and sum(1 for s in t["spans"]
                                   if s["name"] == "attempt") >= 2
                           and tree_complete(t, require=(
                               "attempt", "request"))]
                trace_path = os.path.join(
                    workdir, f"fleet-{drill}-traces.json")
                try:
                    tracer.export_chrome(trace_path)
                except OSError:
                    trace_path = None
                results[drill] = {
                    "anomaly_traces": len(anomalies),
                    "trace_trees_complete": trees_ok,
                    "signature_traces": len(sig),
                    "trace_id": sig[0]["trace_id"] if sig else None,
                    "trace_export": trace_path,
                    "availability_pct": round(
                        100.0 - res["error_rate_pct"], 3),
                    "requests": res["requests"],
                    "errors": res["error_types"],
                    "reroutes": int(reg.counter("fleet.reroute").value),
                    "hedged": int(reg.counter("serve.hedged").value),
                    "hedge_won": int(reg.counter("serve.hedge_won").value),
                    "victim": victim.id,
                    "victim_breaker_trips": victim_breaker["trips"],
                }
        return results
    finally:
        if own_tmp is not None:
            own_tmp.cleanup()


def fleet_drill_checks(results: Dict[str, Dict]) -> Dict[str, bool]:
    """The fleet drills' verdict, ``<drill>.<check>`` by name."""
    checks: Dict[str, bool] = {}
    for drill, res in results.items():
        checks[f"{drill}.availability_floor"] = (
            res["availability_pct"] >= AVAILABILITY_FLOOR_PCT)
        checks[f"{drill}.trace_trees_complete"] = bool(
            res["trace_trees_complete"])
        checks[f"{drill}.signature_trace_kept"] = res["signature_traces"] >= 1
    kill, slow = results.get("kill_replica"), results.get("slow_replica")
    if kill is not None:
        checks["kill_replica.breaker_tripped"] = (
            kill["victim_breaker_trips"] >= 1)
        checks["kill_replica.rerouted"] = kill["reroutes"] >= 1
    if slow is not None:
        checks["slow_replica.hedged"] = slow["hedged"] >= 1
    return checks
