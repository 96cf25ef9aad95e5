"""Serving fleet: ring determinism + bounded spill, hedge first-writer-wins
and budget cap, breaker-aware routing, typed-failure re-route, connection
draining, and affinity-vs-random cache economics (the fleet drill itself is
in ``test_drills.py``).

The router's correctness bars (ISSUE 13): consistent-hash ownership must be
reproducible across construction orders and a removed node must only move
its own keys; a stalled primary must lose to its hedge (first writer wins)
without the governor's budget ever being exceeded; an open breaker must
demote its replica to last resort; ``drain`` must complete in-flight
requests before teardown and land ``drain`` events in the ledger; affinity
routing must beat random spray's aggregate cache hit rate on zipf traffic.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.serving import Overloaded, Servant
from swiftsnails_tpu.serving.fleet import Fleet
from swiftsnails_tpu.serving.loadgen import anchor_ids, zipf_weights
from swiftsnails_tpu.serving.router import (
    EwmaQuantile,
    HashRing,
    HedgeGovernor,
    route_hash,
    spill_order,
)
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    render_failures,
)
from swiftsnails_tpu.telemetry.registry import Histogram

DIM = 8
CAP = 64


def _table(cap=CAP, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((cap, DIM)).astype(np.float32)


def _mk_fleet(n=2, *, cap=CAP, buckets=(8,), cache_rows=64,
              breaker_threshold=0, ledger=None, **fleet_kw):
    table = _table(cap)

    def factory(rid):
        return Servant(
            {"t": table}, batch_buckets=buckets, cache_rows=cache_rows,
            breaker_threshold=breaker_threshold)

    return table, Fleet(factory, replicas=n, ledger=ledger, **fleet_kw)


def _owned_key(fleet, rid, lo=0, hi=CAP):
    """First key in [lo, hi) whose ring owner is ``rid``."""
    for k in range(lo, hi):
        if fleet._ring.successors(route_hash(k))[0] == rid:
            return k
    raise AssertionError(f"no key in [{lo}, {hi}) owned by {rid}")


# ------------------------------------------------------------ hash ring ----


def test_ring_ownership_is_insertion_order_invariant():
    nodes = [f"r{i}" for i in range(4)]
    r1, r2 = HashRing(), HashRing()
    for n in nodes:
        r1.add(n)
    for n in reversed(nodes):
        r2.add(n)
    for key in range(500):
        h = route_hash(key)
        assert r1.owner(h) == r2.owner(h)
        assert r1.successors(h) == r2.successors(h)
    # successors is a permutation of the members, owner first
    order = r1.successors(route_hash(17))
    assert sorted(order) == nodes and order[0] == r1.owner(route_hash(17))


def test_ring_remove_moves_only_the_victims_keys():
    ring = HashRing()
    for i in range(4):
        ring.add(f"r{i}")
    before = {k: ring.owner(route_hash(k)) for k in range(500)}
    ring.remove("r2")
    for k, owner in before.items():
        new = ring.owner(route_hash(k))
        if owner == "r2":
            assert new != "r2"  # re-homed somewhere alive
        else:
            assert new == owner  # everyone else's keys did not move
    assert "r2" not in ring and len(ring) == 3


def test_spill_order_bounded_load():
    loads = {"a": 10, "b": 0}
    # total = 11, cap = ceil(1.5 * 11 / 2) = 9: the owner at 10 spills
    ordered, spilled, cap = spill_order(["a", "b"], loads.get, spill=1.5)
    assert spilled and ordered == ["b", "a"] and loads["a"] >= cap
    # owner under cap keeps the key
    loads = {"a": 1, "b": 0}
    ordered, spilled, _ = spill_order(["a", "b"], loads.get, spill=1.5)
    assert not spilled and ordered == ["a", "b"]
    # uniformly at cap: the owner keeps the request (moving it would shed
    # affinity without shedding queueing)
    loads = {"a": 4, "b": 4}
    ordered, spilled, _ = spill_order(["a", "b"], loads.get, spill=0.5)
    assert not spilled and ordered == ["a", "b"]


# --------------------------------------------------------- hedge policy ----


def test_ewma_quantile_holds_floor_until_warm():
    eq = EwmaQuantile(initial=25.0, min_samples=8)
    for _ in range(7):
        eq.observe(1.0)
    assert eq.value == 25.0  # cold: two lucky samples must not arm hedges
    eq.observe(1.0)
    assert eq.value == 1.0  # first full estimate replaces the floor
    for _ in range(64):
        eq.observe(100.0)
    assert eq.value > 50.0  # tracks the tail once the window turns over


def test_hedge_governor_budget_cap():
    gov = HedgeGovernor(budget_pct=10.0)
    assert not gov.allow()  # zero observed requests: never hedge
    for _ in range(9):
        gov.note_request()
    assert not gov.allow()  # 1 > 10% of 9
    gov.note_request()
    assert gov.allow()  # 1 <= 10% of 10
    gov.note_hedge()
    assert not gov.allow()  # budget spent
    assert HedgeGovernor(0.0).allow() is False  # 0 disables outright


def test_hedge_first_writer_wins(tmp_path):
    ledger = Ledger(str(tmp_path / "l.jsonl"))
    table, fleet = _mk_fleet(
        2, ledger=ledger, hedge_budget_pct=100.0, hedge_p95_ms=15.0)
    with fleet:
        reps = {r.id: r for r in fleet.replicas()}
        key = _owned_key(fleet, "r0")
        release = threading.Event()
        reps["r0"].request_hook = lambda kernel: release.wait(10)
        got = fleet.pull([key], key=key)  # primary parked: the hedge answers
        release.set()
        np.testing.assert_array_equal(got, table[[key]])
        reg = fleet.registry
        assert reg.counter("serve.hedged").value == 1
        assert reg.counter("serve.hedge_won").value == 1
        assert fleet.stats()["hedge"]["hedged"] == 1
    ev = ledger.latest("hedge")
    assert ev is not None and ev["source"] == "fleet"
    assert ev["primary"] == "r0" and ev["hedge"] == "r1"
    assert "HEDGE    kernel=pull" in render_failures(ledger)
    assert "r0->r1" in render_failures(ledger)


def test_hedge_budget_zero_never_hedges():
    table, fleet = _mk_fleet(2, hedge_budget_pct=0.0, hedge_p95_ms=5.0)
    with fleet:
        reps = {r.id: r for r in fleet.replicas()}
        key = _owned_key(fleet, "r0")
        reps["r0"].request_hook = lambda kernel: time.sleep(0.05)
        got = fleet.pull([key], key=key)  # slow, but served by the owner
        np.testing.assert_array_equal(got, table[[key]])
        assert fleet.registry.counter("serve.hedged").value == 0


# ------------------------------------------------------ breakers/reroute ---


def test_open_breaker_demotes_replica_to_last_resort():
    table, fleet = _mk_fleet(2, breaker_threshold=1, hedge_budget_pct=0.0)
    with fleet:
        reps = {r.id: r for r in fleet.replicas()}
        key = _owned_key(fleet, "r0")
        reps["r0"].servant.breakers["pull"].record_failure()  # trips at 1
        assert fleet._breaker_open(reps["r0"], "pull")
        got = fleet.pull([key], key=key)
        np.testing.assert_array_equal(got, table[[key]])
        # the affinity owner was walked around, not dispatched to
        assert reps["r0"].requests == 0 and reps["r1"].requests == 1
        assert fleet.health()["status"] == "degraded"


def test_typed_failure_reroutes_synchronously():
    table, fleet = _mk_fleet(2, hedge_budget_pct=0.0)
    with fleet:
        reps = {r.id: r for r in fleet.replicas()}
        key = _owned_key(fleet, "r0")

        def sick(kernel):
            raise Overloaded("synthetic queue-full")

        reps["r0"].request_hook = sick
        got = fleet.pull([key], key=key)
        np.testing.assert_array_equal(got, table[[key]])
        assert fleet.registry.counter("fleet.reroute").value == 1
        assert fleet.stats()["reroutes"] == 1


# ------------------------------------------------------------- draining ----


def test_drain_completes_inflight_requests(tmp_path):
    ledger = Ledger(str(tmp_path / "l.jsonl"))
    table, fleet = _mk_fleet(2, ledger=ledger, hedge_budget_pct=0.0)
    with fleet:
        reps = {r.id: r for r in fleet.replicas()}
        key = _owned_key(fleet, "r0")
        gate = threading.Event()
        entered = threading.Event()

        def parked(kernel):
            entered.set()
            assert gate.wait(10)

        reps["r0"].request_hook = parked
        result = {}
        puller = threading.Thread(
            target=lambda: result.update(rows=fleet.pull([key], key=key)),
            daemon=True)
        puller.start()
        assert entered.wait(10)  # the request is in flight on r0
        records = {}
        drainer = threading.Thread(
            target=lambda: records.update(drain=fleet.drain("r0")),
            daemon=True)
        drainer.start()
        time.sleep(0.1)
        assert drainer.is_alive() and "drain" not in records  # waiting it out
        gate.set()
        puller.join(10)
        drainer.join(10)
        np.testing.assert_array_equal(result["rows"], table[[key]])
        rec = records["drain"]
        assert rec["clean"] is True and rec["inflight_at_start"] == 1
        assert rec["remaining_replicas"] == 1
        assert [r.id for r in fleet.replicas()] == ["r1"]
        # the survivor serves what the drained replica owned
        np.testing.assert_array_equal(
            fleet.pull([key], key=key), table[[key]])
    ev = ledger.latest("drain")
    assert ev is not None and ev["phase"] == "complete" and ev["clean"]
    out = render_failures(ledger)
    assert "DRAIN    r0 start" in out and "DRAIN    r0 complete" in out


def test_add_replica_extends_the_ring():
    _, fleet = _mk_fleet(1, hedge_budget_pct=0.0)
    with fleet:
        assert len(fleet._ring) == 1
        rid = fleet.add_replica()
        assert rid == "r1" and len(fleet._ring) == 2
        assert sorted(r.id for r in fleet.replicas()) == ["r0", "r1"]


# -------------------------------------------------- affinity vs random -----


def _aggregate_hit_rate(fleet):
    hits = sum(r.servant.cache.hits for r in fleet.replicas())
    misses = sum(r.servant.cache.misses for r in fleet.replicas())
    return hits / max(hits + misses, 1)


def test_affinity_beats_random_on_zipf_traffic():
    cap, batch, n_anchors = 256, 4, 64
    weights = zipf_weights(n_anchors, 1.1)
    rng = np.random.default_rng(7)
    anchors = rng.choice(n_anchors, size=400, p=weights)
    rates = {}
    for affinity in (True, False):
        _, fleet = _mk_fleet(
            2, cap=cap, buckets=(batch,), cache_rows=16,
            affinity=affinity, hedge_budget_pct=0.0)
        with fleet:
            for a in anchors:
                ids = anchor_ids(int(a), batch, cap)
                fleet.pull(ids, key=int(ids[0]))
            rates[affinity] = _aggregate_hit_rate(fleet)
    # same zipf trace, same per-replica LRU budget: keeping a key slice on
    # its owner must beat spraying the global head over every cache
    assert rates[True] > rates[False]


# --------------------------------------------- histogram + failure lines ---


def test_histogram_summary_percentiles():
    h = Histogram("t")
    assert h.summary() == {"count": 0}  # empty: no percentile keys at all
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["p50"] == 50.0 and s["p95"] == 95.0 and s["p99"] == 99.0
    assert s["p99"] >= s["p95"] >= s["p50"]


def test_hedge_and_drain_failure_lines_render(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("hedge", {
        "source": "fleet", "kernel": "pull", "primary": "r0", "hedge": "r1",
        "budget_ms": 25.0, "hedged_total": 1, "hedge_rate_pct": 1.0,
    })
    led.append("drain", {
        "source": "fleet", "phase": "start", "replica": "r1",
        "inflight": 2, "remaining_replicas": 1,
    })
    led.append("drain", {
        "source": "fleet", "phase": "complete", "replica": "r1",
        "inflight_at_start": 2, "waited_ms": 12.5, "clean": True,
        "remaining_replicas": 1,
    })
    out = render_failures(led)
    assert "HEDGE    kernel=pull" in out and "r0->r1" in out
    assert "DRAIN    r1 start" in out and "inflight=2" in out
    assert "DRAIN    r1 complete" in out and "clean=True" in out
