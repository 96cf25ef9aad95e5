"""TCP replicas on the fleet ring (ISSUE 19 tentpole): RemoteServant
parity behind the unchanged router/breaker/hedge interfaces, stale-epoch
refusal, lease-driven drain + respawn under an injectable clock, the new
transport chaos kinds, and the ledger/ops surfaces (the transport drill
matrix itself is in ``test_drills.py``)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.net.fleet import NetFleet, ReplicaManager
from swiftsnails_tpu.net.remote import StaleEpoch
from swiftsnails_tpu.net.replica_server import ServantRpcServer
from swiftsnails_tpu.resilience.chaos import (
    ChaosPlan,
    ChaosSpecError,
    parse_chaos_spec,
)
from swiftsnails_tpu.serving import Servant
from swiftsnails_tpu.serving.breaker import OPEN
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    render_failures,
)
from swiftsnails_tpu.telemetry.ops import render_ops
from swiftsnails_tpu.utils.config import Config

DIM = 8
CAP = 64


def _table(seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((CAP, DIM)).astype(np.float32)


def _servant(table=None):
    t = _table() if table is None else table
    return Servant({"t": t.copy()}, batch_buckets=(8,), cache_rows=32)


def _cfg(**over):
    return Config({
        "net_connect_timeout_ms": "200", "net_read_timeout_ms": "400",
        "retry_max_attempts": "2", "retry_deadline_ms": "1500",
        "retry_base_ms": "2", "retry_cap_ms": "10", **over,
    })


def _serve(n=2, ledger=None, **cfg_over):
    servers = [ServantRpcServer(_servant(), ledger=ledger).start()
               for _ in range(n)]
    fleet = NetFleet.connect([s.address for s in servers], _cfg(**cfg_over),
                             ledger=ledger)
    return servers, fleet


# -- serving parity over the wire --------------------------------------------


def test_tcp_pull_is_bit_identical_to_in_process():
    servers, fleet = _serve()
    try:
        ids = np.array([3, 0, 17, CAP - 1], np.int64)
        reference = np.asarray(servers[0].servant.pull(ids))
        np.testing.assert_array_equal(np.asarray(fleet.pull(ids)), reference)
        st = fleet.stats()
        for rs in st["replicas"].values():
            assert rs["transport"] == "connected"
            assert rs["peer"] and rs["incarnation"]
    finally:
        fleet.close()
        for s in servers:
            s.stop()


def test_fleet_apply_lands_every_tcp_replica_on_one_epoch():
    # the first apply compiles the replica's scatter: on a loaded host that
    # outlasts the drills' 400 ms read deadline, and the retry then meets
    # the epoch its first try did land. The subject here is the epoch, so
    # the deadline is one no compile reaches
    servers, fleet = _serve(net_read_timeout_ms="60000",
                            retry_deadline_ms="120000")
    try:
        rows = np.array([4, 8, 15], np.int64)
        vals = np.random.default_rng(5).standard_normal(
            (3, DIM)).astype(np.float32)
        epoch = fleet.apply_rows({"t": (rows, vals)}, step=2)
        versions = {s.servant.version for s in servers}
        assert versions == {epoch}  # one shared epoch, no mixed serving
        for s in servers:
            np.testing.assert_array_equal(
                np.asarray(s.servant.pull(rows)), vals)
        np.testing.assert_array_equal(np.asarray(fleet.pull(rows)), vals)
    finally:
        fleet.close()
        for s in servers:
            s.stop()


def test_stale_epoch_refused_after_heal():
    servers, fleet = _serve(n=1)
    try:
        rep = fleet.replicas()[0]
        rows = np.array([1], np.int64)
        vals = np.ones((1, DIM), np.float32)
        v = rep.servant.apply_rows({"t": (rows, vals)}, version=5, step=1)
        assert v == 5
        # a write at/below the served version is the partitioned-side
        # stale write: refused typed, the replica must resync instead
        with pytest.raises(StaleEpoch):
            rep.servant.apply_rows({"t": (rows, vals)}, version=5, step=1)
        with pytest.raises(StaleEpoch):
            rep.servant.apply_rows({"t": (rows, vals)}, version=3, step=1)
        assert rep.servant.apply_rows({"t": (rows, vals)},
                                      version=6, step=2) == 6
    finally:
        fleet.close()
        for s in servers:
            s.stop()


def test_breakers_read_open_while_transport_down_and_pull_survives():
    servers, fleet = _serve()
    try:
        ids = np.array([2, 9], np.int64)
        reference = np.asarray(servers[0].servant.pull(ids))
        victim = fleet.replicas()[1]
        servers[1].stop()
        # the liveness probe notices without raising...
        h = victim.servant.health(read_timeout_ms=150.0)
        assert h["status"] == "unreachable"
        assert victim.servant.transport == "reconnecting"
        # ...the router's hot-path introspection demotes it (no RPC)...
        assert victim.servant.breakers.get("pull").state == OPEN
        # ...and routed pulls keep serving bit-identically from the live one
        for _ in range(4):
            np.testing.assert_array_equal(
                np.asarray(fleet.pull(ids)), reference)
    finally:
        fleet.close()
        for s in servers:
            s.stop()


# -- lease-driven membership -------------------------------------------------


class _FakeProc:
    """Stands in for a spawned replica process: points at an in-process
    server (no subprocess in tier-1)."""

    def __init__(self, server):
        self.host, self.port = server.address
        self.incarnation = server.incarnation
        self.pid = 4242
        self.closed = 0

    def close(self):
        self.closed += 1


class _FakeSpawner:
    def __init__(self, server):
        self.server = server
        self.spawned = 0

    def spawn(self):
        self.spawned += 1
        return _FakeProc(self.server)


def test_lease_expiry_drains_ring_and_respawns_with_fresh_incarnation(
        tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    servers, fleet = _serve(ledger=led)
    standby = ServantRpcServer(_servant(), ledger=led).start()
    clock = [0.0]
    mgr = ReplicaManager(fleet, spawner=_FakeSpawner(standby), ledger=led,
                         lease_ms=1_000.0, probe_timeout_ms=150.0,
                         clock=lambda: clock[0])
    try:
        assert mgr.tick() == []  # both answer: leases renew, nobody lost
        victim = fleet.replicas()[1]
        old_incarnation = victim.servant.incarnation
        servers[1].stop()
        clock[0] = 2.0  # 2000ms later: past the 1000ms lease
        lost = mgr.tick()
        assert lost == [victim.id]
        # the arc completed: drain -> respawn -> rejoin on a fresh id
        assert mgr.respawns == 1
        rids = {r.id for r in fleet.replicas()}
        assert victim.id not in rids and len(rids) == 2
        joined = next(r for r in fleet.replicas() if r.id != lost[0]
                      and r.servant.incarnation == standby.incarnation)
        assert joined.servant.incarnation != old_incarnation
        ids = np.array([7, 30], np.int64)
        np.testing.assert_array_equal(
            np.asarray(fleet.pull(ids)),
            np.asarray(servers[0].servant.pull(ids)))
        events = [r["event"] for r in led.records("transport")]
        assert "drained" in events and "respawn" in events
        # the membership ledger carries the worker-lost half of the story
        assert any(r.get("action") == "worker-lost"
                   for r in led.records("membership"))
    finally:
        mgr.close()
        fleet.close()
        for s in servers:
            s.stop()
        standby.stop()


def test_answered_probe_rejoins_instead_of_replacing():
    servers, fleet = _serve()
    clock = [0.0]
    mgr = ReplicaManager(fleet, lease_ms=1_000.0, probe_timeout_ms=150.0,
                         clock=lambda: clock[0])
    try:
        clock[0] = 5.0  # the liveness loop paused, not the replicas
        assert mgr.tick() == []  # answered probes re-register, no drain
        assert len(fleet.replicas()) == 2 and mgr.respawns == 0
    finally:
        mgr.close()
        fleet.close()
        for s in servers:
            s.stop()


# -- chaos plan: the transport fault kinds -----------------------------------


def test_chaos_spec_parses_and_fires_the_net_kinds():
    plan = ChaosPlan(parse_chaos_spec(
        "proc_kill@1,net_partition@2,net_slow@3"))
    assert plan.net_fault(0) == []
    assert plan.net_fault(1) == ["proc_kill"]
    assert plan.net_fault(1) == []  # one-shot
    assert plan.net_fault(2) == ["net_partition"]
    assert plan.net_fault(3) == ["net_slow"]
    with pytest.raises(ChaosSpecError):
        parse_chaos_spec("net_meteor@1")


# -- ledger / ops / CI surfaces ----------------------------------------------


def test_failures_report_renders_the_transport_timeline(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("transport", {"event": "proc_kill", "replica": "r1",
                             "pid": 999})
    led.append("transport", {"event": "conn_lost", "peer": "127.0.0.1:9",
                             "replica": "r1", "error": "OSError: gone"})
    led.append("transport", {"event": "drained", "replica": "r1",
                             "pid": 999})
    led.append("transport", {"event": "respawn", "replica": "r1",
                             "replacement": "r2", "incarnation": "abc123",
                             "pid": 1000})
    led.append("transport", {"event": "partition", "replica": "r2",
                             "duration_ms": 30000.0})
    led.append("transport", {"event": "reconnect", "peer": "127.0.0.1:9",
                             "reconnects": 3})
    out = render_failures(led)
    for line in ("PROC-KILL", "CONN-LOST", "DRAINED", "RESPAWN",
                 "PARTITION", "RECONNECT"):
        assert line in out
    assert "abc123" in out and "127.0.0.1:9" in out


def test_ops_dashboard_shows_per_replica_transport_state():
    servers, fleet = _serve()
    try:
        out = render_ops(fleet.stats(), health=fleet.health())
        assert "transport" in out and "connected" in out
    finally:
        fleet.close()
        for s in servers:
            s.stop()
