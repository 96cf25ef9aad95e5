"""Cluster supervisor: exactly-once batch accounting, lease-based
membership under a fake clock, EWMA straggler policy with backup substeps,
elastic reassignment, the simulated fleet, and the ledger / CLI surfaces
(``--failures`` membership timeline, ``supervisor-status``); the membership
drill matrix itself is in ``test_drills.py``."""

import os

import numpy as np
import pytest

from swiftsnails_tpu.cluster import (
    BatchAccountant,
    Supervisor,
    WorkerClient,
    WorkerLost,
)
from swiftsnails_tpu.cluster.accounting import compress_ranges, expand_ranges
from swiftsnails_tpu.cluster.worker import IndexedBatchSource
from swiftsnails_tpu.resilience import parse_chaos_spec
from swiftsnails_tpu.resilience.chaos import ChaosPlan
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    render_failures,
)


class FakeClock:
    """Injectable monotonic clock — the same idiom the retry tests use."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


# ---------------------------------------------------------- range algebra ---


def test_compress_and_expand_ranges_roundtrip():
    idx = [0, 1, 2, 5, 7, 8, 9]
    spans = compress_ranges(idx)
    assert spans == [[0, 3], [5, 6], [7, 10]]
    assert expand_ranges(spans) == idx
    assert compress_ranges([]) == []


# ------------------------------------------------------------- accountant ---


def test_accountant_exactly_once_proof():
    acct = BatchAccountant()
    lease = acct.grant("w0", 0, 8)
    for i in range(8):
        assert acct.try_claim(lease.lease_id, i)
        assert acct.commit(lease.lease_id, i)
    proof = acct.verify(8)
    assert proof["exact"] and proof["lost_count"] == 0
    assert proof["duplicated_count"] == 0
    assert lease.watermark == 8


def test_accountant_first_writer_wins_discards_duplicate():
    acct = BatchAccountant()
    a = acct.grant("w0", 0, 4)
    b = acct.grant("w1", 0, 4, backup=True)  # duplicated span
    assert acct.try_claim(a.lease_id, 2)
    acct.commit(a.lease_id, 2)
    # the backup replica's claim on the committed index is refused
    assert not acct.try_claim(b.lease_id, 2)
    assert acct.dup_discarded == 1
    assert acct.verify(4)["duplicated_count"] == 0  # refused != applied


def test_accountant_commit_after_commit_is_the_broken_invariant():
    acct = BatchAccountant()
    a = acct.grant("w0", 0, 2)
    acct.commit(a.lease_id, 0)
    assert not acct.commit(a.lease_id, 0)  # second application reached commit
    proof = acct.verify(2)
    assert not proof["exact"] and proof["duplicated"] == [0]


def test_accountant_claims_respect_lease_bounds_and_revocation():
    acct = BatchAccountant()
    a = acct.grant("w0", 4, 8)
    assert not acct.try_claim(a.lease_id, 3)   # outside the span
    assert not acct.try_claim(999, 5)          # unknown lease
    acct.commit(a.lease_id, 5)
    rest = acct.revoke(a.lease_id)
    assert rest == [[4, 5], [6, 8]]            # committed 5 punched out
    assert not acct.try_claim(a.lease_id, 6)   # revoked lease refuses


def test_accountant_snapshot_restore_drops_live_leases():
    acct = BatchAccountant()
    a = acct.grant("w0", 0, 6)
    for i in (0, 1, 3):
        acct.commit(a.lease_id, i)
    snap = acct.snapshot()
    fresh = BatchAccountant()
    fresh.restore(snap)
    assert fresh.is_committed(1) and not fresh.is_committed(2)
    # leases are NOT resurrected: the supervisor re-leases elastically
    assert fresh.leases_of("w0") == []


# ------------------------------------------------- membership + fake clock ---


def test_lease_expiry_declares_worker_lost_and_reassigns(tmp_path):
    clock = FakeClock()
    led = Ledger(str(tmp_path / "led.jsonl"))
    sup = Supervisor(total_batches=32, lease_ms=1000.0, ledger=led,
                     clock=clock)
    sup.register("w0")
    sup.register("w1")
    dead = sup.next_range("w0")
    sup.accountant.commit(dead.lease_id, dead.lo)  # one committed batch
    clock.advance(0.5)
    sup.heartbeat("w1")
    clock.advance(0.8)  # w0's lease (renewed never) is now past deadline
    assert sup.poll() == ["w0"]
    # the stale worker heartbeating after the verdict gets the typed error
    with pytest.raises(WorkerLost):
        sup.heartbeat("w0")
    # w0's uncommitted remainder went to the survivor, committed batch not
    d = sup.heartbeat("w1")
    adopted = d["adopted"]
    assert [(l.lo, l.hi) for l in adopted] == [(dead.lo + 1, dead.hi)]
    events = [r["action"] for r in led.records("membership")]
    assert "worker-lost" in events and "reassigned" in events


def test_rejoin_after_loss_is_a_fresh_member(tmp_path):
    clock = FakeClock()
    sup = Supervisor(total_batches=16, lease_ms=1000.0, clock=clock)
    client = WorkerClient(sup, "w0")
    sup.register("w1")
    clock.advance(2.0)
    sup.poll()
    assert "w0" not in sup.alive() or sup._members["w0"].lost
    client._rejoin()
    assert client.rejoins == 1
    assert "w0" in sup.alive()


def test_straggler_flagged_shrunk_and_cleared():
    clock = FakeClock()
    sup = Supervisor(total_batches=None, lease_ms=1e6, straggler_ewma=1.0,
                     clock=clock)
    for w in ("w0", "w1", "w2"):
        sup.register(w)
    for _ in range(3):
        sup.heartbeat("w0", step_ms=100.0)
        sup.heartbeat("w1", step_ms=100.0)
    sup.heartbeat("w2", step_ms=500.0)  # > 2x the fleet median of 100
    m = sup._members["w2"]
    assert m.straggler and m.share < 1.0
    assert sup.stragglers_flagged == 1
    # a recovered worker gets its full share back
    sup.heartbeat("w2", step_ms=90.0)
    assert not m.straggler and m.share == 1.0


def test_straggler_grants_shrink_with_share():
    clock = FakeClock()
    sup = Supervisor(total_batches=1000, lease_ms=1e6, grant_batches=8,
                     straggler_ewma=1.0, clock=clock)
    for w in ("w0", "w1", "w2"):
        sup.register(w)
    full = sup.next_range("w0")
    assert full.hi - full.lo == 8
    for _ in range(2):
        sup.heartbeat("w0", step_ms=100.0)
        sup.heartbeat("w1", step_ms=100.0)
    sup.heartbeat("w2", step_ms=1000.0)
    shrunk = sup.next_range("w2")
    assert shrunk.hi - shrunk.lo == 4  # 8 * STRAGGLER_SHARE


def test_backup_substeps_duplicate_to_fastest_with_dedup(tmp_path):
    clock = FakeClock()
    led = Ledger(str(tmp_path / "led.jsonl"))
    sup = Supervisor(total_batches=64, lease_ms=1e6, straggler_ewma=1.0,
                     backup_substeps=2, ledger=led, clock=clock)
    for w in ("w0", "w1", "w2"):
        sup.register(w)
    slow = sup.next_range("w2")
    for _ in range(2):
        sup.heartbeat("w0", step_ms=100.0)
        sup.heartbeat("w1", step_ms=100.0)
    sup.heartbeat("w2", step_ms=1000.0)  # flags w2; duplicates its pending
    backups = [l for w in ("w0", "w1")
               for l in sup.accountant.leases_of(w) if l.backup]
    assert len(backups) == 1
    bk = backups[0]
    assert (bk.lo, bk.hi) == (slow.watermark, slow.watermark + 2)
    # whichever replica commits first wins; the loser's claim is refused
    assert sup.accountant.try_claim(bk.lease_id, bk.lo)
    sup.accountant.commit(bk.lease_id, bk.lo)
    assert not sup.accountant.try_claim(slow.lease_id, bk.lo)
    assert sup.accountant.dup_discarded == 1
    assert any(r["action"] == "backup" for r in led.records("membership"))


def test_elastic_restore_returns_uncommitted_spans_to_pool():
    clock = FakeClock()
    sup = Supervisor(total_batches=32, lease_ms=1e6, grant_batches=8,
                     clock=clock)
    sup.register("w0")
    lease = sup.next_range("w0")
    for i in range(lease.lo, lease.lo + 3):
        sup.accountant.commit(lease.lease_id, i)
    snap = sup.cursor()
    fresh = Supervisor(total_batches=32, lease_ms=1e6, clock=clock)
    fresh.register("wX")  # different membership entirely
    fresh.restore(snap)
    assert fresh._frontier == lease.hi
    assert fresh._free == [[lease.lo + 3, lease.hi]]
    regrant = fresh.next_range("wX")  # pool drains before the frontier
    assert (regrant.lo, regrant.hi) == (lease.lo + 3, lease.hi)


# ------------------------------------------------------------ worker client ---


def test_indexed_batch_source_random_access_and_backward_seek():
    src = IndexedBatchSource(lambda: iter([10, 11, 12, 13]))
    assert src.get(2) == 12
    assert src.get(0) == 10  # backward seek replays the generator
    assert src.restarts == 1
    with pytest.raises(StopIteration):
        src.get(9)


def test_leased_stream_serves_smallest_first_and_claims():
    clock = FakeClock()
    sup = Supervisor(total_batches=6, lease_ms=1e6, grant_batches=3,
                     clock=clock)
    client = WorkerClient(sup, "w0")
    stream = client.leased_stream(lambda: iter(range(100)))
    seen = []
    for batch in stream:
        seen.append(batch)
        client.on_step(len(seen))
    assert seen == [0, 1, 2, 3, 4, 5]  # index == batch for range source
    assert sup.accountant.verify(6)["exact"]


# ------------------------------------------------------------ chaos grammar ---


def test_cluster_chaos_kinds_parse_and_fire_once():
    faults = parse_chaos_spec("worker_dead@10,worker_slow@16-18,partition@30")
    assert ("worker_dead", 10) in faults
    assert ("worker_slow", 17) in faults and ("partition", 30) in faults
    plan = ChaosPlan(faults, seed=7)
    assert plan.cluster_fault(10) == ["worker_dead"]
    assert plan.cluster_fault(10) == []  # consumed
    assert plan.cluster_fault(30) == ["partition"]


# ------------------------------------------------------- simulated drills ---


@pytest.fixture(scope="module")
def drill_trainer(tmp_path_factory):
    from swiftsnails_tpu.resilience.drill import make_trainer

    wd = tmp_path_factory.mktemp("cluster-sim")
    return make_trainer(str(wd))


def test_sim_worker_kill_reassigns_and_stays_exact(drill_trainer, tmp_path):
    from swiftsnails_tpu.cluster.sim import simulate_cluster

    led = Ledger(str(tmp_path / "led.jsonl"))
    chaos = ChaosPlan(parse_chaos_spec("worker_dead@10"), seed=7, ledger=led)
    res = simulate_cluster(drill_trainer, 24, workers=3, chaos=chaos,
                           supervised=True, ledger=led)
    acct = res["accounting"]
    assert acct["exact"], acct
    assert res["status"]["workers_lost"] == 1
    assert res["status"]["reassignments"] >= 1
    dead = [w for w, st in res["workers"].items() if not st["alive"]]
    assert len(dead) == 1


def test_sim_unsupervised_control_loses_the_dead_workers_range(drill_trainer):
    from swiftsnails_tpu.cluster.sim import simulate_cluster

    chaos = ChaosPlan(parse_chaos_spec("worker_dead@10"), seed=7)
    res = simulate_cluster(drill_trainer, 24, workers=3, chaos=chaos,
                           supervised=False)
    assert res["accounting"]["lost_count"] > 0  # static shards: range gone


def test_sim_partition_refuses_stale_commits(drill_trainer):
    from swiftsnails_tpu.cluster.sim import simulate_cluster

    chaos = ChaosPlan(parse_chaos_spec("partition@10"), seed=7)
    res = simulate_cluster(drill_trainer, 24, workers=3, chaos=chaos,
                           supervised=True)
    acct = res["accounting"]
    assert acct["exact"]
    # the healed worker's buffered duplicates were refused, not re-applied
    assert acct["duplicated_count"] == 0
    assert res["stale_rejected"] + acct["dup_discarded"] >= 0


# ----------------------------------------------- ledger + CLI + regression ---


def test_render_failures_shows_membership_timeline(tmp_path):
    led = Ledger(str(tmp_path / "led.jsonl"))
    sup = Supervisor(total_batches=8, lease_ms=1000.0, ledger=led,
                     clock=FakeClock())
    sup.register("w0")
    sup.register("w1")
    sup.next_range("w0")
    sup.mark_dead("w0", reason="drill kill")
    out = render_failures(led)
    assert "WORKER-LOST" in out and "REASSIGNED" in out
    assert "w0" in out and "drill kill" in out


def test_supervisor_status_cli(tmp_path, capsys):
    from swiftsnails_tpu.cli import main

    path = str(tmp_path / "led.jsonl")
    led = Ledger(path)
    sup = Supervisor(total_batches=8, lease_ms=1000.0, ledger=led,
                     clock=FakeClock())
    sup.register("w0")
    sup.register("w1")
    sup.next_range("w0")
    sup.mark_dead("w0", reason="killed")
    assert main(["supervisor-status", path]) == 0
    out = capsys.readouterr().out
    assert "w0" in out and "lost" in out
    assert "w1" in out and "alive" in out
    assert "lifecycle: 1 lost, 1 reassigned" in out
    # missing ledger is a clean nonzero exit, not a traceback
    assert main(["supervisor-status", str(tmp_path / "nope.jsonl")]) == 1
