"""Transport drills: real replica processes behind a :class:`NetFleet`.

Used by ``tools/chaos_drill.py --net`` and the tier-1 tests. Two spawned
``replica_server`` processes serve one checkpoint over TCP; the three
transport chaos kinds are scheduled through the chaos-spec syntax and a
fourth drill kills the delta stream's publisher:

- ``proc_kill`` — a replica is SIGKILL'd and must be declared lost by lease
  expiry, drained from the ring, respawned, and serving again with a fresh
  incarnation, every row it serves bit-identical to the checkpoint;
- ``net_partition`` — a black-holed replica misses an epoch, and on heal a
  stale write (epoch at/below its own) must be REFUSED typed
  (:class:`StaleEpoch`) before the replica resyncs to the shared epoch;
- ``net_slow`` — server-side delay above the read timeout must surface as
  a typed client deadline, never a hang, and clear on heal;
- ``publisher_kill`` — the delta stream's publisher dies mid-stream and a
  new incarnation takes over; the TCP-fed subscriber must fall back and
  reconverge to whole-plane bit parity 0.0.

:func:`net_drill_checks` is the verdict on the result.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

NET_SEED = 23
# fast lease for drills: a SIGKILL'd replica must be declared lost, drained
# and respawned within a couple of liveness rounds, not 15s of wall clock
DRILL_LEASE_MS = 600.0
DRILL_PROBE_TIMEOUT_MS = 250.0


def _serve_cfg(extra: Optional[Dict] = None):
    from swiftsnails_tpu.utils.config import Config

    base = {
        "dim": "16", "capacity": str(1 << 9), "packed": "0",
        "seed": str(NET_SEED), "subsample": "0",
        # snappy transport for drills: a dead peer costs ~0.5s, not 3s
        "net_connect_timeout_ms": "500", "net_read_timeout_ms": "1000",
        "net_lease_ms": str(DRILL_LEASE_MS),
    }
    base.update({k: str(v) for k, v in (extra or {}).items()})
    return Config(base)


def _build_checkpoint(workdir: str):
    """Train-free checkpoint build (the freshness drill idiom): init a
    small word2vec state and save it — the drills exercise serving and
    transport, not training."""
    from swiftsnails_tpu.framework.checkpoint import save_checkpoint
    from swiftsnails_tpu.framework.quality import paired_corpus
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.serving.engine import Servant

    cfg = _serve_cfg()
    ids, vocab = paired_corpus(n_pairs=32, reps=4, seed=NET_SEED)
    trainer = Word2VecTrainer(cfg, mesh=None, corpus_ids=ids, vocab=vocab)
    state = trainer.init_state()
    ck_root = os.path.join(workdir, "ckpt")
    save_checkpoint(ck_root, state, step=1, wait=True)
    reference = Servant.from_checkpoint(ck_root, cfg)
    return ck_root, cfg, reference


def _spawn_n(spawner, n: int) -> List:
    """Spawn ``n`` replica processes concurrently (each pays a Python +
    jax import on startup; serialized spawns would double the drill)."""
    procs: List = [None] * n
    errs: List[BaseException] = []

    def _one(i: int) -> None:
        try:
            procs[i] = spawner.spawn()
        except BaseException as e:  # surfaced after join
            errs.append(e)

    threads = [threading.Thread(target=_one, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        for p in procs:
            if p is not None:
                p.close()
        raise errs[0]
    return procs


def _tcp_parity(reference, fleet) -> float:
    """Whole-plane mismatch fraction, pulled over the wire: every row of
    every table, from every replica, must be bit-identical to the
    reference checkpoint's planes."""
    bad = total = 0
    for rep in fleet.replicas():
        for name, want in reference._tables.items():
            want = np.asarray(want)
            got = np.asarray(rep.servant.pull(
                np.arange(want.shape[0], dtype=np.int64), table=name))
            bad += int(np.sum(want.astype(got.dtype, copy=False) != got))
            total += int(want.size)
    return float(bad) / float(total) if total else 1.0


def net_chaos_drill(workdir: Optional[str] = None) -> Dict:
    """The ``tools/chaos_drill.py --net`` matrix: the three transport
    chaos kinds fired from a :class:`ChaosPlan` spec against REAL spawned
    replica processes, then the delta publisher's loss, each required to
    recover (:func:`net_drill_checks` is the verdict on what it returns):

    - ``proc_kill``: SIGKILL -> lease expiry -> drain -> respawn ->
      rejoin with a fresh incarnation -> serves;
    - ``net_partition``: black-hole -> missed epoch -> heal -> stale
      write refused typed -> resync;
    - ``net_slow``: injected server-side delay above the read timeout ->
      client deadlines fire (never a hang) -> recovers to fast serving
      when the slowness clears;
    - ``publisher_kill``: a new publisher incarnation mid-stream -> the
      TCP-fed subscriber falls back -> whole-plane parity 0.0.
    """
    from swiftsnails_tpu.net.fleet import (
        NetFleet,
        ReplicaManager,
        ReplicaSpawner,
    )
    from swiftsnails_tpu.net.remote import StaleEpoch
    from swiftsnails_tpu.resilience.chaos import ChaosPlan, parse_chaos_spec
    from swiftsnails_tpu.serving.breaker import Unavailable
    from swiftsnails_tpu.serving.engine import Overloaded

    own_tmp = None
    if workdir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="ssn-net-drill-")
        workdir = own_tmp.name
    try:
        ck_root, cfg, reference = _build_checkpoint(workdir)
        plane = np.asarray(reference._tables["in_table"])
        rng = np.random.default_rng(NET_SEED)
        spawner = ReplicaSpawner(ck_root, cfg)
        procs = _spawn_n(spawner, 2)
        fleet = NetFleet.connect([(p.host, p.port) for p in procs], cfg,
                                 checkpoint_root=ck_root)
        manager = ReplicaManager(
            fleet, spawner=spawner, config=cfg,
            probe_timeout_ms=DRILL_PROBE_TIMEOUT_MS)
        for rep, proc in zip(fleet.replicas(), procs):
            manager.attach_process(rep.id, proc)

        # the storm schedule comes from the chaos-spec syntax — the same
        # plan ticks the train storms use, now with transport kinds
        plan = ChaosPlan(parse_chaos_spec(
            "proc_kill@1,net_partition@2,net_slow@3"), seed=NET_SEED)
        drills: Dict[str, Dict] = {}
        try:
            for tick in (1, 2, 3):
                for kind in plan.net_fault(tick):
                    if kind == "proc_kill":
                        drills[kind] = _drill_kill(fleet, manager, reference)
                    elif kind == "net_partition":
                        drills[kind] = _drill_partition(
                            fleet, plane, rng, StaleEpoch,
                            (Unavailable, Overloaded))
                    else:
                        drills[kind] = _drill_slow(fleet)
            drills["publisher_kill"] = _publisher_kill_drill(
                fleet, reference, cfg, ck_root,
                os.path.join(workdir, "deltas"))
        finally:
            manager.close()
            fleet.close()
        return drills
    finally:
        if own_tmp is not None:
            own_tmp.cleanup()


def _drill_kill(fleet, manager, reference) -> Dict:
    """SIGKILL one replica, then tick the liveness loop until the lease
    expires and the replacement rejoins."""
    victim = fleet.replicas()[0]
    old_incarnation = victim.servant.incarnation
    proc = manager.process_of(victim.id)
    proc.kill()
    proc.wait(timeout=5.0)
    rejoined = False
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        manager.tick()
        reps = fleet.replicas()
        if (manager.respawns >= 1 and len(reps) >= 2
                and victim.id not in [r.id for r in reps]):
            rejoined = True
            break
        time.sleep(0.1)
    try:
        parity = _tcp_parity(reference, fleet)
    except Exception:
        parity = 1.0
    return {
        "killed": victim.id,
        "respawns": manager.respawns,
        "replicas": [r.id for r in fleet.replicas()],
        "rejoined": bool(rejoined),
        "fresh_incarnation": old_incarnation not in [
            r.servant.incarnation for r in fleet.replicas()],
        "parity": parity,
    }


def _drill_partition(fleet, plane, rng, stale_exc, transport_excs) -> Dict:
    reps = fleet.replicas()
    healthy, cut = reps[0].servant, reps[1].servant
    rows = np.sort(rng.choice(plane.shape[0], size=8, replace=False))
    batch = {"in_table": (rows.astype(np.int64), plane[rows])}
    pre = int(cut.version)
    cut.chaos(partition_ms=30_000.0)
    epoch = fleet._next_epoch()
    healthy.apply_rows(batch, version=epoch)
    missed = False
    try:
        cut.apply_rows(batch, version=epoch)
    except transport_excs:
        missed = True
    cut.chaos(partition_ms=0.0)
    cut.health()
    refused = False
    try:
        cut.apply_rows(batch, version=pre)
    except stale_exc:
        refused = True
    cut.apply_rows(batch, version=epoch)
    return {
        "missed_write_during_partition": bool(missed),
        "stale_write_refused": bool(refused),
        "epoch": epoch,
        "versions": {r.id: int(r.servant.version)
                     for r in fleet.replicas()},
    }


def _drill_slow(fleet) -> Dict:
    """Inject server-side delay above the read timeout: the client's
    deadline must fire (typed, never a hang) and serving must recover
    once the slowness clears."""
    from swiftsnails_tpu.serving.breaker import Unavailable
    from swiftsnails_tpu.serving.engine import Overloaded

    victim = fleet.replicas()[0].servant
    read_timeout_ms = victim.client.read_timeout_ms
    victim.chaos(slow_ms=read_timeout_ms * 3.0)
    t0 = time.monotonic()
    timed_out = False
    try:
        victim.pull(np.arange(4, dtype=np.int64))
    except (Unavailable, Overloaded):
        timed_out = True
    stall_ms = (time.monotonic() - t0) * 1e3
    # the deadline must bound the stall: attempts x read timeout plus the
    # policy's backoff budget, nowhere near the injected 3x delay x tries
    bounded = stall_ms < read_timeout_ms * 6.0
    victim.chaos(slow_ms=0.0)
    victim.health()
    try:
        ok = np.asarray(victim.pull(
            np.arange(4, dtype=np.int64))).shape[0] == 4
    except Exception:
        ok = False
    return {
        "timed_out_typed": bool(timed_out),
        "stall_bounded": bool(bounded),
        "serves_after_heal": bool(ok),
    }


def net_drill_checks(drills: Dict[str, Dict]) -> Dict[str, bool]:
    """The transport drills' verdict, ``<drill>.<check>`` by name."""
    checks: Dict[str, bool] = {}
    kill = drills.get("proc_kill")
    if kill is not None:
        checks["proc_kill.respawned"] = kill["respawns"] >= 1
        checks["proc_kill.rejoined"] = bool(kill["rejoined"])
        checks["proc_kill.fresh_incarnation"] = bool(
            kill["fresh_incarnation"])
        checks["proc_kill.parity_zero"] = kill["parity"] == 0.0
    part = drills.get("net_partition")
    if part is not None:
        checks["net_partition.missed_write"] = bool(
            part["missed_write_during_partition"])
        checks["net_partition.stale_write_refused"] = bool(
            part["stale_write_refused"])
        checks["net_partition.shared_version"] = (
            set(part["versions"].values()) == {part["epoch"]})
    slow = drills.get("net_slow")
    if slow is not None:
        checks["net_slow.timed_out_typed"] = bool(slow["timed_out_typed"])
        checks["net_slow.stall_bounded"] = bool(slow["stall_bounded"])
        checks["net_slow.serves_after_heal"] = bool(
            slow["serves_after_heal"])
    pub = drills.get("publisher_kill")
    if pub is not None:
        checks["publisher_kill.fell_back"] = pub["fallbacks"] >= 1
        checks["publisher_kill.converged"] = bool(pub["converged"])
        checks["publisher_kill.parity_zero"] = pub["parity"] == 0.0
    return checks


def _publisher_kill_drill(fleet, reference, cfg, ck_root: str,
                          delta_dir: str) -> Dict:
    """Stream deltas to the fleet over TCP, kill the publisher mid-stream
    (a NEW incarnation takes over the directory), and require the
    subscriber to fall back, resubscribe, and reconverge to whole-plane
    bit parity 0.0 — the file poll's recovery ladder, over a socket."""
    from swiftsnails_tpu.freshness.publisher import DeltaPublisher
    from swiftsnails_tpu.freshness.subscriber import DeltaSubscriber
    from swiftsnails_tpu.net.delta_stream import (
        DeltaStreamServer,
        TcpDeltaSource,
    )

    plane = np.asarray(reference._tables["in_table"])
    rng = np.random.default_rng(NET_SEED + 3)

    def _batch():
        rows = np.sort(rng.choice(plane.shape[0], size=8, replace=False))
        return {"in_table": (rows.astype(np.int64), plane[rows])}

    pub = DeltaPublisher(delta_dir, base_step=1)
    pub.publish(_batch(), step=2)
    pub.publish(_batch(), step=3)

    sub = DeltaSubscriber(fleet, delta_dir, config=cfg,
                          checkpoint_root=ck_root)
    with DeltaStreamServer(delta_dir).start() as server:
        src = TcpDeltaSource(sub, *server.address, config=cfg).start()
        try:
            _wait(lambda: sub.status()["applied_seq"] >= 2, 20.0)
            # mid-stream publisher kill: a fresh incarnation reopens the
            # directory — the stream re-sends its base, the subscriber
            # must detect the restart and fall back
            pub2 = DeltaPublisher(delta_dir, base_step=3)
            pub2.publish(_batch(), step=4)
            converged = _wait(
                lambda: (sub.status()["fallbacks"] >= 1
                         and sub.status()["applied_step"] >= 4), 30.0)
        finally:
            src.stop()
    st = sub.status()
    parity = _tcp_parity(reference, fleet)
    return {
        "parity": parity,
        "fallbacks": st["fallbacks"],
        "applied_seq": st["applied_seq"],
        "applied_step": st["applied_step"],
        "frames": src.frames,
        "reconnects": src.reconnects,
        "converged": bool(converged),
    }


def _wait(cond, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return bool(cond())
