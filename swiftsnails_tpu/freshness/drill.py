"""Delta-pipeline drills: induced freshness failures against a live fleet.

Used by ``tools/chaos_drill.py --freshness`` and the tier-1 tests. A
2-replica :class:`~swiftsnails_tpu.serving.fleet.Fleet` subscribed to a
hot-row delta log loses its publisher mid-stream (a new incarnation takes
over), reads a bit-flipped delta batch (CRC), and hits a deleted segment
(sequence gap). Each drill must fall back to a full checkpoint reload,
resubscribe past the fault, and end with every replica on one shared
version, whole-plane parity 0.0 against the reference planes, and a
complete ``fallback`` anomaly trace (detect -> reload -> resubscribe).
:func:`freshness_drill_checks` is the verdict on the result.
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, Optional

import numpy as np

FRESHNESS_SEED = 17


def _full_parity(reference, served) -> float:
    """Whole-plane mismatch fraction (post-fallback: a full reload must
    leave every row equal to the reference checkpoint)."""
    bad = total = 0
    for name, want in reference._tables.items():
        got = np.asarray(served._tables[name])
        want = np.asarray(want)
        bad += int(np.sum(want != got))
        total += int(want.size)
    return float(bad) / float(total) if total else 1.0


def freshness_chaos_drill(workdir: Optional[str] = None) -> Dict:
    """The ``tools/chaos_drill.py --freshness`` matrix: three induced
    freshness failures against a live fleet, each required to fall back to
    a full checkpoint reload and converge to parity 0.0
    (:func:`freshness_drill_checks` is the verdict on what it returns).

    - ``publisher_kill``: the publisher dies mid-stream and a NEW
      incarnation takes over the same directory (restart detection);
    - ``corrupt_delta``: one delta batch is bit-flipped on disk (CRC);
    - ``forced_gap``: a published segment is deleted before the subscriber
      reads it (sequence gap).
    """
    from swiftsnails_tpu.freshness.log import seg_path
    from swiftsnails_tpu.freshness.publisher import DeltaPublisher
    from swiftsnails_tpu.freshness.subscriber import DeltaSubscriber
    from swiftsnails_tpu.serving.engine import Servant
    from swiftsnails_tpu.serving.fleet import Fleet
    from swiftsnails_tpu.utils.config import Config

    own_tmp = None
    if workdir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="ssn-freshness-drill-")
        workdir = own_tmp.name
    try:
        from swiftsnails_tpu.framework.checkpoint import save_checkpoint
        from swiftsnails_tpu.models.word2vec import Word2VecTrainer
        from swiftsnails_tpu.framework.quality import paired_corpus

        dim, capacity = 16, 1 << 9
        ids, vocab = paired_corpus(n_pairs=32, reps=4, seed=FRESHNESS_SEED)
        cfg = Config({
            "dim": str(dim), "capacity": str(capacity), "packed": "0",
            "seed": str(FRESHNESS_SEED), "subsample": "0",
        })
        trainer = Word2VecTrainer(cfg, mesh=None, corpus_ids=ids, vocab=vocab)
        state = trainer.init_state()
        ck_root = os.path.join(workdir, "ckpt")
        save_checkpoint(ck_root, state, step=1, wait=True)
        reference = Servant.from_checkpoint(ck_root, cfg)
        rng = np.random.default_rng(FRESHNESS_SEED)
        plane = np.asarray(reference._tables["in_table"])

        def _batch():
            rows = np.sort(
                rng.choice(plane.shape[0], size=8, replace=False))
            return {"in_table": (rows.astype(np.int64), plane[rows])}

        from swiftsnails_tpu.telemetry.request_trace import (
            RequestTracer,
            tree_complete,
        )

        drills: Dict[str, Dict] = {}
        for drill in ("publisher_kill", "corrupt_delta", "forced_gap"):
            fleet = Fleet.from_checkpoint(ck_root, cfg, replicas=2)
            # tail-keep only: the gap->fallback must land as a complete,
            # drillable span tree even at sample rate 0
            tracer = RequestTracer(
                0.0, anomaly_keep=True, seed=FRESHNESS_SEED)
            try:
                d = os.path.join(workdir, drill)
                pub = DeltaPublisher(d, base_step=1, request_tracer=tracer)
                sub = DeltaSubscriber(
                    fleet, d, config=cfg, checkpoint_root=ck_root,
                    request_tracer=tracer)
                pub.publish(_batch(), step=2)
                pub.publish(_batch(), step=3)
                sub.subscribe()
                sub.poll()
                if drill == "publisher_kill":
                    # the old incarnation dies; a new one reopens the dir
                    pub2 = DeltaPublisher(d, base_step=3)
                    pub2.publish(_batch(), step=4)
                    sub.poll()  # detects the restart -> fallback
                    sub.poll()  # applies the new incarnation's stream
                elif drill == "corrupt_delta":
                    p = pub.publish(_batch(), step=4)
                    path = seg_path(d, p)
                    blob = bytearray(open(path, "rb").read())
                    blob[len(blob) // 2] ^= 0xFF
                    open(path, "wb").write(bytes(blob))
                    sub.poll()
                else:  # forced_gap
                    gone = pub.publish(_batch(), step=4)
                    pub.publish(_batch(), step=5)
                    os.remove(seg_path(d, gone))
                    sub.poll()
                    sub.poll()  # re-apply past the gap after the reload
                st = sub.status()
                first = next(iter(fleet._replicas.values())).servant
                parity = _full_parity(reference, first)
                versions = {rid: rep.servant.version
                            for rid, rep in fleet._replicas.items()}
                # the fallback must be drillable: a kept anomaly trace with
                # the full detect -> reload -> resubscribe timeline
                fb_traces = [
                    t for t in (c.to_dict()
                                for c in tracer.anomaly_traces())
                    if "fallback" in t["anomalies"] and tree_complete(
                        t, require=("detect", "reload", "resubscribe",
                                    "request"))]
                drills[drill] = {
                    "fallbacks": st["fallbacks"],
                    "parity": parity,
                    "replica_versions": versions,
                    "applied_seq": st["applied_seq"],
                    "fallback_traces": len(fb_traces),
                    "trace_id": (fb_traces[-1]["trace_id"]
                                 if fb_traces else None),
                }
            finally:
                fleet.close()
        return drills
    finally:
        if own_tmp is not None:
            own_tmp.cleanup()


def freshness_drill_checks(drills: Dict[str, Dict]) -> Dict[str, bool]:
    """The freshness drills' verdict, ``<drill>.<check>`` by name."""
    checks: Dict[str, bool] = {}
    for drill, res in drills.items():
        checks[f"{drill}.fell_back"] = res["fallbacks"] >= 1
        checks[f"{drill}.shared_version"] = (
            len(set(res["replica_versions"].values())) == 1)
        checks[f"{drill}.parity_zero"] = res["parity"] == 0.0
        checks[f"{drill}.fallback_trace_complete"] = (
            res["fallback_traces"] >= 1)
    return checks
