"""Freshness pipeline: delta-log wire format, publisher incarnations,
idempotent/out-of-order-safe subscription, gap->fallback recovery,
quantized delta parity, fleet-wide cutover atomicity, a trainer's published
deltas against its own checkpoint, and the freshness ledger surfaces (the
delta-pipeline drill matrix itself is in ``test_drills.py``).

The delta pipeline's correctness bars (ISSUE 14): a batch must round-trip
bit-identically (f32 wire) and any bit flip must be rejected by the CRC;
re-delivering an applied batch must be a counted no-op (absolute values +
``(table, row, seq)`` keying); out-of-order delivery within the reorder
window must buffer and drain in sequence order; a sequence gap must fall
back to a full checkpoint reload and resume PAST the dead batch (never
loop on it); int8 deltas must dequantize to exactly what a flush +
requantized host master serves; a fleet-wide apply must land every
replica on one shared version; rows a training run published as deltas
must serve bit-identically to the checkpoint the same run wrote; and the
DELTA-GAP / FRESHNESS-FALLBACK failure lines must render.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.freshness.log import (
    DeltaCorrupt,
    list_seqs,
    prune,
    read_base,
    read_batch,
    seg_path,
    write_batch,
)
from swiftsnails_tpu.freshness.publisher import DeltaPublisher
from swiftsnails_tpu.freshness.subscriber import DeltaSubscriber
from swiftsnails_tpu.serving import Servant
from swiftsnails_tpu.serving.fleet import Fleet
from swiftsnails_tpu.telemetry.ledger import (
    Ledger,
    render_failures,
)
from swiftsnails_tpu.tiered.store import (
    _np_dequant_unit_rows,
    _np_quant_unit_rows,
)

DIM = 8
CAP = 64


def _vals(rows, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((len(rows), DIM)).astype(np.float32)


class FakeTarget:
    """Minimal serving target: the apply_rows / reload_from_checkpoint /
    step / version surface the subscriber drives."""

    def __init__(self, cap=CAP, dim=DIM):
        self.tables = {"t": np.zeros((cap, dim), np.float32)}
        self.step = 0
        self.version = 0
        self.applies = 0
        self.reloads = 0

    def apply_rows(self, updates, *, version=None, step=None):
        for name, (rows, vals) in updates.items():
            self.tables[name][np.asarray(rows, np.int64)] = np.asarray(
                vals, np.float32)
        if step is not None:
            self.step = max(self.step, int(step))
        self.version = int(version) if version is not None \
            else self.version + 1
        self.applies += 1
        return self.version

    def reload_from_checkpoint(self, root, config, **kw):
        self.reloads += 1
        self.version += 1
        return self.version


# --------------------------------------------------------- wire format ----


def test_batch_round_trip_bit_identical(tmp_path):
    d = str(tmp_path)
    rows = np.array([3, 0, 17, CAP - 1], np.int64)
    vals = _vals(rows, 1)
    header = {"seq": 1, "publisher": "p0", "base_step": 4, "step": 5,
              "ts_ns": 123, "dtype": "float32"}
    write_batch(d, header, {"t": {"rows": rows, "values": vals}})
    got_header, got_tables = read_batch(seg_path(d, 1))
    assert got_header["publisher"] == "p0"
    assert (got_header["seq"], got_header["step"]) == (1, 5)
    np.testing.assert_array_equal(got_tables["t"]["rows"], rows)
    # f32 wire: the served rows must be bit-identical to the published ones
    np.testing.assert_array_equal(got_tables["t"]["values"], vals)


def test_crc_rejects_bitflip_and_truncation(tmp_path):
    d = str(tmp_path)
    rows = np.arange(8, dtype=np.int64)
    write_batch(d, {"seq": 1, "publisher": "p0", "dtype": "float32"},
                {"t": {"rows": rows, "values": _vals(rows, 2)}})
    path = seg_path(d, 1)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0x40
    open(path, "wb").write(bytes(blob))
    with pytest.raises(DeltaCorrupt):
        read_batch(path)
    open(path, "wb").write(bytes(blob[:10]))
    with pytest.raises(DeltaCorrupt):
        read_batch(path)


def test_prune_deletes_oldest_first_and_keeps_newest(tmp_path):
    d = str(tmp_path)
    rows = np.arange(16, dtype=np.int64)
    for seq in range(1, 6):
        write_batch(d, {"seq": seq, "publisher": "p0", "dtype": "float32"},
                    {"t": {"rows": rows, "values": _vals(rows, seq)}})
    one = os.path.getsize(seg_path(d, 1))
    deleted = prune(d, max_bytes=2 * one + one // 2)
    assert deleted == 3
    assert list_seqs(d) == [4, 5]
    # even an impossible budget never deletes the newest batch
    prune(d, max_bytes=0)
    assert list_seqs(d) == [5]


# ---------------------------------------------------- publisher restart ----


def test_new_publisher_incarnation_owns_the_directory(tmp_path):
    d = str(tmp_path / "log")
    rows = np.arange(4, dtype=np.int64)
    a = DeltaPublisher(d, base_step=1)
    for step in (2, 3, 4):
        a.publish({"t": (rows, _vals(rows, step))}, step)
    assert list_seqs(d) == [1, 2, 3]
    # a restart renumbers from 1: the dead incarnation's segments must be
    # gone BEFORE the new base is visible, or a subscriber could read them
    b = DeltaPublisher(d, base_step=4)
    assert b.id != a.id
    assert list_seqs(d) == []
    assert read_base(d)["publisher"] == b.id
    b.publish({"t": (rows, _vals(rows, 9))}, 5)
    assert list_seqs(d) == [1]


# ----------------------------------------------------------- subscriber ----


def test_duplicate_redelivery_is_a_counted_noop(tmp_path):
    d = str(tmp_path / "log")
    pub = DeltaPublisher(d, base_step=0)
    rows = np.array([2, 7, 11], np.int64)
    vals = _vals(rows, 3)
    pub.publish({"t": (rows, vals)}, 1)
    tgt = FakeTarget()
    sub = DeltaSubscriber(tgt, d)
    assert sub.poll() == 1
    np.testing.assert_array_equal(tgt.tables["t"][rows], vals)
    snapshot = tgt.tables["t"].copy()
    # re-deliver the exact batch the stream already applied
    header, tables = read_batch(seg_path(d, 1))
    assert sub.apply_batch(header, tables) is False
    assert sub.duplicate_batches == 1
    assert sub.applied_batches == 1 and tgt.applies == 1
    np.testing.assert_array_equal(tgt.tables["t"], snapshot)


def test_out_of_order_within_window_buffers_then_drains_in_order(tmp_path):
    d = str(tmp_path / "log")
    pub = DeltaPublisher(d, base_step=0)
    rows = np.array([5, 9], np.int64)
    batches = {}
    for seq, step in ((1, 1), (2, 2), (3, 3)):
        pub.publish({"t": (rows, _vals(rows, 10 + seq))}, step)
        batches[seq] = read_batch(seg_path(d, seq))
    tgt = FakeTarget()
    sub = DeltaSubscriber(tgt, d, window=8)
    # deliver 3, 2, 1: the out-of-order pair buffers, seq 1 drains all
    assert sub.apply_batch(*batches[3]) is False
    assert sub.apply_batch(*batches[2]) is False
    assert sub.status()["pending"] == 2 and sub.applied_batches == 0
    assert sub.apply_batch(*batches[1]) is True
    assert sub.applied_seq == 3 and sub.applied_step == 3
    assert sub.status()["pending"] == 0 and sub.applied_batches == 3
    # the same rows were written by every batch: seq 3's values must win
    np.testing.assert_array_equal(
        tgt.tables["t"][rows], batches[3][1]["t"]["values"])


def test_gap_falls_back_and_resumes_past_the_dead_batch(tmp_path):
    d = str(tmp_path / "log")
    pub = DeltaPublisher(d, base_step=4)
    rows = {1: np.array([1, 2], np.int64), 2: np.array([3, 4], np.int64),
            3: np.array([5, 6], np.int64)}
    vals = {s: _vals(rows[s], 20 + s) for s in rows}
    pub.publish({"t": (rows[1], vals[1])}, 5)
    tgt = FakeTarget()
    sub = DeltaSubscriber(tgt, d, config=object(), checkpoint_root="ck")
    assert sub.poll() == 1 and tgt.step == 5
    pub.publish({"t": (rows[2], vals[2])}, 6)
    pub.publish({"t": (rows[3], vals[3])}, 7)
    os.remove(seg_path(d, 2))  # retention outran us: a real, permanent gap
    assert sub.poll() == 0
    assert sub.fallbacks == 1 and tgt.reloads == 1
    # resumed PAST the missing segment — at or before it would re-trigger
    # the same fallback on every poll forever
    assert sub.next_seq == 3
    assert sub.poll() == 1
    assert sub.applied_seq == 3 and sub.fallbacks == 1
    np.testing.assert_array_equal(tgt.tables["t"][rows[3]], vals[3])


def test_publisher_restart_falls_back_then_adopts_the_new_stream(tmp_path):
    d = str(tmp_path / "log")
    rows = np.arange(4, dtype=np.int64)
    a = DeltaPublisher(d, base_step=1)
    a.publish({"t": (rows, _vals(rows, 1))}, 2)
    tgt = FakeTarget()
    sub = DeltaSubscriber(tgt, d, config=object(), checkpoint_root="ck")
    assert sub.poll() == 1 and sub.publisher == a.id
    b = DeltaPublisher(d, base_step=2)
    new_vals = _vals(rows, 2)
    b.publish({"t": (rows, new_vals)}, 3)
    assert sub.poll() == 0  # changed publisher id IS the restart signal
    assert sub.fallbacks == 1 and tgt.reloads == 1
    assert sub.publisher == b.id
    assert sub.poll() == 1
    np.testing.assert_array_equal(tgt.tables["t"][rows], new_vals)


# ------------------------------------------------------ quantized deltas ----


def test_int8_delta_round_trip_matches_flush_requantized_rows(tmp_path):
    d = str(tmp_path / "log")
    rows = np.array([0, 3, 31, CAP - 1], np.int64)
    vals = _vals(rows, 7) * np.array([[1e-3], [1.0], [40.0], [0.2]],
                                     np.float32)
    pub = DeltaPublisher(d, base_step=0, dtype="int8")
    pub.publish({"t": (rows, vals)}, 1)
    header, tables = read_batch(seg_path(d, 1))
    assert header["dtype"] == "int8"
    # the wire carries the SAME codes/scales a host-master reload would
    # requantize to — so delta-served rows equal flush-requantized rows
    codes, scales = _np_quant_unit_rows(vals)
    np.testing.assert_array_equal(tables["t"]["values"], codes)
    np.testing.assert_array_equal(tables["t"]["scales"], scales)
    expect = _np_dequant_unit_rows(codes, scales, np.float32)
    tgt = FakeTarget()
    sub = DeltaSubscriber(tgt, d)
    assert sub.poll() == 1
    np.testing.assert_array_equal(tgt.tables["t"][rows], expect)


# ------------------------------------------------------- fleet cutover ----


def test_fleet_apply_lands_every_replica_on_one_version(tmp_path):
    table = _vals(range(CAP), 0)

    def factory(rid):
        return Servant({"t": table}, batch_buckets=(8,), cache_rows=32)

    fleet = Fleet(factory, replicas=3)
    d = str(tmp_path / "log")
    pub = DeltaPublisher(d, base_step=0)
    rows = np.array([4, 8, 15], np.int64)
    vals = _vals(rows, 5)
    pub.publish({"t": (rows, vals)}, 2)
    sub = DeltaSubscriber(fleet, d)
    before = {rid: rep.servant.version
              for rid, rep in fleet._replicas.items()}
    assert sub.poll() == 1
    versions = {rep.servant.version for rep in fleet._replicas.values()}
    assert len(versions) == 1  # one shared epoch: no mixed-version serving
    assert versions.pop() > max(before.values())
    assert {rep.servant.step for rep in fleet._replicas.values()} == {2}
    # both routed pulls serve the delta rows bit-identically
    for rid in fleet._replicas:
        np.testing.assert_array_equal(
            np.asarray(fleet._replicas[rid].servant.pull(rows)), vals)


# ------------------------------------------------- ledger / CI surfaces ----


def test_failure_report_renders_delta_gap_and_fallback_lines(tmp_path):
    led = Ledger(str(tmp_path / "l.jsonl"))
    tgt = FakeTarget()
    d = str(tmp_path / "log")
    pub = DeltaPublisher(d, base_step=0)
    rows = np.arange(2, dtype=np.int64)
    pub.publish({"t": (rows, _vals(rows, 1))}, 1)
    pub.publish({"t": (rows, _vals(rows, 2))}, 2)
    sub = DeltaSubscriber(tgt, d, config=object(), checkpoint_root="ck",
                          ledger=led)
    sub.poll()
    os.remove(seg_path(d, 1))  # force a detectable gap on re-subscribe
    sub._fallback("gap", failed_seq=1)
    out = render_failures(led)
    assert "DELTA-GAP" in out and "reason=gap" in out
    assert "FRESHNESS-FALLBACK" in out and "recovered=True" in out


# ------------------------------------- trainer -> deltas -> fleet parity ----


class _RecordingTarget:
    """Forwards to the fleet and remembers which rows the deltas touched, so
    the comparison covers exactly the delta-applied set."""

    def __init__(self, inner):
        self._inner = inner
        self.rows = {}

    @property
    def step(self):
        return self._inner.step

    def apply_rows(self, updates, **kw):
        for name, (ids, _vals) in updates.items():
            self.rows.setdefault(name, set()).update(
                int(r) for r in np.asarray(ids))
        return self._inner.apply_rows(updates, **kw)

    def reload_from_checkpoint(self, root, config, **kw):
        return self._inner.reload_from_checkpoint(root, config, **kw)


def test_published_deltas_serve_bit_identical_to_the_runs_checkpoint(tmp_path):
    """Train to S1 and serve that checkpoint from a 2-replica fleet; resume
    S1 -> S2 with ``freshness_publish: 1``; apply every delta batch. Every
    row a delta touched must then serve bit-identically to a fresh
    ``Servant.from_checkpoint`` of the step-S2 checkpoint, on every replica,
    with no fallback on the way."""
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.framework.trainer import TrainLoop
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    vocab_n, s1, s2 = 256, 8, 24
    rng = np.random.default_rng(17)
    w = 1.0 / np.arange(1, vocab_n + 1, dtype=np.float64) ** 1.1
    ids = np.searchsorted(
        np.cumsum(w) / w.sum(), rng.random(8_000)).astype(np.int32)
    counts = np.maximum(np.bincount(ids, minlength=vocab_n), 1).astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_n)], counts)
    ck_root, delta_dir = str(tmp_path / "ckpt"), str(tmp_path / "deltas")

    def trainer(**over):
        conf = {
            "dim": "16", "window": "1", "negatives": "4",
            "learning_rate": "0.3", "num_iters": "40", "batch_size": "128",
            "subsample": "0", "seed": "0", "packed": "0",
            "prefetch_batches": "0", "param_backup_root": ck_root,
            "param_backup_period": str(s1),
            "ledger_path": str(tmp_path / "LEDGER.jsonl"),
        }
        conf.update({k: str(v) for k, v in over.items()})
        return Word2VecTrainer(
            Config(conf), mesh=None, corpus_ids=ids, vocab=vocab)

    TrainLoop(trainer(), log_every=0).run(max_steps=s1)
    serve_cfg = Config({"dim": "16", "packed": "0", "seed": "17"})
    with Fleet.from_checkpoint(ck_root, serve_cfg, replicas=2) as fleet:
        TrainLoop(trainer(resume="auto", freshness_publish=1,
                          freshness_dir=delta_dir,
                          freshness_delta_dtype="float32"),
                  log_every=0).run(max_steps=s2)
        target = _RecordingTarget(fleet)
        sub = DeltaSubscriber(target, delta_dir, config=serve_cfg,
                              checkpoint_root=ck_root)
        assert sub.subscribe()
        for _ in range(s2):
            sub.poll()
            if sub.status()["applied_step"] >= s2:
                break
        st = sub.status()
        assert st["applied_step"] == s2 and st["fallbacks"] == 0
        assert st["applied_batches"] >= 1 and target.rows
        versions = {rep.servant.version for rep in fleet.replicas()}
        assert len(versions) == 1
        with Servant.from_checkpoint(ck_root, serve_cfg, step=s2) as want:
            for name, rowset in target.rows.items():
                rows = np.fromiter(sorted(rowset), np.int64)
                ref = np.asarray(want._tables[name])[rows]
                for rep in fleet.replicas():
                    np.testing.assert_array_equal(
                        np.asarray(rep.servant._tables[name])[rows], ref)
