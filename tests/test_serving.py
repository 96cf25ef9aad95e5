"""Serving subsystem: checkpoint->serve parity, top-k parity, cache/version
semantics, backpressure shed, and pad-row accounting.

The read path's correctness bars (ISSUE 6): a serving pull must return rows
bit-identical to the checkpointed tables on the f32 wire; the tiled top-k
kernel must match a NumPy full-scan reference; a table reload must atomically
invalidate the hot-row cache (version keying — stale rows can never be
served); a full admission queue must shed with a typed ``Overloaded`` that
reaches the run ledger and ``ledger-report --failures``; micro-batch pad
rows (sentinel id 0) must never be cached or counted as served rows.
"""

import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from swiftsnails_tpu.framework.checkpoint import load_tables, save_checkpoint
from swiftsnails_tpu.serving import (
    HotRowCache,
    Overloaded,
    Servant,
    normalize_table,
    topk_tiled,
)
from swiftsnails_tpu.serving.drill import build_word2vec_checkpoint
from swiftsnails_tpu.telemetry.ledger import Ledger, render_failures

DIM = 24
CAP = 256


@pytest.fixture(scope="module")
def w2v_ckpt(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("serve") / "ckpt")
    cfg, _ = build_word2vec_checkpoint(root, dim=DIM, capacity=CAP)
    return root, cfg


# ------------------------------------------------- checkpoint -> serve -----


def test_pull_round_trip_bit_identical(w2v_ckpt):
    root, cfg = w2v_ckpt
    state, manifest = load_tables(root)
    ref = np.asarray(normalize_table(state["in_table"]["table"], DIM, "packed"))
    with Servant.from_checkpoint(root, cfg) as servant:
        assert servant.step == manifest["step"]
        ids = np.array([0, 1, 5, CAP - 1, 17, 17, 3], np.int32)
        got = servant.pull(ids)
        np.testing.assert_array_equal(got, ref[ids])  # f32 wire: bit-exact
        # second pull is served from the hot-row cache — still bit-exact
        np.testing.assert_array_equal(servant.pull(ids), ref[ids])
        assert servant.cache.hits > 0


def test_load_tables_walks_back_over_corrupt_newest(tmp_path):
    root = str(tmp_path / "ckpt")
    cfg, _ = build_word2vec_checkpoint(root, dim=8, capacity=64)
    state, _ = load_tables(root)
    save_checkpoint(root, state, step=2, wait=True)
    # flip bytes in step 2's biggest array file: CRC (or decode) must reject
    step2 = next(p for p in (tmp_path / "ckpt").iterdir()
                 if p.name.endswith("_2"))
    victim = max(
        (p for p in step2.rglob("*") if p.is_file()
         and p.name != "manifest.json"),
        key=lambda p: p.stat().st_size,
    )
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    victim.write_bytes(bytes(blob))
    restored, manifest = load_tables(root)
    assert manifest["step"] == 1  # walked back past the corrupt newest
    del cfg, restored


def test_load_tables_raises_when_nothing_restorable(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_tables(str(tmp_path / "empty"))


# ----------------------------------------------------------- top-k kernel --


def test_topk_matches_numpy_reference():
    rng = np.random.default_rng(3)
    table = rng.standard_normal((CAP, DIM)).astype(np.float32)
    q = rng.standard_normal(DIM).astype(np.float32)
    tn = table / np.maximum(np.linalg.norm(table, axis=1, keepdims=True), 1e-9)
    sims = tn @ (q / max(np.linalg.norm(q), 1e-9))
    want = np.argsort(-sims)[:10]
    # tile_rows below capacity (and not dividing it): the scan must merge
    # partial tiles and mask the tail pad exactly
    scores, ids = topk_tiled(
        jnp.asarray(table), jnp.asarray(q)[None, :], k=10, tile_rows=50)
    np.testing.assert_array_equal(np.asarray(ids[0]), want)
    np.testing.assert_allclose(
        np.asarray(scores[0]), sims[want], rtol=1e-5, atol=1e-6)


def test_servant_topk_excludes_requested_ids(w2v_ckpt):
    root, cfg = w2v_ckpt
    with Servant.from_checkpoint(root, cfg) as servant:
        row = 7
        query = servant.pull([row])[0]
        out = servant.topk(query, k=5, exclude=(row,))
        assert len(out) == 5
        assert row not in [i for i, _ in out]


# ------------------------------------------------------ CTR score kernel ---


def test_ctr_score_matches_trainer_predict(tmp_path):
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.utils.config import Config

    root = str(tmp_path / "ctr")
    cfg = Config({
        "model": "logreg", "num_fields": "6", "capacity": "512",
        "packed": "1", "seed": "11", "init_scale": "1.0",
    })
    trainer = get_model("logreg")(
        cfg, mesh=None,
        data=(np.zeros(0, np.float32), np.zeros((0, 6), np.int32)),
    )
    save_checkpoint(root, trainer.init_state(), step=1, wait=True)
    state, _ = load_tables(root)
    rng = np.random.default_rng(5)
    feats = rng.integers(0, 1 << 20, size=(9, 6)).astype(np.int32)
    feats[0, 3] = -1  # PAD field must be masked exactly like training
    with Servant.from_checkpoint(root, cfg) as servant:
        got = servant.score(feats)
    # reference: the training-side forward over the packed-small plane
    from swiftsnails_tpu.models.sparse_base import CTRState
    from swiftsnails_tpu.parallel.store import PackedTableState

    ref_state = CTRState(
        table=PackedTableState(
            table=jnp.asarray(state["table"]["table"]), slots={}),
        dense=state["dense"], opt=None,
    )
    want = 1.0 / (1.0 + np.exp(-trainer.predict(ref_state, feats)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# --------------------------------------------------- cache + versioning ----


def test_cache_hits_then_version_bump_invalidates():
    rng = np.random.default_rng(0)
    t1 = rng.standard_normal((32, 4)).astype(np.float32)
    t2 = t1 + 1.0
    with Servant({"t": t1}, batch_buckets=(8,), cache_rows=64) as servant:
        ids = np.arange(8, dtype=np.int32)
        np.testing.assert_array_equal(servant.pull(ids), t1[ids])
        assert servant.cache.hits == 0
        np.testing.assert_array_equal(servant.pull(ids), t1[ids])
        assert servant.cache.hits == len(ids)  # fully cache-served
        v = servant.reload({"t": t2})
        assert v == 1
        # version bump: every old entry misses; new values are served
        np.testing.assert_array_equal(servant.pull(ids), t2[ids])
        assert servant.cache.misses >= 2 * len(ids)


def test_pad_rows_never_cached_or_counted():
    rng = np.random.default_rng(1)
    table = rng.standard_normal((16, 4)).astype(np.float32)
    with Servant({"t": table}, batch_buckets=(4,), cache_rows=64) as servant:
        got = servant.pull(np.array([5, 6, 7], np.int32))  # pads 3 -> 4
        np.testing.assert_array_equal(got, table[[5, 6, 7]])
        reg = servant.registry
        assert reg.counter("serve.pull.rows").value == 3
        assert reg.counter("serve.pull.pad_rows").value == 1
        # the pad sentinel (row 0) must not have been admitted to the cache
        assert ("t", 0) not in servant.cache._rows
        assert len(servant.cache) == 3


def test_hot_row_cache_rejects_pad_mask_rows():
    cache = HotRowCache(8)
    rows = np.ones((3, 2), np.float32)
    admitted = cache.put_many(
        "t", 0, np.array([4, 0, 5]), rows,
        pad_mask=np.array([False, True, False]),
    )
    assert admitted == 2 and ("t", 0) not in cache._rows


# ----------------------------------------------------------- backpressure --


def test_backpressure_sheds_typed_error_and_ledger_event(tmp_path, capsys):
    ledger_path = str(tmp_path / "ledger.jsonl")
    rng = np.random.default_rng(2)
    table = rng.standard_normal((16, 4)).astype(np.float32)
    servant = Servant(
        {"t": table}, batch_buckets=(4,), cache_rows=0, queue_depth=1,
        ledger=Ledger(ledger_path),
    )
    try:
        gate = threading.Event()
        entered = threading.Event()
        orig = servant._pull_fn

        def slow_pull(tbl, rows):
            entered.set()
            assert gate.wait(10)
            return orig(tbl, rows)

        servant._pull_fn = slow_pull
        t1 = threading.Thread(target=servant.pull, args=([1],), daemon=True)
        t1.start()
        assert entered.wait(10)  # dispatcher is parked inside the kernel
        t2 = threading.Thread(target=servant.pull, args=([2],), daemon=True)
        t2.start()
        for _ in range(1000):  # until t2's request occupies the queue
            if len(servant._batchers["pull"]._queue) >= 1:
                break
            threading.Event().wait(0.005)
        with pytest.raises(Overloaded):
            servant.pull([3])
        gate.set()
        t1.join(10)
        t2.join(10)
        assert servant.shed_count() == 1
        assert servant.registry.counter("serve.pull.shed").value == 1
    finally:
        servant.close()
    led = Ledger(ledger_path)
    ev = led.latest("overload")
    assert ev is not None and ev["kernel"] == "pull"
    assert ev["queue_depth"] == 1 and ev["shed_total"] == 1
    # ledger-report --failures renders the shed event
    assert "OVERLOAD kernel=pull" in render_failures(led)
