"""Sparsity-aware hybrid placement (parallel/hybrid.py, parallel/placement.py).

The zipf head of every sparse table lives replicated on each device (dense
quantized grad reduce), the tail keeps the model-sharded collective twins at
a statically smaller dedup capacity. These tests pin:

* the vocab coverage helpers and the auto-partitioner's cut choice (zipf
  picks a head, flat stays uniform, calibration rescales the model);
* split/merge round-trips bit-exactly and checkpoints stay byte-identical
  to the uniform layout (per-array CRC manifest equality);
* uniform-vs-hybrid training parity on the grouped mesh plane, the dense
  plane (8-dev and 1-dev meshes), the CTR small-row packed plane, and
  composed with comm_dtype: int8;
* non-composing configs (no mesh, table_tier: host) resolve to uniform
  with a recorded reason;
* the comm audit's per-table attribution, the ledger's placement
  rendering, and auto's >= 2x audited exchange cut with loss parity on a
  steep-zipf corpus.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swiftsnails_tpu.data.vocab import Vocab
from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.word2vec import Word2VecTrainer
from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from swiftsnails_tpu.parallel.placement import (
    PlacementManager, choose_cut, tail_cap,
)
from swiftsnails_tpu.utils.config import Config


def grouped_cfg(**overrides):
    cfg = {
        "dim": "16", "window": "1", "negatives": "4", "learning_rate": "0.3",
        "num_iters": "2", "batch_size": "256", "subsample": "0", "seed": "0",
        "packed": "1", "neg_mode": "pool", "pool_size": "8",
        "pool_block": "64", "fused": "1", "grouped": "1", "use_native": "0",
    }
    cfg.update(overrides)
    return cfg


def make_grouped_trainer(mesh, **overrides):
    from swiftsnails_tpu.framework.quality import paired_corpus

    ids, vocab = paired_corpus(n_pairs=8, reps=600, seed=0)
    return Word2VecTrainer(
        Config(grouped_cfg(**overrides)), mesh=mesh, corpus_ids=ids,
        vocab=vocab)


def train_grouped(mesh, steps=6, **overrides):
    tr = make_grouped_trainer(mesh, **overrides)
    state = tr.init_state()
    pm = PlacementManager(tr, mesh)
    if pm.active:
        state = pm.adopt(state)
    step = jax.jit(tr.train_step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    metrics, i = None, 0
    for batch in tr.batches():
        if batch["centers"].shape[0] % 8:
            continue
        dev = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = step(state, dev, jax.random.fold_in(key, i))
        i += 1
        if i >= steps:
            break
    state = pm.master_state(state)
    return tr, state, metrics


# ------------------------------------------------ vocab coverage helpers ---


def _zipf_vocab(n=1024, s=1.4):
    counts = (1e6 / np.arange(1, n + 1) ** s).astype(np.int64) + 1
    return Vocab([f"w{i}" for i in range(n)], counts)


def test_vocab_cumulative_coverage():
    v = _zipf_vocab()
    cov = v.cumulative_coverage()
    assert cov[0] == 0.0 and abs(cov[len(v.counts)] - 1.0) < 1e-12
    assert np.all(np.diff(cov) >= 0)
    # zipf: a small head covers most of the mass
    assert v.coverage_at(64) > 0.5
    assert v.coverage_at(64) == pytest.approx(cov[64])


def test_vocab_hottest_rows_are_frequency_ranks():
    v = _zipf_vocab()
    order = v.hottest_rows()
    # counts are rank-ordered, so the hottest rows are the prefix
    assert list(order[:8]) == list(range(8))
    assert v.coverage_at(0) == 0.0


# --------------------------------------------------------- auto cut choice ---


def test_choose_cut_zipf_picks_head_flat_stays_uniform():
    zipf = (1e6 / np.arange(1, 4097) ** 1.4).astype(np.int64) + 1
    d = choose_cut(zipf, 4096, align=4, local_slots=2048, row_elems=128,
                   data=2)
    assert d["cut"] > 0 and d["cut"] % 4 == 0
    assert d["coverage"] > 0.5
    assert d["predicted_exchange_bytes"] < d["predicted_uniform_bytes"] / 2
    flat = np.full(4096, 100, np.int64)
    assert choose_cut(flat, 4096, align=4, local_slots=2048,
                      row_elems=128, data=2)["cut"] == 0


def test_choose_cut_calibration_rescales_prediction():
    zipf = (1e6 / np.arange(1, 4097) ** 1.4).astype(np.int64) + 1
    kw = dict(align=4, local_slots=2048, row_elems=128, data=2)
    d = choose_cut(zipf, 4096, measured_uniform_bytes=1_000_000.0, **kw)
    assert d["predicted_uniform_bytes"] == pytest.approx(1_000_000.0)
    assert d["measured_uniform_bytes"] == pytest.approx(1_000_000.0)


def test_tail_cap_shrinks_with_coverage():
    assert tail_cap(1024, 0.95, slack=2.0) < tail_cap(1024, 0.5, slack=2.0)
    assert tail_cap(1024, 1.0, slack=2.0) >= 8  # never zero
    assert tail_cap(1024, 0.0, slack=8.0) <= tail_cap(1024, 0.0, slack=8.0)


# ---------------------------------------------- split/merge + checkpoints ---


def test_split_merge_round_trip_bit_exact():
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    tr = make_grouped_trainer(mesh, placement="hybrid",
                              placement_head_rows="8")
    assert tr.placement_cut == 8, tr.placement_decision
    state = tr.init_state()
    ref_in = np.asarray(state.in_table.table)
    ref_out = np.asarray(state.out_table.table)
    pm = PlacementManager(tr, mesh)
    assert pm.active
    split = pm.adopt(state)
    from swiftsnails_tpu.parallel.hybrid import is_hybrid

    assert is_hybrid(split.in_table) and is_hybrid(split.out_table)
    merged = pm.master_state(split)
    assert np.array_equal(np.asarray(merged.in_table.table), ref_in)
    assert np.array_equal(np.asarray(merged.out_table.table), ref_out)


def test_hybrid_checkpoint_byte_identical_to_uniform(tmp_path):
    from swiftsnails_tpu.framework.checkpoint import (
        read_manifest, save_checkpoint,
    )

    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    tr = make_grouped_trainer(mesh, placement="hybrid",
                              placement_head_rows="8")
    state = tr.init_state()
    save_checkpoint(str(tmp_path / "uniform"), state, 1)
    pm = PlacementManager(tr, mesh)
    hybrid = pm.adopt(state)
    save_checkpoint(str(tmp_path / "hybrid"), hybrid, 1, placement=pm)
    mu = read_manifest(str(tmp_path / "uniform"), 1)
    mh = read_manifest(str(tmp_path / "hybrid"), 1)
    # per-array CRCs over the exact bytes orbax writes: equal manifests
    # means the hybrid run's checkpoint is byte-identical uniform layout
    assert mu["arrays"] == mh["arrays"]


# ----------------------------------------------------- training parity -----


def test_grouped_mesh_hybrid_matches_uniform():
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    _, s_u, m_u = train_grouped(mesh)
    tr_h, s_h, m_h = train_grouped(mesh, placement="hybrid",
                                   placement_head_rows="8")
    assert tr_h.placement_cut == 8
    assert int(m_h.get("hybrid_dropped", 0)) == 0
    np.testing.assert_allclose(
        np.asarray(s_h.in_table.table), np.asarray(s_u.in_table.table),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_h.out_table.table), np.asarray(s_u.out_table.table),
        rtol=1e-4, atol=1e-5)
    assert abs(float(m_h["loss"]) - float(m_u["loss"])) < 1e-3


def test_grouped_mesh_hybrid_int8_loss_parity():
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    _, _, m_u = train_grouped(mesh, comm_dtype="int8")
    tr_h, _, m_h = train_grouped(mesh, comm_dtype="int8",
                                 placement="hybrid",
                                 placement_head_rows="8")
    assert tr_h.placement_cut == 8
    lu, lh = float(m_u["loss"]), float(m_h["loss"])
    assert np.isfinite(lh)
    assert abs(lh - lu) / abs(lu) < 0.02  # the int8 lane tolerance


def _dense_w2v(mesh, **overrides):
    from swiftsnails_tpu.framework.quality import paired_corpus

    ids, vocab = paired_corpus(n_pairs=8, reps=400, seed=0)
    cfg = {
        "dim": "16", "window": "1", "negatives": "4",
        "learning_rate": "0.1", "num_iters": "1", "batch_size": "128",
        "subsample": "0", "seed": "0", "use_native": "0",
    }
    cfg.update(overrides)
    tr = Word2VecTrainer(Config(cfg), mesh=mesh, corpus_ids=ids, vocab=vocab)
    state = TrainLoop(tr, log_every=0).run()
    return tr, state


@pytest.mark.parametrize("mesh_shape", [(2, 4), (1, 1)])
def test_dense_plane_hybrid_matches_uniform_trainloop(mesh_shape):
    data, model = mesh_shape
    mesh = make_mesh({DATA_AXIS: data, MODEL_AXIS: model},
                     devices=jax.devices()[: data * model])
    _, s_u = _dense_w2v(mesh)
    tr_h, s_h = _dense_w2v(mesh, placement="hybrid",
                           placement_head_rows="8")
    assert tr_h.placement_cut == 8, tr_h.placement_decision
    # TrainLoop merges at run end: the returned layout is uniform again
    assert s_h.in_table.table.shape == s_u.in_table.table.shape
    np.testing.assert_allclose(
        np.asarray(s_h.in_table.table), np.asarray(s_u.in_table.table),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_h.out_table.table), np.asarray(s_u.out_table.table),
        rtol=1e-4, atol=1e-5)


def test_ctr_packed_small_hybrid_matches_uniform():
    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.models.registry import get_model

    data = synth_ctr(4096, 4, 40, seed=3)
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})

    def run(**overrides):
        cfg = Config({
            "num_fields": "4", "capacity": str(1 << 12),
            "learning_rate": "0.2", "optimizer": "adagrad",
            "batch_size": "512", "num_iters": "1", "seed": "0",
        })
        for k, v in overrides.items():
            cfg.set(k, v)
        labels, feats, _ = data
        tr = get_model("logreg")(cfg, mesh=mesh, data=(labels, feats))
        state = TrainLoop(tr, log_every=0).run()
        return tr, state

    _, s_u = run()
    tr_h, s_h = run(placement="hybrid", placement_head_rows="1024")
    assert tr_h.placement_cut > 0, tr_h.placement_decision
    assert s_h.table.table.shape == s_u.table.table.shape
    np.testing.assert_allclose(
        np.asarray(s_h.table.table), np.asarray(s_u.table.table),
        rtol=1e-4, atol=1e-5)


# ------------------------------------------------ uniform-fallback rules ---


def test_placement_resolves_uniform_without_mesh():
    tr = make_grouped_trainer(None, placement="hybrid")
    assert tr.placement_cut == 0
    assert tr.placement_decision["mode"] == "uniform"
    assert "mesh" in tr.placement_decision["reason"]


def test_placement_resolves_uniform_under_tiered():
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    # table_tier: host rides the packed (non-fused) substeps
    tr = make_grouped_trainer(mesh, placement="auto", table_tier="host",
                              tier_hbm_budget_mb="64", fused="0",
                              grouped="0")
    assert tr.placement_cut == 0
    assert "tier" in tr.placement_decision["reason"]


def test_auto_uses_vocab_cdf():
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    tr = make_grouped_trainer(mesh, placement="auto")
    d = tr.placement_decision
    assert d is not None and d["requested"] == "auto"
    # whichever way auto lands, the decision must carry the model's numbers
    assert "predicted_uniform_bytes" in d


# --------------------------------------------------- audit by_table --------


def test_collective_stats_routes_table_scopes():
    from swiftsnails_tpu.telemetry.audit import collective_stats

    hlo = "\n".join([
        '  %ar = f32[16,8]{1,0} all-reduce(%x), '
        'metadata={op_name="jit(step)/ssn_tbl_in/ssn_pull_psum/mul"}',
        '  %ag = f32[32,8]{1,0} all-gather(%y), '
        'metadata={op_name="jit(step)/ssn_tbl_out/ssn_push_gather/add"}',
        '  %p = f32[4,8]{1,0} all-reduce(%z), '
        'metadata={op_name="jit(step)/ssn_hybrid_head_push/psum"}',
    ])
    stats = collective_stats(hlo)
    assert stats["by_table"] == {"in": 512, "out": 1024}
    assert stats["by_scope"] == {
        "ssn_pull_psum": 512, "ssn_push_gather": 1024,
        "ssn_hybrid_head_push": 128,
    }
    assert stats["total_bytes"] == 512 + 1024 + 128


# --------------------------------------------- ledger render + CI gate -----


def test_ledger_renders_placement_decision(tmp_path):
    from swiftsnails_tpu.telemetry.ledger import Ledger, render_report

    led = Ledger(str(tmp_path / "l.jsonl"))
    led.append("run", {
        "model": "word2vec", "steps": 10, "items": 100,
        "placement": {"mode": "hybrid", "cut": 512, "replicated_rows": 1024,
                      "coverage": 0.93, "predicted_exchange_bytes": 1200.0,
                      "predicted_uniform_bytes": 9000.0,
                      "measured_exchange_bytes": 1300},
    })
    out = render_report(led)
    assert "hybrid placement (newest last):" in out
    assert "mode=hybrid" in out and "cut=512" in out
    assert "replicated_rows=1024" in out
    assert "measured=" in out and "predicted=" in out


# ------------------------------------- auto's cut on a steep-zipf corpus ---


def test_auto_cut_halves_audited_exchange_bytes_on_skewed_corpus():
    """On a steep-zipf corpus (s=1.4, vocab id == frequency rank) the
    auto-cut hybrid split, calibrated with the uniform layout's audited
    exchange bytes, must move at most half of them per step at the same
    wire format, with loss parity on identical batches and keys. The bytes
    come from the compiled step's HLO shapes, so they are exact here."""
    import itertools

    from swiftsnails_tpu.data.sampler import batch_stream, skipgram_windows
    from swiftsnails_tpu.parallel.mesh import batch_sharding
    from swiftsnails_tpu.telemetry.audit import audit_step

    vocab_n, dim, b_shard, spc, data, model = 1024, 16, 256, 2, 2, 4
    macro_n = b_shard * data * spc
    rng = np.random.default_rng(23)
    w = 1.0 / np.arange(1, vocab_n + 1, dtype=np.float64) ** 1.4
    ids = np.searchsorted(
        np.cumsum(w) / w.sum(), rng.random(16_000)).astype(np.int32)
    counts = np.bincount(ids, minlength=vocab_n).astype(np.int64)
    # sampling noise can swap neighbours: re-rank so that id == rank exactly
    order = np.argsort(-counts, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(vocab_n)
    ids = inv[ids].astype(np.int32)
    vocab = Vocab([f"w{i}" for i in range(vocab_n)],
                  np.maximum(counts[order], 1))

    rng = np.random.default_rng(29)
    g_c, g_x = skipgram_windows(ids, 5, rng)
    batches = [
        b for b in itertools.islice(batch_stream(g_c, g_x, macro_n, rng), 4)
        if b["centers"].shape[0] == macro_n]
    assert batches
    mesh = make_mesh({DATA_AXIS: data, MODEL_AXIS: model},
                     devices=jax.devices()[:8])
    bs = batch_sharding(mesh)
    dev_batches = [{k: jax.device_put(v, bs) for k, v in b.items()}
                   for b in batches]

    def layout(placement, calib_bytes=None):
        conf = {
            "dim": str(dim), "window": "5", "negatives": "5",
            "learning_rate": "0.025", "batch_size": str(macro_n // spc),
            "subsample": "0", "num_iters": "1", "steps_per_call": str(spc),
            "table_dtype": "float32", "packed": "1", "neg_mode": "pool",
            "pool_size": "64", "pool_block": "512", "fused": "1",
            "grouped": "1", "comm_dtype": "float32", "placement": placement,
        }
        if calib_bytes:
            conf["placement_calib_bytes"] = str(int(calib_bytes))
        trainer = Word2VecTrainer(
            Config(conf), mesh=mesh, corpus_ids=np.zeros(2, np.int32),
            vocab=vocab)
        state = trainer.init_state()
        pm = PlacementManager(trainer, mesh)
        if pm.active:
            state = pm.adopt(state)
        step = jax.jit(trainer.train_step, donate_argnums=(0,))
        key = jax.random.PRNGKey(3)
        for i in range(4):
            state, m = step(state, dev_batches[i % len(dev_batches)],
                            jax.random.fold_in(key, i))
        report = audit_step(
            step, state, dev_batches[0], jax.random.fold_in(key, 0))
        return trainer, sum(report["by_scope"].values()), float(m["loss"]), report

    _, uniform_bytes, uniform_loss, _ = layout("uniform")
    hybrid, hybrid_bytes, hybrid_loss, report = layout(
        "auto", calib_bytes=uniform_bytes)
    assert hybrid.placement_decision["mode"] == "hybrid"
    assert hybrid.placement_decision["cut"] == hybrid.placement_cut > 0
    assert uniform_bytes >= 2.0 * hybrid_bytes > 0
    assert abs(hybrid_loss - uniform_loss) <= 0.01 * abs(uniform_loss)
    assert report["by_table"]
