"""``optimizer_sharding: zero`` — ZeRO-style weight-update sharding over the
data axis (arXiv 2004.13336) and the depth-2 pipelined macro-step riding
along with it.

The core contracts pinned here:

* ``reduce_scatter_quantized`` returns, for EVERY wire format, exactly the
  owned slice of ``reduce_sum_quantized`` — sharding the update must never
  change a single bit of the math.
* The hybrid head under ``zero=True`` produces bit-identical parameters and
  slot planes to the unsharded push (the all-gathered param plane is exact
  f32 movement).
* The CTR dense-optimizer planes adopted by ``ZeroManager`` stay sharded
  through the jitted step, values bit-identical to the replicated run, and
  the per-replica HBM census shows the 1/data reduction.
* Checkpoints written from a sharded run are byte-identical (manifest CRCs)
  to the unsharded format, and ``resume: auto`` under sharding continues
  bit-identically.
* ``overlap: 2`` keeps the async-SGD staleness semantics: the same macro
  batch produces the same loss as ``overlap: 1`` and the serial schedule
  on the first dispatch.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from swiftsnails_tpu.data.vocab import Vocab
from swiftsnails_tpu.models.word2vec import Word2VecTrainer
from swiftsnails_tpu.parallel.comm import (
    reduce_scatter_quantized,
    reduce_sum_quantized,
)
from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from swiftsnails_tpu.parallel.placement import PlacementManager
from swiftsnails_tpu.parallel.zero import (
    ZeroManager,
    resolve_optimizer_sharding,
    zero_plane_spec,
)
from swiftsnails_tpu.utils.config import Config

DATA, MODEL = 4, 2


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({DATA_AXIS: DATA, MODEL_AXIS: MODEL}, jax.devices()[:8])


# ------------------------------------------------- reduce-scatter parity ---


@pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8", "int4",
                                  "int4x32"])
@pytest.mark.parametrize("stochastic", [False, True])
def test_reduce_scatter_matches_owned_slice(mesh, wire, stochastic):
    """The scatter form must be bit-identical to slicing the full reduce."""
    rows, dim = 32, 8
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(DATA, rows, dim)).astype(np.float32))
    seed = jnp.uint32(5)

    def full(xs):
        return reduce_sum_quantized(
            xs[0], DATA_AXIS, wire, DATA, stochastic=stochastic, seed=seed)

    def scat(xs):
        return reduce_scatter_quantized(
            xs[0], DATA_AXIS, wire, DATA, stochastic=stochastic, seed=seed)

    # xs keeps the global (DATA, rows, dim) buffer: in_spec P(DATA_AXIS)
    # hands each shard one identical full local gradient via xs[0];
    # check_vma off — the quantized paths move bytes with gather/all-to-all
    # and sum by hand, which the replication checker can't see through
    summed = jax.jit(jax.shard_map(
        full, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(),
        check_vma=False))(x)
    scattered = jax.jit(jax.shard_map(
        scat, mesh=mesh, in_specs=(P(DATA_AXIS),),
        out_specs=P(DATA_AXIS), check_vma=False))(x)
    np.testing.assert_array_equal(np.asarray(scattered), np.asarray(summed))


def test_reduce_scatter_rejects_misaligned_leading_dim(mesh):
    x = jnp.zeros((DATA, 30, 4), jnp.float32)  # 30 % 4 != 0

    def scat(xs):
        return reduce_scatter_quantized(xs[0], DATA_AXIS, "float32", DATA)

    with pytest.raises(ValueError, match="not\\s+divisible"):
        jax.jit(jax.shard_map(scat, mesh=mesh, in_specs=(P(DATA_AXIS),),
                          out_specs=P(DATA_AXIS)))(x)


def test_resolve_optimizer_sharding_validates():
    assert resolve_optimizer_sharding("none") == "none"
    assert resolve_optimizer_sharding("zero") == "zero"
    with pytest.raises(ValueError):
        resolve_optimizer_sharding("stage3")


def test_zero_plane_spec_eligibility():
    assert zero_plane_spec(np.zeros((8, 4)), 4) == P(DATA_AXIS)
    assert zero_plane_spec(np.zeros((6, 4)), 4) is None  # 6 % 4 != 0
    assert zero_plane_spec(np.zeros((2,)), 4) is None  # smaller than axis
    assert zero_plane_spec(np.float32(0.0), 4) is None  # scalar


# -------------------------------------------------- word2vec hybrid head ---


def _w2v(mesh, **overrides):
    vocab_size = 256
    rng = np.random.default_rng(0)
    counts = np.arange(vocab_size, 0, -1).astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    corpus = rng.integers(0, vocab_size, size=2048).astype(np.int32)
    base = {
        "dim": "8", "window": "2", "negatives": "2", "batch_size": "16",
        "num_iters": "1", "learning_rate": "0.05", "subsample": "0",
        "seed": "0", "packed": "1", "fused": "1", "grouped": "1",
        "steps_per_call": "2", "placement": "hybrid",
        "placement_head_rows": "64",
    }
    base.update({k: str(v) for k, v in overrides.items()})
    return Word2VecTrainer(Config(base), mesh=mesh, corpus_ids=corpus,
                           vocab=vocab)


def _w2v_step(trainer, mesh, batch=None):
    state = trainer.init_state()
    pm = PlacementManager(trainer, mesh)
    if pm.active:
        state = pm.adopt(state)
    zm = ZeroManager(trainer, mesh)
    if zm.active:
        state = zm.adopt(state)
    if batch is None:
        batch = next(iter(trainer.batches()))
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    st, m = jax.jit(trainer.train_step)(state, dev, jax.random.PRNGKey(0))
    return st, float(m["loss"]), batch


def test_zero_head_push_bit_identical(mesh):
    """Sharded head update == replicated head update, bit for bit."""
    base_tr = _w2v(mesh)
    st0, loss0, batch = _w2v_step(base_tr, mesh)
    zero_tr = _w2v(mesh, optimizer_sharding="zero")
    assert zero_tr.zero
    st1, loss1, _ = _w2v_step(zero_tr, mesh, batch=batch)
    assert loss1 == loss0
    np.testing.assert_array_equal(
        np.asarray(st1.in_table.head), np.asarray(st0.in_table.head))
    np.testing.assert_array_equal(
        np.asarray(st1.out_table.head), np.asarray(st0.out_table.head))


def test_zero_aligns_head_cut_to_data_axis(mesh):
    tr = _w2v(mesh, optimizer_sharding="zero", placement_head_rows="64")
    # zero requires cut % (group * data) == 0 so each shard owns whole rows
    assert tr.placement_cut % DATA == 0


# ------------------------------------------------------ CTR dense planes ---


def _ctr(mesh, **overrides):
    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.models.widedeep import WideDeepTrainer

    labels, feats, _ = synth_ctr(256, 4, 20, seed=1)
    base = {
        "num_fields": "4", "capacity": "1024", "batch_size": "64",
        "learning_rate": "0.1", "num_iters": "1", "seed": "0",
        "hidden_dims": "32,16", "embed_dim": "4", "optimizer": "adagrad",
        "packed": "0", "placement": "hybrid", "placement_head_rows": "128",
    }
    base.update({k: str(v) for k, v in overrides.items()})
    return WideDeepTrainer(Config(base), mesh=mesh, data=(labels, feats))


def _ctr_step(trainer, mesh):
    state = trainer.init_state()
    pm = PlacementManager(trainer, mesh)
    if pm.active:
        state = pm.adopt(state)
    zm = ZeroManager(trainer, mesh)
    if zm.active:
        state = zm.adopt(state)
    batch = next(iter(trainer.batches()))
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    st, m = jax.jit(trainer.train_step)(state, dev, jax.random.PRNGKey(0))
    return zm, pm, st, float(m["loss"])


def test_ctr_zero_planes_sharded_and_bit_identical(mesh):
    _, _, st0, loss0 = _ctr_step(_ctr(mesh), mesh)
    zm, _, st1, loss1 = _ctr_step(_ctr(mesh, optimizer_sharding="zero"), mesh)
    assert loss1 == loss0
    # census: the adopted planes dropped per-replica bytes by the data axis
    summary = zm.summary()
    assert summary["planes"] >= 1
    assert summary["reduction"] == float(DATA)
    assert (summary["replicated_bytes"]
            == DATA * summary["sharded_bytes_per_replica"])
    # values bit-identical, placement still sharded after the jitted step
    l0 = jax.tree_util.tree_leaves(st0.opt)
    l1 = jax.tree_util.tree_leaves(st1.opt)
    assert len(l0) == len(l1)
    for a, b in zip(l0, l1):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
    sharded = [
        x for x in l1
        if hasattr(x, "sharding") and isinstance(x.sharding, NamedSharding)
        and x.sharding.spec == P(DATA_AXIS)
    ]
    assert len(sharded) >= summary["planes"] - 1  # head slot lives in table
    np.testing.assert_array_equal(
        np.asarray(st1.table.head), np.asarray(st0.table.head))
    for k in st0.table.head_slots:
        np.testing.assert_array_equal(
            np.asarray(st1.table.head_slots[k]),
            np.asarray(st0.table.head_slots[k]))


def test_zero_manager_master_state_unshards(mesh):
    zm, pm, st, _ = _ctr_step(_ctr(mesh, optimizer_sharding="zero"), mesh)
    merged = zm.master_state(st)
    for leaf in jax.tree_util.tree_leaves(merged.opt):
        if hasattr(leaf, "sharding") and isinstance(
                leaf.sharding, NamedSharding):
            assert DATA_AXIS not in jax.tree_util.tree_leaves(
                [leaf.sharding.spec]), leaf.sharding


# ------------------------------------------------- checkpoint byte parity ---


def test_checkpoint_byte_identical_sharded_vs_unsharded(mesh, tmp_path):
    """A save under ``optimizer_sharding: zero`` must commit the exact bytes
    of the unsharded format (manifest CRC equality), and restore into a
    sharded-resident run."""
    from swiftsnails_tpu.framework.checkpoint import (
        read_manifest, restore_checkpoint, save_checkpoint,
    )

    _, pm0, st0, _ = _ctr_step(_ctr(mesh), mesh)
    zm, pm, st1, _ = _ctr_step(_ctr(mesh, optimizer_sharding="zero"), mesh)
    root0, root1 = str(tmp_path / "plain"), str(tmp_path / "zero")
    save_checkpoint(root0, st0, 1, placement=pm0)
    save_checkpoint(root1, st1, 1, placement=pm, zero=zm)
    m0, m1 = read_manifest(root0, 1), read_manifest(root1, 1)
    assert m0 is not None and m1 is not None
    assert m1["arrays"] == m0["arrays"]
    # restore the zero save into a fresh unsharded template: bit round-trip
    tr = _ctr(mesh)
    restored = restore_checkpoint(root1, tr.init_state())
    merged = pm.master_state(zm.master_state(st1))
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def test_resume_auto_under_sharding_bit_identical(mesh, tmp_path):
    """``resume: auto`` from a zero-sharded run's checkpoint continues
    bit-identically whether or not the resuming run shards again."""
    from swiftsnails_tpu.framework.trainer import TrainLoop

    root = str(tmp_path / "backups")
    tr = _w2v(mesh, optimizer_sharding="zero", param_backup_period="2",
              param_backup_root=root, steps_per_call="1")
    TrainLoop(tr, log_every=0).run(max_steps=2)

    def resume_run(**ov):
        t = _w2v(mesh, param_backup_period="1000000",
                 param_backup_root=root, resume="auto",
                 steps_per_call="1", **ov)
        return TrainLoop(t, log_every=0).run(max_steps=1)

    s_zero = resume_run(optimizer_sharding="zero")
    s_plain = resume_run()
    # run() returns the merged master state either way — every leaf must
    # match bit for bit
    lz = jax.tree_util.tree_leaves(s_zero)
    lp = jax.tree_util.tree_leaves(s_plain)
    assert len(lz) == len(lp)
    for a, b in zip(lp, lz):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


# ------------------------------------------------------- pipelined macro ---


def test_overlap_depths_agree_on_first_macro(mesh):
    """overlap 0/1/2 train the same macro batch to the same loss within f32
    tolerance — not bit for bit: substep i of depth d reads rows that miss
    the last d pushes, so from the second substep on its loss is computed on
    (slightly) different row values. One substep's update is ~1e-3 of a
    row, which moves the mean loss by 1-2 ulp here (measured per substep on
    jax 0.9.0: substep 0 identical, substeps 1-3 differ in the last bits) —
    a real staleness difference, not a reduction reordering."""
    losses = {}
    batch = None
    for depth in (0, 1, 2):
        tr = _w2v(mesh, overlap=depth, steps_per_call="4",
                  placement="uniform")
        _, loss, batch = _w2v_step(tr, mesh, batch=batch)
        losses[depth] = loss
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-5)
    np.testing.assert_allclose(losses[2], losses[0], rtol=1e-5)


def test_overlap_validation():
    with pytest.raises(ValueError, match="overlap"):
        _w2v(None, overlap="3")
    with pytest.raises(ValueError, match="requires"):
        _w2v(None, overlap="2", grouped="0")


def test_overlap2_composes_with_zero(mesh):
    tr = _w2v(mesh, overlap="2", steps_per_call="3",
              optimizer_sharding="zero")
    _, loss, _ = _w2v_step(tr, mesh)
    assert np.isfinite(loss)


# --------------------------------------------- audited grad-reduce bytes ---


def test_zero_grad_reduce_bytes_within_psum_baseline(mesh):
    """The dense-grad reduce of the hybrid head under ``zero``: its
    reduce-scatter must move no more bytes than the psum it replaces. A ring
    all-reduce is reduce-scatter + all-gather inside but the audit bills it
    once (its defining shape), so the scatter leg is compared like for like;
    the all-gather of the updated plane is what is left of the scope. Bytes
    come from the compiled step's HLO shapes: exact on any host."""
    from swiftsnails_tpu.telemetry.audit import audit_step

    def audited(**over):
        tr = _w2v(mesh, **over)
        state = tr.init_state()
        pm = PlacementManager(tr, mesh)
        if pm.active:
            state = pm.adopt(state)
        zm = ZeroManager(tr, mesh)
        if zm.active:
            state = zm.adopt(state)
        batch = next(iter(tr.batches()))
        dev = {k: jnp.asarray(v) for k, v in batch.items()}
        return audit_step(jax.jit(tr.train_step), state, dev,
                          jax.random.PRNGKey(0))

    base, zero = audited(), audited(optimizer_sharding="zero")
    psum_bytes = base["by_scope"]["ssn_hybrid_head_push"]
    ops = zero["ops"]
    scatter_bytes = sum((ops.get(k) or {}).get("bytes", 0)
                        for k in ("reduce-scatter", "all-reduce-scatter"))
    assert 0 < scatter_bytes <= psum_bytes
    assert zero["by_scope"]["ssn_zero_head_push"] >= scatter_bytes
    assert "ssn_hybrid_head_push" not in zero["by_scope"]
