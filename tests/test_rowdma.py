"""Row-DMA kernels (interpret mode) + packed store + packed/pooled Word2Vec.

The kernels are exercised through pallas interpret mode on the CPU mesh —
same code path the TPU compiles (SURVEY §4's loopback-test analog at the
kernel level).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.ops import rowdma
from swiftsnails_tpu.parallel.access import AdaGradAccess, SgdAccess
from swiftsnails_tpu.parallel.store import (
    PackedTableState,
    create_packed_table,
    merge_duplicate_rows,
    pull_packed,
    push_packed,
)


def _mk_table(c=64, s=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((c, s, 128), dtype=np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("s", [1, 2])
@pytest.mark.parametrize("nblocks", [1, 2, 3])
# 8: one unrolled group; 12: no multiple of the unroll; 64: one chunked wait;
# 128: two; 192: three, and no multiple of 128
@pytest.mark.parametrize("block_rows", [8, 12, 64, 128, 192])
def test_gather_rows_interpret(block_rows, nblocks, s, dtype):
    table = _mk_table(c=48, s=s, seed=block_rows + nblocks).astype(dtype)
    n = block_rows * nblocks
    rows = np.random.default_rng(n + s).integers(0, 48, n).astype(np.int32)
    rows[1] = rows[0]  # an id twice in one block ...
    rows[-1] = rows[0]  # ... and, with more than one block, across blocks
    got = rowdma.gather_rows(
        table, jnp.asarray(rows), block_rows=block_rows, interpret=True)
    assert got.dtype == table.dtype and got.shape == (n, s, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table)[rows])


@pytest.mark.parametrize("n_ids", [24, 40])  # under one block, and padded to two
def test_pull_packed_small_through_the_gather_kernel(monkeypatch, n_ids):
    """The small-row plane's pull with the kernel in the XLA gather's place
    (interpret mode, as on the chip otherwise): same values, bit for bit."""
    from swiftsnails_tpu.parallel.store import (
        create_packed_small_table,
        pull_packed_small,
    )

    state = create_packed_small_table(100, 16, AdaGradAccess(), seed=3)
    assert state.table.shape[1:] == (2, 128)  # accumulator fused in
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 100, n_ids).astype(np.int32))
    want = pull_packed_small(state, ids, 16, block_rows=32)  # the XLA twin
    calls, gather = [], rowdma.gather_rows

    def kernel(table, rows, block_rows):
        calls.append(rows.shape[0])
        return gather(table, rows, block_rows=block_rows, interpret=True)

    monkeypatch.setattr(rowdma, "on_tpu", lambda: True)
    monkeypatch.setattr(rowdma, "gather_rows", kernel)
    got = pull_packed_small(state, ids, 16, block_rows=32)
    assert calls == [-(-n_ids // 32) * 32]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_add_rows_interpret_unique_and_padding():
    table = _mk_table()
    rows = np.array([3, 1, 7, 64, 64, 9, 2, 64], dtype=np.int32)  # 64 = padding
    deltas = np.random.default_rng(2).random((8, 2, 128)).astype(np.float32)
    want = np.asarray(table).copy()
    for r, d in zip(rows, deltas):
        if r < 64:
            want[r] += d
    got = rowdma.scatter_add_rows(
        jnp.asarray(table), jnp.asarray(rows), jnp.asarray(deltas),
        block_rows=4, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_scatter_write_rows_interpret():
    table = _mk_table()
    rows = np.array([5, 0, 63, 64], dtype=np.int32)
    vals = np.random.default_rng(3).random((4, 2, 128)).astype(np.float32)
    want = np.asarray(table).copy()
    for r, v in zip(rows, vals):
        if r < 64:
            want[r] = v
    got = rowdma.scatter_write_rows(
        jnp.asarray(table), jnp.asarray(rows), jnp.asarray(vals),
        block_rows=4, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.random((10, 200)).astype(np.float32)
    packed = rowdma.pack_rows(jnp.asarray(x))
    assert packed.shape == (10, 2, 128)
    assert float(jnp.abs(packed.reshape(10, -1)[:, 200:]).max()) == 0.0
    back = rowdma.unpack_rows(packed, 200)
    np.testing.assert_array_equal(np.asarray(back), x)


def test_packed_store_pull_push_sgd_matches_dense():
    """push_packed (XLA fallback on CPU) == reference per-key SGD math."""
    access = SgdAccess()
    state = create_packed_table(32, 200, access, seed=0)
    assert state.table.shape == (32, 2, 128)
    rows = jnp.asarray(np.array([1, 5, 1, 31, 5, 5], dtype=np.int32))
    grads2d = np.random.default_rng(4).random((6, 200)).astype(np.float32)
    grads = rowdma.pack_rows(jnp.asarray(grads2d))

    before = np.asarray(state.table).copy()
    new = push_packed(state, rows, grads, access, lr=0.1)
    want = before.reshape(32, -1).copy()
    for r, g in zip(np.asarray(rows), grads2d):
        want[r, :200] -= 0.1 * g
    np.testing.assert_allclose(
        np.asarray(new.table).reshape(32, -1), want, rtol=1e-5, atol=1e-6
    )
    # padding lanes still zero after the update
    assert float(jnp.abs(new.table.reshape(32, -1)[:, 200:]).max()) == 0.0

    pulled = pull_packed(new, jnp.asarray([1, 5], dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(pulled).reshape(2, -1)[:, :200], want[[1, 5], :200], rtol=1e-6
    )


def test_packed_store_adagrad_matches_2d():
    """AdaGrad via packed apply == same rule on an equivalent 2-D table."""
    from swiftsnails_tpu.parallel.store import TableState, create_table, push

    access = AdaGradAccess()
    packed = create_packed_table(16, 256, access, seed=1)
    dense = TableState(
        table=packed.table.reshape(16, 256),
        slots={k: v.reshape(16, 256) for k, v in packed.slots.items()},
    )
    rows = jnp.asarray(np.array([2, 9, 2, 15], dtype=np.int32))
    g2d = np.random.default_rng(5).random((4, 256)).astype(np.float32)
    new_p = push_packed(packed, rows, jnp.asarray(g2d).reshape(4, 2, 128),
                        access, lr=0.5)
    new_d = push(dense, rows, jnp.asarray(g2d), access, lr=0.5, exact=True)
    np.testing.assert_allclose(
        np.asarray(new_p.table).reshape(16, 256), np.asarray(new_d.table),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(new_p.slots["accum"]).reshape(16, 256),
        np.asarray(new_d.slots["accum"]), rtol=1e-5, atol=1e-6,
    )


def test_word2vec_packed_pool_loss_decreases():
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    vocab_size = 50
    counts = np.maximum(rng.integers(1, 50, vocab_size), 1).astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    # structured corpus: consecutive tokens correlated -> learnable signal
    base = np.repeat(np.arange(10), 40) % vocab_size
    corpus = ((base + rng.integers(0, 2, base.size)) % vocab_size).astype(np.int32)
    cfg = Config({
        "dim": "16", "window": "2", "negatives": "3", "learning_rate": "0.1",
        "batch_size": "64", "subsample": "0", "num_iters": "30",
        "pool_size": "8", "pool_block": "32", "steps_per_call": "2",
        "packed": "1", "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None, corpus_ids=corpus, vocab=vocab)
    assert tr.packed and tr.neg_mode == "pool"
    state = tr.init_state()
    assert isinstance(state.in_table, PackedTableState)
    step = jax.jit(tr.train_step)
    key = jax.random.PRNGKey(0)
    losses = []
    for i, batch in enumerate(tr.batches()):
        if batch["centers"].shape[0] % 64:
            continue
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                        jax.random.fold_in(key, i))
        losses.append(float(m["loss"]))
        if len(losses) >= 40:
            break
    assert len(losses) >= 10
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_packed_collectives_match_single_device():
    """pull/push_collective_packed over a (2, 4) mesh == local packed path."""
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from swiftsnails_tpu.parallel.transfer import (
        pull_collective_packed,
        push_collective_packed,
    )

    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    access = SgdAccess()
    state_m = create_packed_table(64, 200, access, mesh=mesh, seed=7)
    state_1 = PackedTableState(
        table=jnp.asarray(np.asarray(state_m.table)), slots={}
    )
    rng = np.random.default_rng(8)
    rows = jnp.asarray(rng.integers(0, 64, 16).astype(np.int32))
    grads = jnp.asarray(rng.random((16, 2, 128), dtype=np.float32))

    got = pull_collective_packed(mesh, state_m, rows)
    want = pull_packed(state_1, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    new_m = push_collective_packed(mesh, state_m, rows, grads, access, 0.1)
    new_1 = push_packed(state_1, rows, grads, access, 0.1)
    np.testing.assert_allclose(
        np.asarray(new_m.table), np.asarray(new_1.table), rtol=1e-5, atol=1e-6
    )


def test_word2vec_packed_mesh_trains():
    """Full packed+pool train_step over a (2, 2) mesh runs and loss is finite."""
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, batch_sharding, make_mesh
    from swiftsnails_tpu.utils.config import Config

    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 2}, devices=jax.devices()[:4])
    rng = np.random.default_rng(0)
    vocab = Vocab([f"w{i}" for i in range(64)],
                  np.maximum(rng.integers(1, 30, 64), 1).astype(np.int64))
    cfg = Config({"dim": "16", "window": "2", "negatives": "2",
                  "learning_rate": "0.1", "batch_size": "32", "subsample": "0",
                  "num_iters": "1", "packed": "1", "pool_size": "8",
                  "pool_block": "16"})
    tr = Word2VecTrainer(cfg, mesh=mesh,
                         corpus_ids=rng.integers(0, 64, 400).astype(np.int32),
                         vocab=vocab)
    assert tr.packed
    state = tr.init_state()
    batch = {
        "centers": jax.device_put(rng.integers(0, 64, 32).astype(np.int32),
                                  batch_sharding(mesh)),
        "contexts": jax.device_put(rng.integers(0, 64, 32).astype(np.int32),
                                   batch_sharding(mesh)),
    }
    state, m = jax.jit(tr.train_step)(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_word2vec_packed_export_and_neighbors(tmp_path):
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    vocab = Vocab([f"w{i}" for i in range(20)],
                  np.maximum(rng.integers(1, 9, 20), 1).astype(np.int64))
    cfg = Config({"dim": "8", "window": "2", "negatives": "2",
                  "learning_rate": "0.1", "batch_size": "16", "subsample": "0",
                  "num_iters": "1", "packed": "1"})
    tr = Word2VecTrainer(cfg, mesh=None,
                         corpus_ids=rng.integers(0, 20, 100).astype(np.int32),
                         vocab=vocab)
    state = tr.init_state()
    out = tmp_path / "vec.txt"
    tr.export_text(state, str(out))
    lines = out.read_text().strip().split("\n")
    assert lines[0] == "20 8"
    assert len(lines) == 21
    nb = tr.neighbors(state, "w0", topn=3)
    assert len(nb) == 3
