"""Small-row packed plane (CTR tables) + fused AdaGrad RMW kernel.

The plane packs G = 128 // stride logical rows per 128-lane tile
(store.create_packed_small_table); lane groups are disjoint so tile-level
merging is exactly per-row merging. These tests pin the layout math against
the 2-D reference plane and the fused AdaGrad kernel (interpret mode)
against ``AdaGradAccess.apply_push_value``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.parallel.access import AdaGradAccess, SgdAccess
from swiftsnails_tpu.parallel.store import (
    TableState,
    create_packed_small_table,
    create_table,
    merge_duplicate_rows,
    pull_packed_small,
    push,
    push_packed_small,
    small_group,
)


def test_small_group_values():
    assert small_group(1) == 128
    assert small_group(8) == 16
    assert small_group(17) == 4  # Criteo W&D table_dim
    assert small_group(32) == 4
    assert small_group(33) == 2
    assert small_group(64) == 2
    assert small_group(65) == 1
    assert small_group(128) == 1
    with pytest.raises(ValueError):
        small_group(129)


@pytest.mark.parametrize("dim", [1, 17, 33])
def test_pull_matches_logical_layout(dim):
    cap = 512
    access = SgdAccess()
    state = create_packed_small_table(cap, dim, access, seed=3)
    g = small_group(dim)
    stride = 128 // g
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, cap, 200).astype(np.int32))
    got = pull_packed_small(state, rows, dim)
    # direct layout read: tile r//G, lanes (r%G)*stride ... + dim
    flat = np.asarray(state.table).reshape(cap // g, 128)
    want = np.stack([
        flat[r // g, (r % g) * stride : (r % g) * stride + dim]
        for r in np.asarray(rows)
    ])
    np.testing.assert_array_equal(np.asarray(got), want)
    # padding lanes between groups are zero
    lane = np.arange(128) % stride
    assert np.all(flat[:, lane >= dim] == 0)


@pytest.mark.parametrize("dim", [17, 33])
def test_push_sgd_matches_2d_plane(dim):
    """Same rows (with duplicates) + grads through the small plane and the
    2-D TableState plane must produce identical logical values."""
    cap = 256
    rng = np.random.default_rng(1)
    access = SgdAccess()
    small = create_packed_small_table(cap, dim, access, seed=5)
    # mirror into a logical 2-D table
    ids = jnp.arange(cap, dtype=jnp.int32)
    logical = pull_packed_small(small, ids, dim)
    ref = TableState(table=logical, slots={})

    rows = jnp.asarray(rng.integers(0, cap, 96).astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(96, dim)).astype(np.float32))
    new_small, _ = push_packed_small(small, rows, grads, access, 0.1, dim)
    new_ref = push(ref, rows, grads, access, 0.1)
    got = pull_packed_small(new_small, ids, dim)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(new_ref.table), rtol=1e-6, atol=1e-7
    )


def test_push_adagrad_merged_semantics():
    """AdaGrad through the small plane: duplicates merge their gradients
    BEFORE the accumulator update (exact merge_push_value semantics)."""
    cap, dim = 128, 17
    rng = np.random.default_rng(2)
    access = AdaGradAccess()
    small = create_packed_small_table(cap, dim, access, seed=7)
    ids = jnp.arange(cap, dtype=jnp.int32)
    logical = pull_packed_small(small, ids, dim)

    rows_np = np.array([3, 7, 3, 11, 7, 3], dtype=np.int32)
    grads_np = rng.normal(size=(6, dim)).astype(np.float32)
    new_small, live = push_packed_small(
        small, jnp.asarray(rows_np), jnp.asarray(grads_np), access, 0.5, dim
    )
    assert int(live) == 3  # rows 3, 7, 11 sit in tiles 0, 1, 2
    got = pull_packed_small(new_small, ids, dim)

    want = np.asarray(logical).copy()
    for r in np.unique(rows_np):
        g = grads_np[rows_np == r].sum(axis=0)
        accum = g * g  # slots start at zero
        want[r] = want[r] - 0.5 * g / np.sqrt(accum + access.eps)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_scatter_adagrad_kernel_interpret():
    """The fused RMW kernel (interpret mode) == apply_push_value, including
    skipped padding rows and accumulator state carried across calls."""
    from swiftsnails_tpu.ops.rowdma import scatter_adagrad_rows

    rng = np.random.default_rng(3)
    C, S, L, N = 64, 2, 128, 16
    access = AdaGradAccess()
    table = rng.normal(size=(C, S, L)).astype(np.float32)
    accum = (rng.random((C, S, L)) * 0.1).astype(np.float32)
    rows = np.concatenate([
        rng.permutation(C)[: N - 4].astype(np.int32),
        np.full(4, C, np.int32),  # padding: skipped
    ])
    grads = rng.normal(size=(N, S, L)).astype(np.float32)

    got_t, got_a = scatter_adagrad_rows(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(rows),
        jnp.asarray(grads), 0.3, block_rows=8, interpret=True,
    )
    want_t, want_a = table.copy(), accum.copy()
    for j, r in enumerate(rows):
        if r >= C:
            continue
        g = grads[j]
        want_a[r] = want_a[r] + g * g
        want_t[r] = want_t[r] - 0.3 * g / np.sqrt(want_a[r] + access.eps)
    np.testing.assert_allclose(np.asarray(got_t), want_t, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_a), want_a, rtol=1e-5, atol=1e-6)

    # second call: accumulator state must carry
    got_t2, got_a2 = scatter_adagrad_rows(
        got_t, got_a, jnp.asarray(rows), jnp.asarray(grads), 0.3,
        block_rows=8, interpret=True,
    )
    for j, r in enumerate(rows):
        if r >= C:
            continue
        g = grads[j]
        want_a[r] = want_a[r] + g * g
        want_t[r] = want_t[r] - 0.3 * g / np.sqrt(want_a[r] + access.eps)
    np.testing.assert_allclose(np.asarray(got_t2), want_t, rtol=1e-5, atol=1e-6)


def _fused_case(count, n=32, tiles=64, seed=5):
    """A slot-fused table, ``count`` sorted distinct tile ids followed by
    ``tail`` (default: the invalid row), and a non-zero gradient in EVERY
    slot, as ``merge_duplicate_rows`` never leaves them."""
    rng = np.random.default_rng(seed)
    param = rng.normal(size=(tiles, 1, 128)).astype(np.float32)
    accum = (rng.random((tiles, 1, 128)) * 0.1).astype(np.float32)
    table = jnp.asarray(np.concatenate([param, accum], axis=1))  # [T, 2, 128]
    ids = rng.permutation(tiles)[:n].astype(np.int32)
    live, dead = np.sort(ids[:count]), ids[count:]
    grads = jnp.asarray(rng.normal(size=(n, 1, 128)).astype(np.float32))
    return table, live, dead, grads


@jax.jit
def _fused_twin(table, uniq, grads, lr, count):
    """The XLA twin of the kernel inside ``push_packed_small``, jitted as
    in every CPU run of the trainers (run op by op it rounds otherwise)."""
    from swiftsnails_tpu.parallel.store import (
        PackedTableState, _apply_merged_small)

    state = PackedTableState(table=table, slots={})
    return _apply_merged_small(
        state, uniq, grads, count, AdaGradAccess(), lr, 8).table


# (count, slots, block_rows): nothing live, one row, one short of a block, a
# block, a block and one, a partial last block, every slot; then blocks wide
# enough for the 64-row chunked waits and the unrolled starts with their
# remainders
@pytest.mark.parametrize("count,n,block", [
    (0, 32, 8), (1, 32, 8), (7, 32, 8), (8, 32, 8), (9, 32, 8), (21, 32, 8),
    (32, 32, 8), (203, 256, 128), (129, 256, 128), (256, 256, 128),
])
def test_scatter_adagrad_fused_kernel_interpret(count, n, block):
    """Slot-fused RMW kernel (param+accum in one tile), told how many
    leading rows are live: bit-equal to the XLA twin at every count."""
    from swiftsnails_tpu.ops.rowdma import scatter_adagrad_fused_rows

    table, live, dead, grads = _fused_case(count, n=n, tiles=2 * n)
    tiles = table.shape[0]
    uniq = jnp.asarray(np.concatenate(
        [live, np.full(dead.shape, tiles, np.int32)]))
    want = _fused_twin(table, uniq, grads, 0.3, count)
    got = scatter_adagrad_fused_rows(
        table + 0, uniq, grads, 0.3, count, eps=AdaGradAccess().eps,
        block_rows=block, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    touched = np.any(np.asarray(got) != np.asarray(table), axis=(1, 2))
    assert sorted(np.flatnonzero(touched)) == sorted(live)


@pytest.mark.parametrize("count", [0, 5, 8, 19])
def test_scatter_adagrad_fused_kernel_ignores_rows_past_count(count):
    """The contract: slots from ``count`` on are not looked at, even where
    they hold valid, distinct ids with non-zero gradients. (Were a dead
    block to run, these rows would move.)"""
    from swiftsnails_tpu.ops.rowdma import scatter_adagrad_fused_rows

    table, live, dead, grads = _fused_case(count, seed=6)
    tiles = table.shape[0]
    got = scatter_adagrad_fused_rows(
        table + 0, jnp.asarray(np.concatenate([live, dead])), grads, 0.3,
        count, block_rows=8, interpret=True,
    )
    want = _fused_twin(
        table,
        jnp.asarray(np.concatenate([live, np.full(dead.shape, tiles, np.int32)])),
        grads, 0.3, count)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got)[dead], np.asarray(table)[dead])


@pytest.mark.parametrize("rows,invalid,want_uniq", [
    ([5, 2, 5, 9, 2, 5], 16, [2, 5, 9]),          # duplicates
    ([7, 3, 11, 0], 16, [0, 3, 7, 11]),          # none
    ([16, 4, 16, 1, 4, 40], 16, [1, 4]),         # ids >= invalid_row mixed in
    ([16, 16, 99], 16, []),                      # nothing to update
])
def test_merge_duplicate_rows_live_count(rows, invalid, want_uniq):
    """``live_count`` is the number of leading slots of ``uniq`` that hold a
    row below ``invalid_row``: sorted, distinct, everything else after."""
    from swiftsnails_tpu.parallel.store import live_count

    rows = jnp.asarray(rows, jnp.int32)
    grads = jnp.ones((rows.shape[0], 4), jnp.float32)
    uniq, merged = jax.jit(
        lambda r, g: merge_duplicate_rows(r, g, invalid_row=invalid))(rows, grads)
    count = int(live_count(uniq, invalid))
    assert count == len(want_uniq)
    assert list(np.asarray(uniq[:count])) == want_uniq
    assert np.all(np.asarray(uniq[count:]) >= invalid)
    for j, r in enumerate(want_uniq):  # the merged gradients meet their rows
        assert float(merged[j, 0]) == float(np.sum(np.asarray(rows) == r))


def test_fused_slot_layout_selected_for_adagrad():
    from swiftsnails_tpu.parallel.store import _fuse_small_slots

    assert _fuse_small_slots(AdaGradAccess(), jnp.float32)
    assert not _fuse_small_slots(SgdAccess(), jnp.float32)
    assert not _fuse_small_slots(
        AdaGradAccess(slot_dtype=jnp.bfloat16), jnp.float32)
    state = create_packed_small_table(128, 17, AdaGradAccess(), seed=0)
    assert state.table.shape == (32, 2, 128) and not state.slots
    state = create_packed_small_table(128, 17, SgdAccess(), seed=0)
    assert state.table.shape == (32, 1, 128)


def test_non_multiple_capacity_rounds_up():
    """capacity not divisible by the pack group must work (trailing group
    slots are dead padding) — the round-2 default CTR configs depend on it."""
    access = SgdAccess()
    state = create_packed_small_table(1000, 1, access, seed=0)  # g=128
    assert state.table.shape[0] == -(-1000 // 128)
    rows = jnp.asarray([0, 999], jnp.int32)
    vals = pull_packed_small(state, rows, 1)
    assert vals.shape == (2, 1)
    new, _ = push_packed_small(
        state, rows, jnp.ones((2, 1), jnp.float32), access, 0.5, 1)
    got = pull_packed_small(new, rows, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(vals) - 0.5,
                               rtol=1e-6)


def test_ctr_trainer_packed_plane_end_to_end():
    """W&D on the packed small plane trains (loss down, finite) and exports
    logical rows; packed: 0 still runs the 2-D plane."""
    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.utils.config import Config

    labels, feats, _ = synth_ctr(2048, 4, 50, seed=0)
    cfg = {
        "num_fields": "4", "capacity": "1024", "batch_size": "256",
        "learning_rate": "0.1", "num_iters": "4", "seed": "0",
        "hidden_dims": "16,8", "embed_dim": "4", "optimizer": "adagrad",
    }
    tr = get_model("widedeep")(Config(dict(cfg)), mesh=None, data=(labels, feats))
    assert tr.packed, "small plane should be on by default single-device"
    state = tr.init_state()
    step = jax.jit(tr.train_step, donate_argnums=(0,))
    losses = []
    for i, batch in enumerate(tr.batches()):
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                        jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-8:]) < np.mean(losses[:8])
    auc = tr.eval_auc(state)
    assert auc > 0.6, f"AUC {auc}"


def test_train_step_reports_push_live_share():
    """``train_step``'s metrics carry ``push_live_share`` = (distinct tiles
    of the batch) / (slots pushed), the share of the fused scatter's slots
    that get any per-slot work; a plane with no such count reports none."""
    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.utils.config import Config

    labels, feats, _ = synth_ctr(512, 4, 50, seed=1)
    cfg = {
        "num_fields": "4", "capacity": "1024", "batch_size": "256",
        "learning_rate": "0.1", "num_iters": "1", "seed": "0",
        "hidden_dims": "16,8", "embed_dim": "4", "optimizer": "adagrad",
    }
    tr = get_model("widedeep")(Config(dict(cfg)), mesh=None, data=(labels, feats))
    batch = next(iter(tr.batches()))
    batch["feats"] = batch["feats"].copy()
    batch["feats"][64:] = batch["feats"][0]  # three quarters: one example
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    tiles = np.asarray(tr._rows(batch["feats"])) // small_group(tr.table_dim)
    want = np.unique(tiles).size / tiles.size
    assert 0 < want < 0.3
    _, m = jax.jit(tr.train_step)(tr.init_state(), batch, jax.random.PRNGKey(0))
    assert set(m) == {"loss", "accuracy", "push_live_share"}
    assert float(m["push_live_share"]) == pytest.approx(want, rel=1e-6)

    dense = get_model("widedeep")(
        Config({**cfg, "packed": "0"}), mesh=None, data=(labels, feats))
    _, m = jax.jit(dense.train_step)(
        dense.init_state(), batch, jax.random.PRNGKey(0))
    assert set(m) == {"loss", "accuracy"}


def test_ctr_trainer_packed_vs_dense_agree_sgd():
    """SGD: the packed small plane and the 2-D plane are the same math —
    final logical tables must agree bit-close on identical batches."""
    from swiftsnails_tpu.data.ctr import synth_ctr
    from swiftsnails_tpu.models.registry import get_model
    from swiftsnails_tpu.utils.config import Config

    labels, feats, _ = synth_ctr(1024, 4, 50, seed=4)
    base = {
        "num_fields": "4", "capacity": "512", "batch_size": "256",
        "learning_rate": "0.1", "num_iters": "2", "seed": "0",
        "optimizer": "sgd", "factor_dim": "8",
    }
    finals = {}
    logical0 = None
    ids = jnp.arange(512, dtype=jnp.int32)
    for packed in ("1", "0"):
        cfg = Config({**base, "packed": packed})
        tr = get_model("fm")(cfg, mesh=None, data=(labels, feats))
        assert tr.packed == (packed == "1")
        state = tr.init_state()
        if packed == "1":
            logical0 = pull_packed_small(state.table, ids, tr.table_dim)
        else:
            # identical starting point: the two planes init with different
            # shapes/draws, so seed the 2-D table from the packed logical view
            state = state._replace(
                table=TableState(table=logical0, slots=state.table.slots)
            )
        step = jax.jit(tr.train_step, donate_argnums=(0,))
        for i, batch in enumerate(tr.batches()):
            state, _ = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                            jax.random.PRNGKey(i))
        if packed == "1":
            finals[packed] = np.asarray(
                pull_packed_small(state.table, ids, tr.table_dim))
        else:
            finals[packed] = np.asarray(state.table.table)
    np.testing.assert_allclose(finals["1"], finals["0"], rtol=2e-4, atol=1e-6)
