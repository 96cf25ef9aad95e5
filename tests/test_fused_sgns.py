"""Fused SGNS kernel vs an exact sequential reference (interpret mode).

In interpret mode the grid is sequential, so the kernel's result equals
"apply blocks in order; within a block gather first, then write V rows in
index order, then U rows, then pool rows (later write wins)" — which this
test implements directly in numpy.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.ops.fused_sgns import fused_sgns_step


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def reference_fused(in_t, out_t, in_rows, pos_rows, pool_rows, lr, lam, p, pn):
    """Models the kernel's double-buffered schedule: block b's reads are
    issued before block b-1's writes land, so they see the table state after
    writes of blocks <= b-2 (the one-block hogwild staleness window)."""
    in_t = in_t.copy()
    out_t = out_t.copy()
    b = len(in_rows)
    nblocks = b // p
    inv_b = 1.0 / b
    total_loss = 0.0
    d = in_t.shape[1] * in_t.shape[2]
    snap_in, snap_out = in_t.copy(), out_t.copy()  # writes <= b-2 view
    for blk in range(nblocks):
        ir = in_rows[blk * p : (blk + 1) * p]
        pr = pos_rows[blk * p : (blk + 1) * p]
        qr = pool_rows[blk * pn : (blk + 1) * pn]
        V = snap_in[ir].reshape(p, d).astype(np.float32)
        U = snap_out[pr].reshape(p, d).astype(np.float32)
        Q = snap_out[qr].reshape(pn, d).astype(np.float32)
        snap_in, snap_out = in_t.copy(), out_t.copy()  # now writes <= blk-1
        pos = (V * U).sum(1)
        neg = V @ Q.T
        g_pos = (_sigmoid(pos) - 1.0) * inv_b
        g_neg = lam * inv_b * _sigmoid(neg)
        dV = g_pos[:, None] * U + g_neg @ Q
        dU = g_pos[:, None] * V
        dQ = g_neg.T @ V
        shape = in_t.shape[1:]
        for j in range(p):  # V writes, later index wins
            in_t[ir[j]] = (V[j] - lr * dV[j]).reshape(shape)
        for j in range(p):  # then U writes
            out_t[pr[j]] = (U[j] - lr * dU[j]).reshape(shape)
        for q in range(pn):  # then pool writes
            out_t[qr[q]] = (Q[q] - lr * dQ[q]).reshape(shape)
        total_loss += -(
            np.log(_sigmoid(pos)).sum() + lam * np.log(_sigmoid(-neg)).sum()
        ) * inv_b
    return in_t, out_t, total_loss


@pytest.mark.parametrize("seed", [0, 1])
def test_fused_matches_sequential_reference(seed):
    rng = np.random.default_rng(seed)
    C, S, L = 64, 2, 128
    B, P, PN = 32, 8, 4
    in_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    out_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    # include duplicates on purpose (hogwild semantics must still match the
    # sequential reference under interpret's serial execution)
    in_rows = rng.integers(0, C, B).astype(np.int32)
    pos_rows = rng.integers(0, C, B).astype(np.int32)
    pool_rows = rng.integers(0, C, (B // P) * PN).astype(np.int32)
    lr, lam = 0.05, 0.625

    want_in, want_out, want_loss = reference_fused(
        in_t, out_t, in_rows, pos_rows, pool_rows, lr, lam, P, PN
    )
    got_in, got_out, got_loss = fused_sgns_step(
        jnp.asarray(in_t), jnp.asarray(out_t),
        jnp.asarray(in_rows), jnp.asarray(pos_rows), jnp.asarray(pool_rows),
        lr=lr, lam=lam, pairs_per_block=P, pool_size=PN, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_in), want_in, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_out), want_out, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got_loss), want_loss, rtol=1e-4)


def test_fused_trains_toy_corpus():
    """End to end through the trainer config (fused: 1), CPU interpret."""
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    vocab_size = 32
    counts = np.maximum(rng.integers(1, 20, vocab_size), 1).astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    base = np.repeat(np.arange(8), 60) % vocab_size
    corpus = ((base + rng.integers(0, 2, base.size)) % vocab_size).astype(np.int32)
    cfg = Config({
        "dim": "16", "window": "2", "negatives": "2", "learning_rate": "0.1",
        "batch_size": "64", "subsample": "0", "num_iters": "20",
        "pool_size": "8", "pool_block": "16", "packed": "1", "fused": "1",
        "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None, corpus_ids=corpus, vocab=vocab)
    assert tr.fused
    state = tr.init_state()
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
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


# ---------------------------------------------------------------- grouped ---


def reference_grouped(in_t, out_t, centers, ctxs, pool_rows, lr, lam, window,
                      pc, pn):
    """Sequential reference for the center-major kernel: same double-buffer
    staleness window as reference_fused; per block, reads see writes of
    blocks <= b-2, pool shared center-wide, pads skipped."""
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_grouped_step  # noqa

    in_t = in_t.copy()
    out_t = out_t.copy()
    n, cw = ctxs.shape
    nblocks = n // pc
    inv_b = 1.0 / (n * (window + 1))
    d = in_t.shape[1] * in_t.shape[2]
    shape = in_t.shape[1:]
    total_loss = 0.0
    snap_in, snap_out = in_t.copy(), out_t.copy()
    for blk in range(nblocks):
        cr = centers[blk * pc : (blk + 1) * pc]
        cx = ctxs[blk * pc : (blk + 1) * pc]  # [pc, cw], -1 pads
        qr = pool_rows[blk * pn : (blk + 1) * pn]
        V = snap_in[cr].reshape(pc, d).astype(np.float32)
        U = np.zeros((cw, pc, d), np.float32)
        mask = np.zeros((cw, pc), np.float32)
        for p in range(pc):
            for c in range(cw):
                if cx[p, c] >= 0:
                    U[c, p] = snap_out[cx[p, c]].reshape(d)
                    mask[c, p] = 1.0
        Q = snap_out[qr].reshape(pn, d).astype(np.float32)
        snap_in, snap_out = in_t.copy(), out_t.copy()
        pos = (U * V[None]).sum(-1)  # [cw, pc]
        n_real = mask.sum(0)  # [pc]
        neg = V @ Q.T  # [pc, pn]
        g_pos = (_sigmoid(pos) - 1.0) * inv_b * mask
        g_neg = lam * inv_b * _sigmoid(neg) * n_real[:, None]
        dV = (g_pos[:, :, None] * U).sum(0) + g_neg @ Q
        dU = g_pos[:, :, None] * V[None]
        dQ = g_neg.T @ V
        for p in range(pc):
            in_t[cr[p]] = (V[p] - lr * dV[p]).reshape(shape)
        # U writes in compacted (c-major) order, later write wins
        for c in range(cw):
            for p in range(pc):
                if cx[p, c] >= 0:
                    out_t[cx[p, c]] = (U[c, p] - lr * dU[c, p]).reshape(shape)
        for q in range(pn):
            out_t[qr[q]] = (Q[q] - lr * dQ[q]).reshape(shape)
        total_loss += -(
            (np.log(_sigmoid(pos)) * mask).sum()
            + lam * (np.log(_sigmoid(-neg)) * n_real[:, None]).sum()
        ) * inv_b
    return in_t, out_t, total_loss


def _grouped_inputs(rng, C, N, PC, PN, CW, blocks=None):
    """Centers, contexts and pool rows of a grouped step. ``blocks`` gives,
    per block of ``PC`` centers, (real context slots, distinct rows among
    them): what the kernel reads as ``nctx`` and as its write prefix
    ``nwrite_u``. Without it the slots are random, pads and a fully padded
    center included."""
    centers = rng.integers(0, C, N).astype(np.int32)
    pool_rows = rng.integers(0, C, (N // PC) * PN).astype(np.int32)
    if blocks is None:
        ctxs = rng.integers(0, C, (N, CW)).astype(np.int32)
        # random pads (including fully-padded centers) + duplicates
        ctxs[rng.random((N, CW)) < 0.4] = -1
        ctxs[3] = -1
        return centers, ctxs, pool_rows
    assert len(blocks) == N // PC
    ctxs = np.full((N, CW), -1, np.int32)
    for b, (real, distinct) in enumerate(blocks):
        rows = rng.permutation(C)[:distinct]
        rows = np.concatenate([rows, rng.choice(rows, real - distinct)]) if real else rows
        slots = rng.permutation(PC * CW)[:real]
        ctxs[b * PC + slots // CW, slots % CW] = rng.permutation(rows)
    return centers, ctxs, pool_rows


# name -> (seed, PC, PN, per block (real context slots, distinct rows) or None).
# The issue loops start _START_UNROLL (8) copies to an iteration and the
# remainder one by one: every count below 8, at 8, and 8k + r is a path
_GROUPED_CASES = {
    "seed0": (0, 8, 4, None),
    "seed1": (1, 8, 4, None),
    # reads of 0, 1..7, exactly 8 and 8k + r slots, all rows distinct, so
    # the write prefix is as long as the read list
    "ctx_0_5_8_19": (2, 8, 4, [(0, 0), (5, 5), (8, 8), (19, 19)]),
    "ctx_1_7_16_33": (3, 8, 4, [(1, 1), (7, 7), (16, 16), (33, 33)]),
    # duplicates: the write prefix (last occurrences only) is shorter than
    # the read list and has edges of its own
    "write_prefix_1_3_8_17": (4, 8, 4, [(9, 1), (12, 3), (21, 8), (40, 17)]),
    # neither the centers nor the pool a multiple of 8: 12 = 8 + 4, 11 = 8 + 3
    "pc12_pool11": (5, 12, 11, None),
    "pc12_pool11_ctx_0_8_13_72": (6, 12, 11, [(0, 0), (8, 8), (13, 9), (72, 30)]),
    # the same step with the starts one to an iteration, the loop before PR 32
    "bit_equal_to_rolled_starts": (7, 8, 4, [(0, 0), (6, 4), (8, 8), (27, 19)]),
}


@pytest.mark.parametrize("case", list(_GROUPED_CASES))
def test_grouped_matches_sequential_reference(case, monkeypatch):
    from swiftsnails_tpu.ops import fused_sgns

    seed, PC, PN, blocks = _GROUPED_CASES[case]
    rng = np.random.default_rng(seed)
    C, S, L = 64, 2, 128
    N, W = 4 * PC, 3
    CW = 2 * W
    in_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    out_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    centers, ctxs, pool_rows = _grouped_inputs(rng, C, N, PC, PN, CW, blocks)
    lr, lam = 0.05, 0.625
    if blocks is not None:  # the counts the kernel will be handed
        per_block = ctxs.reshape(N // PC, PC * CW)
        assert [(int((r >= 0).sum()), len(np.unique(r[r >= 0])))
                for r in per_block] == blocks

    def step():
        return fused_sgns.fused_sgns_grouped_step(
            jnp.asarray(in_t), jnp.asarray(out_t), jnp.asarray(centers),
            jnp.asarray(ctxs), jnp.asarray(pool_rows),
            lr=lr, lam=lam, window=W, centers_per_block=PC, pool_size=PN,
            interpret=True,
        )

    want_in, want_out, want_loss = reference_grouped(
        in_t, out_t, centers, ctxs, pool_rows, lr, lam, W, PC, PN
    )
    got_in, got_out, got_loss = step()
    np.testing.assert_allclose(np.asarray(got_in), want_in, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_out), want_out, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got_loss), want_loss, rtol=1e-4)

    if case == "bit_equal_to_rolled_starts":
        # the jitted step keeps its trace: drop it on both sides of the patch
        try:
            with monkeypatch.context() as patch:
                patch.setattr(fused_sgns, "_START_UNROLL", 1)
                fused_sgns.fused_sgns_grouped_step.clear_cache()
                rolled = step()
        finally:
            fused_sgns.fused_sgns_grouped_step.clear_cache()
        for got, want in zip((got_in, got_out, got_loss), rolled):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------- resident ---


def reference_resident(in_t, out_t, centers, ctxs, pool_rows, lr, lam, window,
                       pc, pn, hot_n):
    """Sequential reference for the resident kernel: rows < hot_n live in a
    resident copy — reads always current (writes <= b-1), duplicate slots
    within a block SUM their gradients (merged, deterministic). Cold rows
    keep the grouped kernel's semantics: reads see writes <= b-2,
    last-write-wins in V, U (c-major), pool order."""
    in_t = in_t.copy()
    out_t = out_t.copy()
    hi, ho = in_t[:hot_n].copy(), out_t[:hot_n].copy()
    n, cw = ctxs.shape
    nblocks = n // pc
    inv_b = 1.0 / (n * (window + 1))
    d = in_t.shape[1] * in_t.shape[2]
    shape = in_t.shape[1:]
    total_loss = 0.0
    snap_in, snap_out = in_t.copy(), out_t.copy()
    for blk in range(nblocks):
        cr = centers[blk * pc : (blk + 1) * pc]
        cx = ctxs[blk * pc : (blk + 1) * pc]  # [pc, cw], -1 pads
        qr = pool_rows[blk * pn : (blk + 1) * pn]
        V = np.stack([
            hi[r].reshape(d) if r < hot_n else snap_in[r].reshape(d)
            for r in cr
        ]).astype(np.float32)
        U = np.zeros((cw, pc, d), np.float32)
        mask = np.zeros((cw, pc), np.float32)
        for p in range(pc):
            for c in range(cw):
                r = cx[p, c]
                if r >= 0:
                    U[c, p] = (ho[r] if r < hot_n else snap_out[r]).reshape(d)
                    mask[c, p] = 1.0
        Q = np.stack([
            (ho[r] if r < hot_n else snap_out[r]).reshape(d) for r in qr
        ]).astype(np.float32)
        snap_in, snap_out = in_t.copy(), out_t.copy()
        pos = (U * V[None]).sum(-1)
        n_real = mask.sum(0)
        neg = V @ Q.T
        g_pos = (_sigmoid(pos) - 1.0) * inv_b * mask
        g_neg = lam * inv_b * _sigmoid(neg) * n_real[:, None]
        dV = (g_pos[:, :, None] * U).sum(0) + g_neg @ Q
        dU = g_pos[:, :, None] * V[None]
        dQ = g_neg.T @ V
        # hot: exact merged accumulation, one application per row
        dv_sum = np.zeros((hot_n, d), np.float32)
        du_sum = np.zeros((hot_n, d), np.float32)
        for p in range(pc):
            if cr[p] < hot_n:
                dv_sum[cr[p]] += dV[p]
            else:
                in_t[cr[p]] = (V[p] - lr * dV[p]).reshape(shape)
        for c in range(cw):  # cold U writes in c-major order, later wins
            for p in range(pc):
                r = cx[p, c]
                if r >= 0:
                    if r < hot_n:
                        du_sum[r] += dU[c, p]
                    else:
                        out_t[r] = (U[c, p] - lr * dU[c, p]).reshape(shape)
        for q in range(pn):
            if qr[q] < hot_n:
                du_sum[qr[q]] += dQ[q]
            else:
                out_t[qr[q]] = (Q[q] - lr * dQ[q]).reshape(shape)
        hi -= (lr * dv_sum).reshape((hot_n,) + shape)
        ho -= (lr * du_sum).reshape((hot_n,) + shape)
        total_loss += -(
            (np.log(_sigmoid(pos)) * mask).sum()
            + lam * (np.log(_sigmoid(-neg)) * n_real[:, None]).sum()
        ) * inv_b
    in_t[:hot_n] = hi
    out_t[:hot_n] = ho
    return in_t, out_t, total_loss


@pytest.mark.parametrize("seed,hot_rows", [(0, 32), (1, 32), (0, 64)])
def test_resident_matches_sequential_reference(seed, hot_rows):
    """hot_rows=32: mixed hot/cold traffic; hot_rows=64 (= capacity): fully
    deterministic merged semantics."""
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_resident_step

    rng = np.random.default_rng(seed)
    C, S, L = 64, 2, 128
    N, PC, PN, W = 32, 8, 4, 3
    CW = 2 * W
    in_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    out_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    centers = rng.integers(0, C, N).astype(np.int32)
    ctxs = rng.integers(0, C, (N, CW)).astype(np.int32)
    ctxs[rng.random((N, CW)) < 0.4] = -1
    ctxs[3] = -1
    pool_rows = rng.integers(0, C, (N // PC) * PN).astype(np.int32)
    lr, lam = 0.05, 0.625

    want_in, want_out, want_loss = reference_resident(
        in_t, out_t, centers, ctxs, pool_rows, lr, lam, W, PC, PN, hot_rows
    )
    got_in, got_out, got_loss = fused_sgns_resident_step(
        jnp.asarray(in_t), jnp.asarray(out_t), jnp.asarray(centers),
        jnp.asarray(ctxs), jnp.asarray(pool_rows),
        lr=lr, lam=lam, window=W, centers_per_block=PC, pool_size=PN,
        hot_rows=hot_rows, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_in), want_in, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_out), want_out, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got_loss), want_loss, rtol=1e-4)


# ----------------------------------------------------------------- dedup ---


def reference_dedup(in_t, out_t, centers, ctxs, pool_rows, lr, lam, window,
                    pc, pn, u_cap):
    """Sequential reference for the dedup kernel: per block, context rows
    are ranked by ascending row id; ranks < u_cap are 'deduped' (one read
    from the snapshot, exact merged gradient sum, one write) and the rest
    keep the grouped kernel's per-slot semantics. Reads see writes <= b-2;
    write order: centers, direct ctx (c-major), pool, unique (ascending)."""
    in_t = in_t.copy()
    out_t = out_t.copy()
    n, cw = ctxs.shape
    nblocks = n // pc
    inv_b = 1.0 / (n * (window + 1))
    d = in_t.shape[1] * in_t.shape[2]
    shape = in_t.shape[1:]
    total_loss = 0.0
    snap_in, snap_out = in_t.copy(), out_t.copy()
    for blk in range(nblocks):
        cr = centers[blk * pc : (blk + 1) * pc]
        cx = ctxs[blk * pc : (blk + 1) * pc]  # [pc, cw], -1 pads
        qr = pool_rows[blk * pn : (blk + 1) * pn]
        valid_rows = sorted({int(r) for r in cx.reshape(-1) if r >= 0})
        uniq_rows = valid_rows[:u_cap]
        rank = {r: i for i, r in enumerate(valid_rows)}
        V = snap_in[cr].reshape(pc, d).astype(np.float32)
        U = np.zeros((cw, pc, d), np.float32)
        mask = np.zeros((cw, pc), np.float32)
        for p in range(pc):
            for c in range(cw):
                if cx[p, c] >= 0:
                    U[c, p] = snap_out[cx[p, c]].reshape(d)
                    mask[c, p] = 1.0
        Q = snap_out[qr].reshape(pn, d).astype(np.float32)
        # unique rows were READ from the same <= b-2 snapshot the slots saw;
        # their merged writeback uses that base, not the refreshed snap
        uniq_base = {r: snap_out[r].reshape(d).copy() for r in uniq_rows}
        snap_in, snap_out = in_t.copy(), out_t.copy()
        pos = (U * V[None]).sum(-1)
        n_real = mask.sum(0)
        neg = V @ Q.T
        g_pos = (_sigmoid(pos) - 1.0) * inv_b * mask
        g_neg = lam * inv_b * _sigmoid(neg) * n_real[:, None]
        dV = (g_pos[:, :, None] * U).sum(0) + g_neg @ Q
        dU = g_pos[:, :, None] * V[None]
        dQ = g_neg.T @ V
        for p in range(pc):  # centers: last write wins
            in_t[cr[p]] = (V[p] - lr * dV[p]).reshape(shape)
        du_sum = {r: np.zeros(d, np.float32) for r in uniq_rows}
        for c in range(cw):  # direct ctx in c-major order, later wins
            for p in range(pc):
                r = cx[p, c]
                if r >= 0:
                    if rank[int(r)] < u_cap:
                        du_sum[int(r)] += dU[c, p]
                    else:
                        out_t[r] = (U[c, p] - lr * dU[c, p]).reshape(shape)
        for q in range(pn):
            out_t[qr[q]] = (Q[q] - lr * dQ[q]).reshape(shape)
        for r in uniq_rows:  # merged unique writes, ascending row order
            out_t[r] = (uniq_base[r] - lr * du_sum[r]).reshape(shape)
        total_loss += -(
            (np.log(_sigmoid(pos)) * mask).sum()
            + lam * (np.log(_sigmoid(-neg)) * n_real[:, None]).sum()
        ) * inv_b
    return in_t, out_t, total_loss


@pytest.mark.parametrize("seed,u_cap", [(0, 64), (1, 64), (0, 16), (0, 24)])
def test_dedup_matches_sequential_reference(seed, u_cap):
    """u_cap=64 (>= distinct rows: all deduped); u_cap=16: mixed dedup +
    direct-overflow traffic; u_cap=24: one-hot chunk (8) smaller than and
    dividing u_cap — the 384-style multi-chunk layout."""
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_dedup_step

    rng = np.random.default_rng(seed)
    C, S, L = 64, 2, 128
    N, PC, PN, W = 32, 8, 4, 3
    CW = 2 * W
    in_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    out_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    centers = rng.integers(0, C, N).astype(np.int32)
    # consecutive-ish contexts with duplicates + pads (the workload shape)
    ctxs = (centers[:, None] + rng.integers(-3, 4, (N, CW))).astype(np.int32) % C
    ctxs[rng.random((N, CW)) < 0.4] = -1
    ctxs[3] = -1
    pool_rows = rng.integers(0, C, (N // PC) * PN).astype(np.int32)
    lr, lam = 0.05, 0.625

    want_in, want_out, want_loss = reference_dedup(
        in_t, out_t, centers, ctxs, pool_rows, lr, lam, W, PC, PN, u_cap
    )
    got_in, got_out, got_loss = fused_sgns_dedup_step(
        jnp.asarray(in_t), jnp.asarray(out_t), jnp.asarray(centers),
        jnp.asarray(ctxs), jnp.asarray(pool_rows),
        lr=lr, lam=lam, window=W, centers_per_block=PC, pool_size=PN,
        u_cap=u_cap, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_in), want_in, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_out), want_out, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got_loss), want_loss, rtol=1e-4)


# ------------------------------------------------------ dedup + resident ---


def reference_dedup_resident(in_t, out_t, centers, ctxs, pool_rows, lr, lam,
                             window, pc, pn, u_cap, hot_n):
    """Sequential reference for the composed kernel: rows < hot_n live in a
    resident copy (reads current, exact merged sums from every appearance —
    centers, pool, unique ctx entries). Cold ctx rows rank AFTER hot ones
    (hot-first ascending, then cold ascending); cold in-list uniques read
    the <= b-2 snapshot and get one merged write; direct overflow (always
    cold, since u_cap >= hot_n) and cold centers/pool keep the hogwild
    last-write-wins semantics. Write order: centers, direct ctx (c-major),
    pool, cold uniques ascending."""
    in_t = in_t.copy()
    out_t = out_t.copy()
    hi, ho = in_t[:hot_n].copy(), out_t[:hot_n].copy()
    n, cw = ctxs.shape
    nblocks = n // pc
    inv_b = 1.0 / (n * (window + 1))
    d = in_t.shape[1] * in_t.shape[2]
    shape = in_t.shape[1:]
    total_loss = 0.0
    snap_in, snap_out = in_t.copy(), out_t.copy()
    for blk in range(nblocks):
        cr = centers[blk * pc : (blk + 1) * pc]
        cx = ctxs[blk * pc : (blk + 1) * pc]
        qr = pool_rows[blk * pn : (blk + 1) * pn]
        rows = sorted({int(r) for r in cx.reshape(-1) if r >= 0})
        ranked = [r for r in rows if r < hot_n] + [r for r in rows if r >= hot_n]
        uniq_rows = ranked[:u_cap]
        rank = {r: i for i, r in enumerate(ranked)}
        V = np.stack([
            (hi[r] if r < hot_n else snap_in[r]).reshape(d) for r in cr
        ]).astype(np.float32)
        U = np.zeros((cw, pc, d), np.float32)
        mask = np.zeros((cw, pc), np.float32)
        for p in range(pc):
            for c in range(cw):
                r = cx[p, c]
                if r >= 0:
                    U[c, p] = (ho[r] if r < hot_n else snap_out[r]).reshape(d)
                    mask[c, p] = 1.0
        Q = np.stack([
            (ho[r] if r < hot_n else snap_out[r]).reshape(d) for r in qr
        ]).astype(np.float32)
        uniq_base = {
            r: (ho[r] if r < hot_n else snap_out[r]).reshape(d).copy()
            for r in uniq_rows
        }
        snap_in, snap_out = in_t.copy(), out_t.copy()
        pos = (U * V[None]).sum(-1)
        n_real = mask.sum(0)
        neg = V @ Q.T
        g_pos = (_sigmoid(pos) - 1.0) * inv_b * mask
        g_neg = lam * inv_b * _sigmoid(neg) * n_real[:, None]
        dV = (g_pos[:, :, None] * U).sum(0) + g_neg @ Q
        dU = g_pos[:, :, None] * V[None]
        dQ = g_neg.T @ V
        dv_hot = np.zeros((hot_n, d), np.float32)
        du_hot = np.zeros((hot_n, d), np.float32)
        for p in range(pc):
            if cr[p] < hot_n:
                dv_hot[cr[p]] += dV[p]
            else:
                in_t[cr[p]] = (V[p] - lr * dV[p]).reshape(shape)
        du_uniq = {r: np.zeros(d, np.float32) for r in uniq_rows}
        for c in range(cw):
            for p in range(pc):
                r = cx[p, c]
                if r >= 0:
                    if rank[int(r)] < u_cap:
                        du_uniq[int(r)] += dU[c, p]
                    else:  # overflow: always cold (u_cap >= hot_n)
                        out_t[r] = (U[c, p] - lr * dU[c, p]).reshape(shape)
        for q in range(pn):
            if qr[q] < hot_n:
                du_hot[qr[q]] += dQ[q]
            else:
                out_t[qr[q]] = (Q[q] - lr * dQ[q]).reshape(shape)
        for r in uniq_rows:
            if r < hot_n:
                du_hot[r] += du_uniq[r]
            else:  # cold merged write, ascending order
                out_t[r] = (uniq_base[r] - lr * du_uniq[r]).reshape(shape)
        hi -= (lr * dv_hot).reshape((hot_n,) + shape)
        ho -= (lr * du_hot).reshape((hot_n,) + shape)
        total_loss += -(
            (np.log(_sigmoid(pos)) * mask).sum()
            + lam * (np.log(_sigmoid(-neg)) * n_real[:, None]).sum()
        ) * inv_b
    in_t[:hot_n] = hi
    out_t[:hot_n] = ho
    return in_t, out_t, total_loss


@pytest.mark.parametrize("seed,u_cap,hot_rows", [
    (0, 64, 32),   # mixed hot/cold, every distinct row in-list
    (1, 64, 32),
    (0, 16, 8),    # mixed + direct-overflow traffic
    (0, 64, 64),   # fully hot (= capacity): fully deterministic
])
def test_dedup_resident_matches_sequential_reference(seed, u_cap, hot_rows):
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_dedup_resident_step

    rng = np.random.default_rng(seed)
    C, S, L = 64, 2, 128
    N, PC, PN, W = 32, 8, 4, 3
    CW = 2 * W
    in_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    out_t = rng.normal(size=(C, S, L)).astype(np.float32) * 0.1
    centers = rng.integers(0, C, N).astype(np.int32)
    ctxs = (centers[:, None] + rng.integers(-3, 4, (N, CW))).astype(np.int32) % C
    ctxs[rng.random((N, CW)) < 0.4] = -1
    ctxs[3] = -1
    pool_rows = rng.integers(0, C, (N // PC) * PN).astype(np.int32)
    lr, lam = 0.05, 0.625

    want_in, want_out, want_loss = reference_dedup_resident(
        in_t, out_t, centers, ctxs, pool_rows, lr, lam, W, PC, PN,
        u_cap, hot_rows,
    )
    got_in, got_out, got_loss = fused_sgns_dedup_resident_step(
        jnp.asarray(in_t), jnp.asarray(out_t), jnp.asarray(centers),
        jnp.asarray(ctxs), jnp.asarray(pool_rows),
        lr=lr, lam=lam, window=W, centers_per_block=PC, pool_size=PN,
        u_cap=u_cap, hot_rows=hot_rows, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_in), want_in, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_out), want_out, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got_loss), want_loss, rtol=1e-4)


def test_composed_vmem_check_models_union():
    """The composed kernel's fail-fast must model the UNION of the dedup
    scratch and the resident head buffers — a config each single-kernel
    check would pass can overflow combined."""
    from swiftsnails_tpu.ops.fused_sgns import _check_dedup_vmem

    row = (8, 128)  # 4 KiB rows
    # ~98 MiB as plain dedup: passes...
    _check_dedup_vmem(1024, 256, 2560, 64, row, jnp.float32)
    # ...but + the resident head buffers and head-expansion one-hots
    # (~16 MiB) it must raise
    with pytest.raises(ValueError, match="composed"):
        _check_dedup_vmem(1024, 256, 2560, 64, row, jnp.float32, hot_n=1024)


def test_dedup_resident_rejects_small_u_cap():
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_dedup_resident_step

    t = jnp.zeros((64, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="u_cap"):
        fused_sgns_dedup_resident_step(
            t, t, jnp.zeros(8, jnp.int32), jnp.zeros((8, 6), jnp.int32),
            jnp.zeros(4, jnp.int32), lr=0.1, lam=0.5, window=3,
            centers_per_block=8, pool_size=4, u_cap=8, hot_rows=32,
            interpret=True,
        )


def test_dedup_trainer_trains_toy_corpus():
    """dedup: 1 end to end through the trainer (block-ordered batches),
    CPU interpret."""
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    vocab_size = 48
    counts = np.sort(rng.integers(1, 50, vocab_size))[::-1].astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    base = np.repeat(np.arange(12), 50) % vocab_size
    corpus = ((base + rng.integers(0, 2, base.size)) % vocab_size).astype(np.int32)
    cfg = Config({
        "dim": "16", "window": "2", "negatives": "2", "learning_rate": "0.1",
        "batch_size": "64", "subsample": "0", "num_iters": "20",
        "pool_size": "8", "pool_block": "16", "packed": "1", "fused": "1",
        "grouped": "1", "dedup": "1", "u_cap": "32",
        "centers_per_block": "16", "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None, corpus_ids=corpus, vocab=vocab)
    assert tr.dedup and tr.grouped
    state = tr.init_state()
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
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_dedup_trainer_native_window_producer():
    """dedup: 1 with the native C window producer (the production path):
    batches carry the window schema and train to finite losses."""
    from swiftsnails_tpu.data import native
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    if not native.available():
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(0)
    vocab_size = 48
    counts = np.sort(rng.integers(1, 50, vocab_size))[::-1].astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    corpus = rng.integers(0, vocab_size, 1500).astype(np.int32)
    cfg = Config({
        "dim": "16", "window": "2", "negatives": "2", "learning_rate": "0.1",
        "batch_size": "64", "subsample": "0", "num_iters": "4",
        "pool_size": "8", "pool_block": "16", "packed": "1", "fused": "1",
        "grouped": "1", "dedup": "1", "u_cap": "32",
        "centers_per_block": "16", "use_native": "1",
    })
    tr = Word2VecTrainer(cfg, mesh=None, corpus_ids=corpus, vocab=vocab)
    assert tr.dedup
    state = tr.init_state()
    step = jax.jit(tr.train_step, donate_argnums=(0,))
    n = 0
    for batch in tr.batches():
        assert batch["contexts"].ndim == 2
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                        jax.random.fold_in(jax.random.PRNGKey(0), n))
        assert np.isfinite(float(m["loss"]))
        n += 1
        if n >= 6:
            break
    assert n >= 4


def test_batch_stream_blocks_non_divisible_batch():
    """batch_size not divisible by block: batches must still be EXACTLY
    batch_size (train_step reshapes by it) — block shrinks to a divisor."""
    from swiftsnails_tpu.data.sampler import batch_stream_blocks

    rng = np.random.default_rng(1)
    centers = np.arange(4000, dtype=np.int32)
    ctxs = np.tile(centers[:, None], (1, 2))
    for b in batch_stream_blocks(centers, ctxs, 1000, rng, block=256):
        assert b["centers"].shape[0] == 1000
        # 250-run blocks (largest divisor of 1000 below 256)
        assert np.all(np.diff(b["centers"][:250]) == 1)


def test_batch_stream_blocks_preserves_block_order():
    from swiftsnails_tpu.data.sampler import batch_stream_blocks

    rng = np.random.default_rng(0)
    n, cw, block = 64, 4, 8
    centers = np.arange(n, dtype=np.int32)
    ctxs = np.tile(centers[:, None], (1, cw))
    seen = []
    for b in batch_stream_blocks(centers, ctxs, 16, rng, block=block):
        c = b["centers"]
        assert len(c) == 16
        # each block of 8 is a consecutive run
        for lo in range(0, 16, block):
            blk = c[lo : lo + block]
            assert np.all(np.diff(blk) == 1), blk
            seen.append(blk[0])
    assert len(set(seen)) == len(seen)  # blocks are distinct


def test_resident_trainer_trains_toy_corpus():
    """resident: 1 end to end through the trainer (mixed hot/cold rows:
    hot_rows below vocab size), CPU interpret."""
    from swiftsnails_tpu.data.vocab import Vocab
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    vocab_size = 48
    counts = np.sort(rng.integers(1, 50, vocab_size))[::-1].astype(np.int64)
    vocab = Vocab([f"w{i}" for i in range(vocab_size)], counts)
    base = np.repeat(np.arange(12), 50) % vocab_size
    corpus = ((base + rng.integers(0, 2, base.size)) % vocab_size).astype(np.int32)
    cfg = Config({
        "dim": "16", "window": "2", "negatives": "2", "learning_rate": "0.1",
        "batch_size": "64", "subsample": "0", "num_iters": "20",
        "pool_size": "8", "pool_block": "16", "packed": "1", "fused": "1",
        "grouped": "1", "resident": "1", "hot_rows": "24",
        "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None, corpus_ids=corpus, vocab=vocab)
    assert tr.resident and tr.grouped
    state = tr.init_state()
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
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_resident_trainer_hash_keys(tmp_path):
    """resident: 1 + hash_keys: 1 — under hashing the hot set is arbitrary
    rows < hot_n (not the frequency head); the kernel must stay correct.
    Mirrors the grouped hash_keys test, end to end on CPU interpret."""
    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(48)]
    path = tmp_path / "c.txt"
    with open(path, "w") as f:
        for _ in range(400):
            f.write(" ".join(words[i] for i in rng.integers(0, 48, 12)) + "\n")
    cfg = Config({
        "data": str(path), "dim": "8", "window": "2", "negatives": "2",
        "learning_rate": "0.1", "batch_size": "64", "subsample": "0",
        "num_iters": "1", "min_count": "1", "packed": "1",
        "neg_mode": "pool", "pool_size": "8", "pool_block": "32",
        "fused": "1", "grouped": "1", "resident": "1", "hot_rows": "32",
        "hash_keys": "1", "capacity": "128", "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None)
    assert tr.resident and tr.hash_keys
    state = tr.init_state()
    step = jax.jit(tr.train_step, donate_argnums=(0,))
    n = 0
    losses = []
    for batch in tr.batches():
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                        jax.random.fold_in(jax.random.PRNGKey(0), n))
        losses.append(float(m["loss"]))
        n += 1
        if n >= 8:
            break
    assert n >= 4 and all(np.isfinite(l) for l in losses)


def test_effective_hot_rows_rounding():
    from swiftsnails_tpu.ops.fused_sgns import effective_hot_rows

    assert effective_hot_rows(1024, 1 << 20) == (1024, 256)
    assert effective_hot_rows(300, 1 << 20) == (256, 256)  # rounds to 256
    assert effective_hot_rows(100, 1 << 20) == (96, 96)  # multiple of 8
    assert effective_hot_rows(1024, 24) == (24, 24)  # capacity clip
    assert effective_hot_rows(7, 1 << 20) == (0, 0)  # too small
    assert effective_hot_rows(4096, 1 << 20) == (4096, 256)


def test_resident_rejects_mismatched_tables():
    from swiftsnails_tpu.ops.fused_sgns import fused_sgns_resident_step

    in_t = jnp.zeros((64, 2, 128), jnp.float32)
    out_t = jnp.zeros((64, 1, 128), jnp.float32)
    centers = jnp.zeros((8,), jnp.int32)
    ctxs = jnp.zeros((8, 2), jnp.int32)
    pool = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="row shape"):
        fused_sgns_resident_step(
            in_t, out_t, centers, ctxs, pool, lr=0.1, lam=0.5, window=1,
            centers_per_block=8, pool_size=4, hot_rows=32, interpret=True,
        )


def test_grouped_trainer_hash_keys_and_stream(tmp_path):
    """Grouped path with hash_keys: 1 (pads must stay -1 through hashing)
    and stream: 1 ingestion feeding window batches, end to end on CPU
    interpret."""
    import os

    from swiftsnails_tpu.models.word2vec import Word2VecTrainer
    from swiftsnails_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(48)]
    path = tmp_path / "c.txt"
    with open(path, "w") as f:
        for _ in range(400):
            f.write(" ".join(words[i] for i in rng.integers(0, 48, 12)) + "\n")
    cfg = Config({
        "data": str(path), "dim": "8", "window": "2", "negatives": "2",
        "learning_rate": "0.1", "batch_size": "64", "subsample": "0",
        "num_iters": "1", "min_count": "1", "packed": "1",
        "neg_mode": "pool", "pool_size": "8", "pool_block": "32",
        "fused": "1", "grouped": "1", "hash_keys": "1", "capacity": "128",
        "stream": "1", "chunk_tokens": "1500", "use_native": "0",
    })
    tr = Word2VecTrainer(cfg, mesh=None)
    assert tr.grouped and tr.hash_keys and tr.stream
    state = tr.init_state()
    step = jax.jit(tr.train_step, donate_argnums=(0,))
    n = 0
    for batch in tr.batches():
        assert batch["contexts"].ndim == 2  # window schema
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                        jax.random.fold_in(jax.random.PRNGKey(0), n))
        n += 1
        if n >= 4:
            break
    assert n >= 2 and np.isfinite(float(m["loss"]))
