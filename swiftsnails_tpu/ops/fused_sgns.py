"""Fused SGNS substep: gather -> loss/grads -> SGD writeback, one kernel.

The maximal fusion of the word2vec fast path (see models/word2vec.py): for
each block of ``P`` pairs sharing ``PN`` pooled negatives, the kernel DMAs
the center/context/pool rows into VMEM, computes the pooled-negative SGNS
gradients on the MXU, applies the SGD update in VMEM, and DMAs the updated
rows back — 2 row DMAs per touched row and zero HBM activation traffic,
versus gather + sort-merge + read-modify-write (3+ DMAs and two argsorts)
on the unfused path.

**Semantics: hogwild.** Rows duplicated within a block, colliding between
pool and context slots, or touched by two in-flight blocks race
(last-write-wins / stale-read). This is precisely the reference's
asynchronous-SGD behavior — M workers racing pushes on hot keys with no
cross-worker ordering (``SwiftWorker``'s async pull/push; the original
word2vec C implementation is hogwild across threads, and the reference's
lock striping orders single-key writes but not read-modify-write cycles).
The unfused path (``fused: 0``) keeps the deterministic merged semantics.

In interpret mode the grid runs sequentially, so the result is exactly the
"apply blocks in order, within a block V then U then pool writes, later
slot wins" reference that the unit test implements.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.utils.profiling import phase_scope


_WAIT_CHUNK = 64


def _wait_rows(row_ref, chunk_ref, sem, count):
    """Retire ``count`` single-row DMA completions in ~count/chunk scalar ops.

    The wait side of the DMA loops used to be one scalar op PER COPY —
    half of the ~60ns/op scalar floor every kernel family hits
    (docs/ARCHITECTURE.md round-5 ablation). A wait's decrement is
    derived from its descriptor size, and completions increment the
    shared semaphore in row-additive 32-byte granules (measured:
    tools/sem_probe.py — 15.8x on the wait loop of a bench-shaped DMA
    pipeline), so ONE wait on a ``ch``-row descriptor retires ``ch``
    equal-size single-row copies at once. ``row_ref``/``chunk_ref`` must
    match the row shape+dtype of every copy sharing ``sem`` (the step
    wrappers enforce equal table dtypes).
    """
    ch = chunk_ref.shape[0]  # the chunk wait retires exactly this many rows
    nch = count // ch

    def wch(_, c):
        pltpu.make_async_copy(chunk_ref, chunk_ref, sem).wait()
        return c

    jax.lax.fori_loop(0, nch, wch, 0)

    def w(_, c):
        pltpu.make_async_copy(row_ref, row_ref, sem).wait()
        return c

    jax.lax.fori_loop(0, count - nch * ch, w, 0)


# DMA starts per iteration of the issue loops, each kernel alone on a v5e at
# its cell's shapes. The fused scatter's 55,893 live rows (rowdma.py): 2.99 ->
# 2.25 ms (16: 2.17; PERF.md, PR 27). The gather's 212,992 slots, waits
# chunked (rowdma.py): 5.20 -> 3.37 ms (16: 3.22, 32: 3.15, 64: 3.11; PERF.md,
# PR 29). The grouped SGNS substep's 104,800 starts (_grouped_kernel below):
# 3.00 -> 1.87 ms, 28.6 -> 17.9 ns a start (16: 1.81; the context loop alone
# unrolled: 2.07; PERF.md, PR 32): one constant for all three
_START_UNROLL = 8


def _start_rows(n, start_one):
    """``start_one(j)`` for ``j`` in ``[0, n)``, ``_START_UNROLL`` to a loop
    iteration and the remainder one by one: the scalar core pays per
    iteration, not per DMA. ``n`` is a Python int or a traced scalar."""

    def group(k, _):
        for u in range(_START_UNROLL):
            start_one(k * _START_UNROLL + u)
        return 0

    whole = n // _START_UNROLL
    jax.lax.fori_loop(0, whole, group, 0)
    jax.lax.fori_loop(
        whole * _START_UNROLL, n, lambda j, _: (start_one(j), 0)[1], 0)


def _kernel(in_rows_ref, pos_rows_ref, pool_rows_ref, lr_ref,
            in_t_in, out_t_in, in_table, out_table, loss_ref,
            v_buf, u_buf, p_buf, read_sems, write_sems,
            *, lam, inv_b, pairs, pool):
    del in_t_in, out_t_in
    # lr rides scalar prefetch (SMEM) so a decay schedule never recompiles
    lr = lr_ref[0]
    P, PN = pairs, pool
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    def dmas(b, slot, table_dir):
        """All row DMAs of block b. table_dir: 'read' or 'write'."""
        sems = read_sems if table_dir == "read" else write_sems

        def mk(buf, j, table, row):
            pair = (table.at[row], buf.at[slot, j])
            src, dst = pair if table_dir == "read" else pair[::-1]
            return pltpu.make_async_copy(src, dst, sems.at[slot])

        def v_dma(j, _):
            mk(v_buf, j, in_table, in_rows_ref[b * P + j]).start()
            return 0

        def u_dma(j, _):
            mk(u_buf, j, out_table, pos_rows_ref[b * P + j]).start()
            return 0

        def p_dma(q, _):
            mk(p_buf, q, out_table, pool_rows_ref[b * PN + q]).start()
            return 0

        jax.lax.fori_loop(0, P, v_dma, 0)
        jax.lax.fori_loop(0, P, u_dma, 0)
        jax.lax.fori_loop(0, PN, p_dma, 0)

    def wait_all(b, slot, table_dir):
        sems = read_sems if table_dir == "read" else write_sems
        # equal-size copies share the semaphore; the (fixed, in-bounds)
        # refs only supply the wait size
        ch = min(_WAIT_CHUNK, P)
        _wait_rows(v_buf.at[slot, 0], v_buf.at[slot, :ch],
                   sems.at[slot], 2 * P + PN)

    @pl.when(i == 0)
    def _():
        dmas(0, 0, "read")

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait_all(i - 1, slot_next, "write")

        dmas(i + 1, slot_next, "read")

    slot = i % 2
    wait_all(i, slot, "read")

    # ---- compute (f32, MXU for the pair x pool logits) -------------------
    vv = v_buf[slot].astype(jnp.float32).reshape(P, -1)
    uv = u_buf[slot].astype(jnp.float32).reshape(P, -1)
    pv = p_buf[slot].astype(jnp.float32).reshape(PN, -1)

    # keepdims throughout: rank-1 [P] intermediates hit a Mosaic relayout
    # limitation (implicit-dim vector<1x512xf32> -> replicated-lane form)
    pos = jnp.sum(vv * uv, axis=1, keepdims=True)  # [P, 1]
    neg = jax.lax.dot_general(
        vv, pv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [P, PN]

    g_pos = (jax.nn.sigmoid(pos) - 1.0) * inv_b  # [P, 1]
    g_neg = (lam * inv_b) * jax.nn.sigmoid(neg)  # [P, PN]

    dv = g_pos * uv + jax.lax.dot_general(
        g_neg, pv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    du = g_pos * vv
    dp = jax.lax.dot_general(
        g_neg, vv, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [PN, D]

    shape_v = v_buf[slot].shape
    v_buf[slot] = (vv - lr * dv).reshape(shape_v).astype(v_buf.dtype)
    u_buf[slot] = (uv - lr * du).reshape(shape_v).astype(u_buf.dtype)
    p_buf[slot] = (pv - lr * dp).reshape(p_buf[slot].shape).astype(p_buf.dtype)

    loss = -(jax.nn.log_sigmoid(pos).sum() + lam * jax.nn.log_sigmoid(-neg).sum())
    loss_ref[...] = jnp.full(loss_ref.shape, loss * inv_b, dtype=jnp.float32)

    # ---- writeback -------------------------------------------------------
    dmas(i, slot, "write")

    @pl.when(i == nblocks - 1)
    def _():
        wait_all(i, slot, "write")

        @pl.when(nblocks >= 2)
        def _():
            wait_all(i - 1, (i - 1) % 2, "write")


_ROW_MASK = (1 << 30) - 1  # c_rows: row id | is-last-occurrence << 30
_SLOT_MASK = (1 << 20) - 1  # ctx_slot: buffer slot | is-last-occurrence << 20


def _last_occurrence(rows: jax.Array, valid: jax.Array) -> jax.Array:
    """Per block-row: True where element k is the LAST valid occurrence of
    its value (``rows`` [NB, K] i32, ``valid`` [NB, K] bool)."""
    nb, k = rows.shape
    big = jnp.int32(2**31 - 1)
    keyed = jnp.where(valid, rows, big)
    # stable sort groups equal rows in ascending original index, so the last
    # element of each run is the last occurrence
    order = jnp.argsort(keyed, axis=1, stable=True)
    srow = jnp.take_along_axis(keyed, order, axis=1)
    last_sorted = jnp.concatenate(
        [srow[:, :-1] != srow[:, 1:], jnp.ones((nb, 1), bool)], axis=1
    ) & (srow != big)
    out = jnp.zeros((nb, k), bool)
    return out.at[jnp.arange(nb)[:, None], order].set(last_sorted)


def _grouped_kernel(c_rows_ref, ctx_rows_ref, ctx_slot_ref, nctx_ref,
                    nwc_ref, nwu_ref, pool_rows_ref, lr_ref, mask_in, in_t_in,
                    out_t_in, in_table, out_table, loss_ref,
                    v_buf, u_buf, p_buf, read_sems, write_sems,
                    *, lam, inv_b, pc, cw, pool):
    """Center-major fused SGNS substep (see fused_sgns_grouped_step).

    The flat kernel issues ~4.25 row copies per pair; per-copy issue cost is
    the measured bound (throughput is flat in row size AND row locality).
    Grouping by center loads each center row once for its whole window and
    skips padded context slots entirely (host-compacted copy list, dynamic
    wait counts), cutting copies/pair to ~2.5. Writeback skips every
    non-LAST duplicate-row slot (flag bits packed by the wrapper): under
    last-write-wins those writes can never survive, so the final table is
    bit-identical with ~dup-fraction fewer write copies.
    """
    del in_t_in, out_t_in
    lr = lr_ref[0]
    PC, CW, PN = pc, cw, pool
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)
    cap = PC * CW

    def dmas(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems

        def mk(buf_at, table, row):
            pair = (table.at[row], buf_at)
            src, dst = pair if read else pair[::-1]
            return pltpu.make_async_copy(src, dst, sems.at[slot])

        def v_dma(p):
            v = c_rows_ref[b * PC + p]
            if read:
                mk(v_buf.at[slot, p], in_table, v & _ROW_MASK).start()
            else:
                @pl.when((v >> 30) != 0)
                def _():
                    mk(v_buf.at[slot, p], in_table, v & _ROW_MASK).start()

        def u_dma(k):
            # two-segment copy list (_cold_compact): the first nwu entries
            # are exactly the flagged last-occurrence writes, so the write
            # loop is bounded by nwu and issues UNCONDITIONALLY — no
            # ~60ns/slot branch over mostly-skipped entries
            s = ctx_slot_ref[b * cap + k]
            row = ctx_rows_ref[b * cap + k]
            mk(u_buf.at[slot, s & _SLOT_MASK], out_table, row).start()

        def p_dma(q):
            mk(p_buf.at[slot, q], out_table, pool_rows_ref[b * PN + q]).start()

        _start_rows(PC, v_dma)
        # read: all real slots; write: flagged prefix only
        _start_rows(nctx_ref[b] if read else nwu_ref[b], u_dma)
        _start_rows(PN, p_dma)

    def wait_all(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems
        count = (
            PC + PN + nctx_ref[b]
            if read
            else nwc_ref[b] + PN + nwu_ref[b]
        )
        wc = min(_WAIT_CHUNK, cap)
        _wait_rows(v_buf.at[slot, 0], u_buf.at[slot, :wc],
                   sems.at[slot], count)

    @pl.when(i == 0)
    def _():
        dmas(0, 0, "read")

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait_all(i - 1, slot_next, "write")

        dmas(i + 1, slot_next, "read")

    slot = i % 2
    wait_all(i, slot, "read")

    # ---- compute ([CW, PC] orientation: PC=lanes) ------------------------
    vv = v_buf[slot].astype(jnp.float32).reshape(PC, -1)  # [PC, D]
    uu = u_buf[slot].astype(jnp.float32).reshape(CW, PC, -1)  # [CW, PC, D]
    pv = p_buf[slot].astype(jnp.float32).reshape(PN, -1)  # [PN, D]
    mask = mask_in[0]  # [CW, PC], 1.0 on real context slots
    # pad slots were never DMA'd: whatever is in that VMEM (stale rows,
    # poison) must not reach the arithmetic — 0*NaN would still be NaN
    uu = jnp.where(mask[:, :, None] > 0, uu, 0.0)

    pos = jnp.sum(uu * vv[None, :, :], axis=-1)  # [CW, PC]
    n_real = jnp.sum(mask, axis=0, keepdims=True)  # [1, PC]
    neg = jax.lax.dot_general(
        vv, pv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [PC, PN]

    g_pos = (jax.nn.sigmoid(pos) - 1.0) * inv_b * mask  # [CW, PC]
    # the pool is shared center-wide: each real pair contributes the same
    # negative term, so the per-center weight is its real-context count
    g_neg = (lam * inv_b) * jax.nn.sigmoid(neg) * n_real.reshape(PC, 1)

    dv = jnp.sum(g_pos[:, :, None] * uu, axis=0) + jax.lax.dot_general(
        g_neg, pv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [PC, D]
    du = g_pos[:, :, None] * vv[None, :, :]  # [CW, PC, D]
    dp = jax.lax.dot_general(
        g_neg, vv, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [PN, D]

    v_shape = v_buf[slot].shape
    u_shape = u_buf[slot].shape
    v_buf[slot] = (vv - lr * dv).reshape(v_shape).astype(v_buf.dtype)
    u_buf[slot] = (
        (uu - lr * du).reshape(CW * PC, -1).reshape(u_shape).astype(u_buf.dtype)
    )
    p_buf[slot] = (pv - lr * dp).reshape(p_buf[slot].shape).astype(p_buf.dtype)

    loss = -(
        jnp.sum(jax.nn.log_sigmoid(pos) * mask)
        + lam * jnp.sum(jax.nn.log_sigmoid(-neg) * n_real.reshape(PC, 1))
    )
    loss_ref[...] = jnp.full(loss_ref.shape, loss * inv_b, dtype=jnp.float32)

    dmas(i, slot, "write")

    @pl.when(i == nblocks - 1)
    def _():
        wait_all(i, slot, "write")

        @pl.when(nblocks >= 2)
        def _():
            wait_all(i - 1, (i - 1) % 2, "write")


@functools.partial(
    jax.jit,
    static_argnames=("lam", "centers_per_block", "pool_size", "window",
                     "interpret"),
    donate_argnums=(0, 1),
)
def fused_sgns_grouped_step(
    in_table: jax.Array,
    out_table: jax.Array,
    centers: jax.Array,  # [N] row ids
    ctxs: jax.Array,  # [N, CW] row ids, -1 = pad
    pool_rows: jax.Array,  # [N // centers_per_block * pool_size]
    lr: float,
    lam: float,
    window: int,
    centers_per_block: int = 128,
    pool_size: int = 64,
    interpret: bool = False,
):
    """Center-major fused substep. Returns (in_table, out_table, loss).

    Loss/grads are normalized by the EXPECTED pair count ``N * (window+1)``
    (dynamic window b~U(1,window) gives 2*E[b] = window+1 pairs per center),
    so the per-pair update magnitude matches the flat kernel's 1/B. The
    in-kernel compaction (sort pads last per block) happens here in XLA.
    """
    n, cw = ctxs.shape
    pc, pn = centers_per_block, pool_size
    if n % pc:
        raise ValueError(f"centers {n} not a multiple of centers_per_block {pc}")
    nblocks = n // pc
    if pool_rows.shape[0] != nblocks * pn:
        raise ValueError(f"pool_rows {pool_rows.shape[0]} != {nblocks * pn}")
    cap = pc * cw
    inv_b = 1.0 / (n * (window + 1))

    if cap > _SLOT_MASK:
        raise ValueError(f"centers_per_block*2*window {cap} exceeds slot bits")
    if in_table.shape[0] > _ROW_MASK or out_table.shape[0] > _ROW_MASK:
        raise ValueError("table capacity exceeds 2^30 (row-id flag bit)")
    if in_table.shape[1:] != out_table.shape[1:] or in_table.dtype != out_table.dtype:
        raise ValueError("in/out tables must share row shape and dtype")

    with phase_scope("prep"):  # the copy lists, in XLA
        # [CW, PC] orientation throughout (PC = lanes): flat slot k = c*PC + p
        flat = (
            ctxs.reshape(nblocks, pc, cw).transpose(0, 2, 1).reshape(nblocks, cap)
        ).astype(jnp.int32)
        valid = flat >= 0
        # compact real context slots to the front of each block's copy list,
        # with last-occurrence write flags (under last-write-wins only the
        # LAST write of a duplicated row within a block survives, so all
        # others are skipped in the writeback — bit-identical result, fewer
        # copies); one shared single-sort pass does both
        ctx_rows, ctx_slot, nctx, nwrite_u = _cold_compact(flat, valid)
        mask = valid.reshape(nblocks, cw, pc).astype(jnp.float32)

        c_blocks = centers.astype(jnp.int32).reshape(nblocks, pc)
        c_last = _last_occurrence(c_blocks, jnp.ones_like(c_blocks, bool))
        nwrite_c = c_last.sum(axis=1).astype(jnp.int32)
        c_packed = (c_blocks | jnp.where(c_last, 1 << 30, 0)).reshape(-1)

    kern = functools.partial(
        _grouped_kernel, lam=lam, inv_b=inv_b, pc=pc, cw=cw, pool=pn
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, cw, pc), lambda i, *_: (i, 0, 0)),  # mask
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pc) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((2, cap) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, pn) + out_table.shape[1:], out_table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # the inner scope keeps the kernel's instruction named after this function
    # on the device timeline (XLA names a custom call by its innermost scope)
    with phase_scope("fused"), jax.named_scope("fused_sgns_grouped_step"):
        new_in, new_out, loss_parts = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct(in_table.shape, in_table.dtype),
                jax.ShapeDtypeStruct(out_table.shape, out_table.dtype),
                jax.ShapeDtypeStruct((nblocks, 8, 128), jnp.float32),
            ),
            input_output_aliases={9: 0, 10: 1},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=interpret,
        )(
            c_packed,
            ctx_rows.reshape(-1),
            ctx_slot.reshape(-1),
            nctx,
            nwrite_c,
            nwrite_u,
            pool_rows.astype(jnp.int32),
            jnp.asarray(lr, jnp.float32).reshape(1),
            mask,
            in_table,
            out_table,
        )
        loss = loss_parts[:, 0, 0].sum()
    return new_in, new_out, loss


def _resident_kernel(ccold_rows_ref, ccold_slot_ref, ncc_ref, nwc_ref,
                     ctx_rows_ref, ctx_slot_ref, nctx_ref, nwu_ref,
                     pcold_rows_ref, pcold_slot_ref, npc_ref, nwp_ref, lr_ref,
                     hot_c_in, hot_u_in, hot_p_in, cold_u_in, mask_in,
                     in_t_in, out_t_in,
                     in_table, out_table, loss_ref,
                     v_buf, u_buf, p_buf, hot_in, hot_out,
                     read_sems, write_sems, bulk_sem,
                     *, lam, inv_b, pc, cw, pool, hot_n, ch):
    """Grouped kernel + VMEM-resident head rows (see fused_sgns_resident_step).

    The grouped kernel's throughput is bound by per-row DMA issue rate, and
    under a zipf vocabulary the head rows soak up most of the row traffic
    (ids are frequency-ranked, so "row < hot_n" = the head). This kernel
    keeps the first ``hot_n`` rows of BOTH tables resident in VMEM for the
    whole grid: one bulk DMA loads them at block 0 and one writes them back
    at the last block; per block, hot-row reads are one-hot matmuls out of
    the resident buffers (measured ~8 us per [cap x 1024] @ [1024, D]
    expansion — far below the ~50 ns/copy issue cost they replace) and
    hot-row updates are exact merged accumulations (H^T @ per-slot grads)
    into the resident buffers. Only tail ("cold") rows still move per-row.

    Semantics: cold rows keep the grouped kernel's hogwild behavior; hot
    rows become DETERMINISTIC sequential merged updates (duplicate hot slots
    within a block sum their gradients — the reference's merge_push_value
    semantics, sparsetable.h:176-179 — and block b reads every hot write of
    blocks < b). Strictly closer to the faithful path than the hogwild
    last-write-wins it replaces.
    """
    del in_t_in, out_t_in
    lr = lr_ref[0]
    PC, CW, PN, HOT, CH = pc, cw, pool, hot_n, ch
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)
    cap = PC * CW
    s_t, lanes = in_table.shape[1], in_table.shape[2]
    dp = s_t * lanes
    f32 = jnp.float32

    def bulk_start(table_dir):
        for tbl, buf in ((in_table, hot_in), (out_table, hot_out)):
            src, dst = (tbl.at[pl.ds(0, HOT)], buf)
            if table_dir == "write":
                src, dst = dst, src
            pltpu.make_async_copy(src, dst, bulk_sem).start()

    def bulk_wait():
        for _ in range(2):  # equal sizes: each wait retires one copy
            pltpu.make_async_copy(hot_in, hot_in, bulk_sem).wait()

    def dmas(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems

        def mk(buf_at, table, row):
            pair = (table.at[row], buf_at)
            src, dst = pair if read else pair[::-1]
            return pltpu.make_async_copy(src, dst, sems.at[slot])

        def cold_dma(rows_ref, slot_ref, buf, table, stride):
            # two-segment lists (_cold_compact): write loops are bounded by
            # the flagged-write count and issue unconditionally
            def go(k, _):
                row = rows_ref[b * stride + k]
                sl = slot_ref[b * stride + k]
                mk(buf.at[slot, sl & _SLOT_MASK], table, row).start()
                return 0
            return go

        jax.lax.fori_loop(
            0, ncc_ref[b] if read else nwc_ref[b],
            cold_dma(ccold_rows_ref, ccold_slot_ref, v_buf, in_table, PC), 0)
        jax.lax.fori_loop(
            0, nctx_ref[b] if read else nwu_ref[b],
            cold_dma(ctx_rows_ref, ctx_slot_ref, u_buf, out_table, cap), 0)
        jax.lax.fori_loop(
            0, npc_ref[b] if read else nwp_ref[b],
            cold_dma(pcold_rows_ref, pcold_slot_ref, p_buf, out_table, PN), 0)

    def wait_all(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems
        count = (
            ncc_ref[b] + nctx_ref[b] + npc_ref[b]
            if read
            else nwc_ref[b] + nwu_ref[b] + nwp_ref[b]
        )
        wc = min(_WAIT_CHUNK, cap)
        _wait_rows(v_buf.at[slot, 0], u_buf.at[slot, :wc],
                   sems.at[slot], count)

    @pl.when(i == 0)
    def _():
        bulk_start("read")
        dmas(0, 0, "read")
        bulk_wait()

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait_all(i - 1, slot_next, "write")

        dmas(i + 1, slot_next, "read")

    slot = i % 2
    wait_all(i, slot, "read")

    # ---- hot-row expansion (pass 1): resident rows -> slot-ordered values
    hot_u_idx = hot_u_in[0, 0]  # [cap] i32, sentinel HOT on pads/cold
    hot_c_idx = hot_c_in[0, 0]  # [PC]
    hot_p_idx = hot_p_in[0, 0]  # [PN]
    mask = mask_in[0]  # [CW, PC] f32, 1.0 on real (hot or cold) slots

    def expand(idx, buf, n_rows):
        """one_hot(idx) @ buf[0:HOT] -> [n_rows, dp]; zeros where idx==HOT."""
        acc = jnp.zeros((n_rows, dp), f32)
        for c0 in range(0, HOT, CH):
            j = jax.lax.broadcasted_iota(jnp.int32, (n_rows, CH), 1) + c0
            h = (j == idx[:, None]).astype(f32)
            acc = acc + jax.lax.dot_general(
                h, buf[pl.ds(c0, CH)].reshape(CH, dp).astype(f32),
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
        return acc

    uu_hot = expand(hot_u_idx, hot_out, cap)
    vc_hot = expand(hot_c_idx, hot_in, PC)
    pv_hot = expand(hot_p_idx, hot_out, PN)

    # minor-dim insert must happen on the 32-bit side (Mosaic can't reshape
    # i1 vectors), so compare after the [:, None]; the cold-slot mask comes
    # pre-flattened from the host (reshaping mask [CW, PC] -> [cap, 1]
    # in-kernel is an unsupported shape cast)
    is_hot_u = hot_u_idx[:, None] < HOT  # [cap, 1]
    is_hot_c = hot_c_idx[:, None] < HOT
    is_hot_p = hot_p_idx[:, None] < HOT
    cold_real = cold_u_in[0, 0][:, None] > 0  # [cap, 1]

    # merged slot values: hot from expansion, cold from DMA, pads zero
    # (cold-slot VMEM at hot/pad positions was never DMA'd — poison must not
    # reach arithmetic, so where() everywhere)
    vv = jnp.where(is_hot_c, vc_hot, v_buf[slot].astype(f32).reshape(PC, dp))
    uu = jnp.where(
        is_hot_u, uu_hot,
        jnp.where(cold_real, u_buf[slot].astype(f32).reshape(cap, dp), 0.0))
    pv = jnp.where(is_hot_p, pv_hot, p_buf[slot].astype(f32).reshape(PN, dp))

    # ---- compute (identical math to the grouped kernel) ------------------
    uu3 = uu.reshape(CW, PC, dp)
    pos = jnp.sum(uu3 * vv[None, :, :], axis=-1)  # [CW, PC]
    n_real = jnp.sum(mask, axis=0, keepdims=True)  # [1, PC]
    neg = jax.lax.dot_general(
        vv, pv, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # [PC, PN]

    g_pos = (jax.nn.sigmoid(pos) - 1.0) * inv_b * mask  # [CW, PC]
    g_neg = (lam * inv_b) * jax.nn.sigmoid(neg) * n_real.reshape(PC, 1)

    dv = jnp.sum(g_pos[:, :, None] * uu3, axis=0) + jax.lax.dot_general(
        g_neg, pv, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # [PC, dp]
    du_flat = (g_pos[:, :, None] * vv[None, :, :]).reshape(cap, dp)
    dq = jax.lax.dot_general(
        g_neg, vv, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # [PN, dp]

    v_shape = v_buf[slot].shape
    v_buf[slot] = (vv - lr * dv).reshape(v_shape).astype(v_buf.dtype)
    u_buf[slot] = (
        (uu - lr * du_flat).reshape(u_buf[slot].shape).astype(u_buf.dtype)
    )
    p_buf[slot] = (pv - lr * dq).reshape(p_buf[slot].shape).astype(p_buf.dtype)

    # ---- hot-row merged updates (pass 2): H^T @ grads into residents -----
    for c0 in range(0, HOT, CH):
        def acc_t(idx, grads, n_rows):
            jt = jax.lax.broadcasted_iota(jnp.int32, (CH, n_rows), 0) + c0
            ht = (jt == idx[None, :]).astype(f32)
            return jax.lax.dot_general(
                ht, grads, (((1,), (0,)), ((), ())), preferred_element_type=f32)

        d_out = acc_t(hot_u_idx, du_flat, cap) + acc_t(hot_p_idx, dq, PN)
        hot_out[pl.ds(c0, CH)] = (
            hot_out[pl.ds(c0, CH)].reshape(CH, dp).astype(f32) - lr * d_out
        ).reshape(CH, s_t, lanes).astype(hot_out.dtype)
        d_in = acc_t(hot_c_idx, dv, PC)
        hot_in[pl.ds(c0, CH)] = (
            hot_in[pl.ds(c0, CH)].reshape(CH, dp).astype(f32) - lr * d_in
        ).reshape(CH, s_t, lanes).astype(hot_in.dtype)

    loss = -(
        jnp.sum(jax.nn.log_sigmoid(pos) * mask)
        + lam * jnp.sum(jax.nn.log_sigmoid(-neg) * n_real.reshape(PC, 1))
    )
    loss_ref[...] = jnp.full(loss_ref.shape, loss * inv_b, dtype=jnp.float32)

    dmas(i, slot, "write")

    @pl.when(i == nblocks - 1)
    def _():
        wait_all(i, slot, "write")

        @pl.when(nblocks >= 2)
        def _():
            wait_all(i - 1, (i - 1) % 2, "write")

        bulk_start("write")
        bulk_wait()


def effective_hot_rows(hot_rows: int, *capacities: int) -> tuple[int, int]:
    """(hot_n, ch): the resident row count the kernel will actually use.

    ``hot_rows`` is clipped to the table capacities and rounded down to the
    one-hot chunk size (256, or a multiple of 8 below 256). Exposed so
    callers (the trainer, logs) can see the real value instead of a silent
    round-down; returns ``(0, 0)`` when no resident rows are possible.
    """
    hot_n = min(hot_rows, *capacities)
    if hot_n >= 256:
        hot_n -= hot_n % 256
        ch = 256
    else:
        hot_n -= hot_n % 8
        ch = hot_n
    return (hot_n, ch) if hot_n > 0 else (0, 0)


# Mosaic scoped-VMEM limit for the resident kernel (see CompilerParams
# below); the budget check keeps a margin for Mosaic's own temporaries.
_RESIDENT_VMEM_BYTES = 100 * 1024 * 1024


def _check_resident_vmem(hot_n, pc, cap, pn, row_shape, dtype):
    """Fail fast with a clear message instead of a Mosaic stack OOM."""
    import math

    row_bytes = math.prod(row_shape) * jnp.dtype(dtype).itemsize
    dp_f32 = math.prod(row_shape) * 4
    scratch = (2 * (pc + cap + pn) + 2 * hot_n) * row_bytes
    # f32 working set: merged slot values + grads for cap/pc/pn slots, twice
    # over for where-selects and update temporaries
    working = 4 * dp_f32 * (cap + pc + pn)
    # one-hot expand temporaries: the [n_rows, ch] one-hot + iota broadcast
    # intermediates of the head-expansion loops (previously uncounted — a
    # large hot_n could pass the check and still hit an opaque Mosaic OOM)
    ch = 256 if hot_n >= 256 else hot_n
    onehot = 4 * 2 * (cap + pc + pn) * ch
    need = scratch + working + onehot
    if need > _RESIDENT_VMEM_BYTES:
        raise ValueError(
            f"resident kernel VMEM estimate {need / 2**20:.1f} MiB exceeds "
            f"the {_RESIDENT_VMEM_BYTES / 2**20:.0f} MiB budget "
            f"(hot_rows={hot_n}, centers_per_block={pc}, ctx slots={cap}, "
            f"pool={pn}); lower hot_rows or centers_per_block"
        )


def _check_dedup_vmem(u_cap, pc, cap, pn, row_shape, dtype, hot_n=0):
    """Dedup-shaped twin of :func:`_check_resident_vmem`: fail fast with a
    clear message instead of an opaque Mosaic OOM when ``u_cap`` /
    ``centers_per_block`` push the scratch + f32 working set past the
    scoped-VMEM limit. ``hot_n > 0`` models the COMPOSED kernel, whose
    scratch is the UNION of the dedup buffers and both resident head
    buffers — two independent single-kernel checks would each pass a
    config whose combined footprint overflows."""
    import math

    row_bytes = math.prod(row_shape) * jnp.dtype(dtype).itemsize
    dp_f32 = math.prod(row_shape) * 4
    # double-buffered v/u/p/u_uniq scratch + the resident head buffers
    scratch = 2 * (pc + cap + pn + u_cap) * row_bytes + 2 * hot_n * row_bytes
    # f32 working set: merged slot values + grads (cap/pc/pn), twice over
    # for where-selects and update temporaries, plus the one-hot broadcast
    # accumulator and the unique-row update temporaries
    working = 4 * dp_f32 * (cap + pc + pn) + 2 * dp_f32 * u_cap
    # one-hot expand/broadcast temporaries (ADVICE r4): the [cap, ch] /
    # [ch, cap] one-hot + iota intermediates of the unique-broadcast loops
    # and, in the composed kernel, the [n_rows, ch_h] head-expansion
    # one-hots — live alongside the working set and previously uncounted
    ch = next(d for d in (256, 128, 64, 32, 16, 8) if u_cap % d == 0)
    ch_h = 256 if hot_n >= 256 else hot_n
    onehot = 4 * (2 * 2 * cap * ch + 2 * (u_cap + pc + pn + cap) * ch_h)
    need = scratch + working + onehot
    if need > _RESIDENT_VMEM_BYTES:
        kind = "composed dedup+resident" if hot_n else "dedup"
        raise ValueError(
            f"{kind} kernel VMEM estimate {need / 2**20:.1f} MiB exceeds "
            f"the {_RESIDENT_VMEM_BYTES / 2**20:.0f} MiB budget "
            f"(u_cap={u_cap}, hot_rows={hot_n}, centers_per_block={pc}, "
            f"ctx slots={cap}, pool={pn}); lower u_cap, hot_rows, or "
            "centers_per_block"
        )


# sort key for pad/non-member entries. Plain int, NOT jnp.int32(...): a
# module-level jnp array would initialize the default backend at import
# (and so take the chip) before the entry point has chosen a platform.
# Weak-typed int promotes to i32 against the i32 row arrays.
_BIG = 2**31 - 1


# How prep materializes position-indexed arrays: "scatter" uses XLA
# scatter (.at[].set with computed targets), "sort" uses one more stable
# variadic sort keyed by the target position. Both are exact; which is
# faster depends on how the backend lowers scatter (TPU scatters can
# serialize) — tools/dedup_profile.py A/Bs the prologue under each.
_PREP_IMPLS = ("scatter", "sort")


def _validate_prep_impl(impl: str) -> str:
    # a typo'd env value must fail loudly, not silently fall through to
    # scatter (ADVICE r5) — the A/B tool's whole point is knowing which ran
    if impl not in _PREP_IMPLS:
        raise ValueError(
            f"SSN_PREP_IMPL must be one of {_PREP_IMPLS}, got {impl!r}")
    return impl


_PREP_IMPL = _validate_prep_impl(os.environ.get("SSN_PREP_IMPL", "scatter"))


def get_prep_impl() -> str:
    return _PREP_IMPL


def set_prep_impl(impl: str) -> str:
    """Switch the prep placement implementation at runtime; returns the
    previous value (so callers can restore it in a ``finally``).

    The impl is read at TRACE time, so the jit caches of every step function
    whose jaxpr bakes it in are cleared on an actual switch — without this,
    a cached trace would silently keep running the old impl (the failure
    mode ``tools/dedup_profile.py`` used to hand-patch around).
    """
    global _PREP_IMPL
    prev = _PREP_IMPL
    _PREP_IMPL = _validate_prep_impl(impl)
    if prev != _PREP_IMPL:
        for step_fn in (
            fused_sgns_step,
            fused_sgns_grouped_step,
            fused_sgns_resident_step,
            fused_sgns_dedup_step,
            fused_sgns_dedup_resident_step,
        ):
            clear = getattr(step_fn, "clear_cache", None)
            if clear is not None:
                clear()
    return prev


def _place_by_position(tgt, k, values):
    """Order ``values`` ([NB, K] each) by target position ``tgt`` ([NB, K],
    ``k`` = dropped). Entries with distinct tgt < k land at index tgt;
    positions no entry targets are 0 (scatter) or unspecified past the
    member count (sort) — consumers never read them."""
    nb = tgt.shape[0]
    if _PREP_IMPL == "sort":
        out = jax.lax.sort((tgt,) + tuple(values), dimension=1,
                           is_stable=True, num_keys=1)[1:]
        return tuple(out)
    rows_idx = jnp.arange(nb)[:, None]
    return tuple(
        jnp.zeros((nb, k + 1), v.dtype).at[rows_idx, tgt].set(v)[:, :k]
        for v in values)


def _two_segment_scatter(srow, sslot, select, last, slot_bits=20):
    """Scatter sorted entries into the two-segment copy-list order.

    ``srow``/``sslot`` [NB, K]: sorted row ids and their original slots;
    ``select`` marks the entries to keep, ``last`` their run-end
    (last-occurrence) flags. Output order: [flagged write entries][non-last
    duplicates][dropped] — the contract every kernel write loop relies on
    (read loops run [0, n_member), write loops [0, n_write), both
    unconditional). Returns (rows, packed_slot, n_member, n_write).
    """
    nb, k = srow.shape
    keep_last = select & last
    n_write = keep_last.sum(axis=1).astype(jnp.int32)
    n_member = select.sum(axis=1).astype(jnp.int32)
    pos = jnp.where(
        keep_last, jnp.cumsum(keep_last, axis=1) - 1,
        n_write[:, None] + jnp.cumsum(select & ~keep_last, axis=1) - 1)
    tgt = jnp.where(select, pos, k).astype(jnp.int32)
    rows, packed_slot = _place_by_position(
        tgt, k,
        (jnp.where(select, srow, 0),
         sslot | jnp.where(keep_last, 1 << slot_bits, 0)))
    return rows, packed_slot, n_member, n_write


def _unique_prep(keyed, u_cap, row_mask=-1):
    """Unique-list + overflow ("direct") prep from ONE stable variadic sort.

    ``keyed`` [NB, cap] i32: sort key per slot — the row id (optionally
    with priority bits above the id, e.g. the composed kernel's cold bit),
    ``_BIG`` on invalid/pad slots. ``row_mask`` strips priority bits off
    stored row ids (-1 = none). Returns ``(u_list [NB, u_cap] distinct
    rows in key order, nu, ctx_rows [NB, cap] overflow copies compacted
    front, ctx_slot (slot | last-occurrence << 20), nctx_direct,
    nwu_direct, uidx [NB, cap] unique rank per original slot (sentinel
    u_cap))``.

    The previous implementation paid three [NB, cap] argsorts here (rank
    assignment, overflow compaction, overflow last-occurrence) and the
    prep prologue rivaled the kernel itself. One sort carrying the
    original slots yields all three: in key order the overflow slots are
    exactly the entries whose unique rank >= u_cap — a CONTIGUOUS run
    between the in-list entries and the pads — so compaction is a cyclic
    roll, and the end of each equal-key run is the highest original slot
    (stable sort), i.e. the reference's last-write-wins flag.
    """
    nblocks, cap = keyed.shape
    slots = jnp.broadcast_to(
        jnp.arange(cap, dtype=jnp.int32)[None], (nblocks, cap))
    sr, sslot = jax.lax.sort((keyed, slots), dimension=1, is_stable=True,
                             num_keys=1)
    vs = sr != _BIG
    head = jnp.concatenate(
        [jnp.ones((nblocks, 1), bool), sr[:, 1:] != sr[:, :-1]], axis=1
    ) & vs
    ranks_sorted = jnp.cumsum(head, axis=1) - 1  # unique rank per sorted pos
    in_sorted = vs & (ranks_sorted < u_cap)
    direct_sorted = vs & ~in_sorted
    rows_idx = jnp.arange(nblocks)[:, None]
    srow = sr & row_mask  # row ids with any priority bits stripped
    # back to original slot order (sslot is a permutation per block, so a
    # stable sort keyed by it is an exact inverse): member slots get their
    # unique rank, overflow AND pad slots the u_cap sentinel — overflow
    # ("direct") is then just valid & uidx == u_cap at the caller
    rank_or_sentinel = jnp.where(in_sorted, ranks_sorted, u_cap)
    if _PREP_IMPL == "sort":
        uidx = jax.lax.sort((sslot, rank_or_sentinel), dimension=1,
                            is_stable=True, num_keys=1)[1]
    else:
        uidx = jnp.full((nblocks, cap), u_cap, jnp.int32).at[
            rows_idx, sslot].set(rank_or_sentinel)

    tgt = jnp.where(head & (ranks_sorted < u_cap), ranks_sorted, u_cap)
    u_list = jnp.zeros((nblocks, u_cap + 1), jnp.int32)
    u_list = u_list.at[rows_idx, tgt].set(
        jnp.where(head, srow, 0)
    )[:, :u_cap]
    nu = jnp.minimum(head.sum(axis=1), u_cap).astype(jnp.int32)

    # overflow compaction into the two-segment order the write loops need
    # (see _two_segment_scatter): read loops run [0, nctx_direct), write
    # loops [0, nwu_direct), both with unconditional issues
    last_sorted = jnp.concatenate(
        [sr[:, :-1] != sr[:, 1:], jnp.ones((nblocks, 1), bool)], axis=1
    ) & vs
    ctx_rows, ctx_slot, nctx_direct, nwu_direct = _two_segment_scatter(
        srow, sslot, direct_sorted, last_sorted)
    return u_list, nu, ctx_rows, ctx_slot, nctx_direct, nwu_direct, uidx


def dedup_prep(centers, ctxs, pc, u_cap):
    """Per-block dedup prep for :func:`fused_sgns_dedup_step` (pure XLA).

    ``centers`` [N] row ids, ``ctxs`` [N, cw] (-1 pads), block-ordered.
    Ranks each block's distinct context rows in ASCENDING row-id order;
    the first ``u_cap`` get unique-list slots, the rest stay per-slot
    ("direct") copies. Returns the scalar-prefetch/BlockSpec operands of
    the dedup kernel: ``(c_packed [N], u_list [NB, u_cap], nu [NB],
    ctx_rows [NB, cap], ctx_slot [NB, cap], nctx_direct [NB],
    nw_packed [NB] (direct-ctx writes | center writes << 16),
    uidx [NB, cap], direct_real [NB, cap] f32, mask [NB, cw, pc] f32)``.

    Shared by the step wrapper and ``tools/dedup_profile.py`` so the
    profiled prologue can never drift from the shipped math. (If a native
    host-side prep is ever added it must be pinned bit-identical to this
    function by a test — none exists today.)
    """
    n, cw = ctxs.shape
    nblocks = n // pc
    cap = pc * cw
    flat = (
        ctxs.reshape(nblocks, pc, cw).transpose(0, 2, 1).reshape(nblocks, cap)
    ).astype(jnp.int32)
    valid = flat >= 0
    (u_list, nu, ctx_rows, ctx_slot, nctx_direct, nwu_direct,
     uidx) = _unique_prep(jnp.where(valid, flat, _BIG), u_cap)
    direct_real = (valid & (uidx >= u_cap)).astype(jnp.float32)
    mask = valid.reshape(nblocks, cw, pc).astype(jnp.float32)

    c_blocks = centers.astype(jnp.int32).reshape(nblocks, pc)
    c_last = _last_occurrence(c_blocks, jnp.ones_like(c_blocks, bool))
    nwrite_c = c_last.sum(axis=1).astype(jnp.int32)
    c_packed = (c_blocks | jnp.where(c_last, 1 << 30, 0)).reshape(-1)
    # write-count packing: nwu_ref carries direct-ctx writes (low 16 bits)
    # and center writes (high bits) — the wrapper's cap < 2^16 guard
    # bounds both
    nw_packed = (nwu_direct | (nwrite_c << 16)).astype(jnp.int32)
    return (c_packed, u_list, nu, ctx_rows, ctx_slot, nctx_direct,
            nw_packed, uidx, direct_real, mask)


def _cold_compact(rows, is_cold, slot_bits=20):
    """Compact cold entries to the front of each block's copy list.

    ``rows`` [NB, K] i32 row ids, ``is_cold`` [NB, K] bool. Returns
    (cold_rows [NB, K] — cold entries first, 0 elsewhere; packed_slot
    [NB, K] — original slot | is-last-occurrence << slot_bits; n_cold [NB];
    n_write [NB]).

    ONE variadic stable sort by row id (carrying original slots) does all
    the work: duplicate rows form runs whose END is the highest original
    slot — exactly the reference's last-write-wins flag — and non-cold/pad
    entries sink to the back. The previous implementation spent TWO
    [NB, K] argsorts here (slot-order compaction + a separate
    last-occurrence sort); prep sorts were ~the whole XLA prologue of the
    dedup/resident steps.

    TWO-SEGMENT ORDER: the first ``n_write`` entries are exactly the
    flagged (last-occurrence) copies, the rest of the first ``n_cold``
    are the non-last duplicates. Kernel read loops run [0, n_cold) as
    before; WRITE loops run [0, n_write) with an UNCONDITIONAL issue —
    the per-entry flag branch over mostly-skipped slots was a measured
    ~60ns/iteration of pure scalar-core waste (docs/ARCHITECTURE.md
    round-5 ablation; ~1340 skipped iterations per grouped block at the
    bench shape).

    Consumers depend only on the SET of (row, original slot) copies and
    on which slots carry write flags — both are order-invariant, so the
    reordering cannot change results.
    """
    nb, k = rows.shape
    keyed = jnp.where(is_cold, rows, _BIG)
    slots = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None], (nb, k))
    sr, sslot = jax.lax.sort((keyed, slots), dimension=1, is_stable=True,
                             num_keys=1)
    vs = sr != _BIG
    last = jnp.concatenate(
        [sr[:, :-1] != sr[:, 1:], jnp.ones((nb, 1), bool)], axis=1
    ) & vs
    return _two_segment_scatter(sr, sslot, vs, last, slot_bits=slot_bits)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "centers_per_block", "pool_size", "window",
                     "hot_rows", "interpret"),
    donate_argnums=(0, 1),
)
def fused_sgns_resident_step(
    in_table: jax.Array,
    out_table: jax.Array,
    centers: jax.Array,  # [N] row ids
    ctxs: jax.Array,  # [N, CW] row ids, -1 = pad
    pool_rows: jax.Array,  # [N // centers_per_block * pool_size]
    lr: float,
    lam: float,
    window: int,
    centers_per_block: int = 256,
    pool_size: int = 64,
    hot_rows: int = 1024,
    interpret: bool = False,
):
    """Center-major fused substep with VMEM-resident head rows.

    Returns (in_table, out_table, loss). Rows ``< hot_n`` (``hot_rows``
    clipped to capacity, rounded to the one-hot chunk size) of both tables
    live in VMEM across the whole grid; everything else matches
    :func:`fused_sgns_grouped_step`. Requires frequency-ranked row ids for
    the perf win (Vocab orders by count); correctness never depends on it.
    """
    n, cw = ctxs.shape
    pc, pn = centers_per_block, pool_size
    if n % pc:
        raise ValueError(f"centers {n} not a multiple of centers_per_block {pc}")
    nblocks = n // pc
    if pool_rows.shape[0] != nblocks * pn:
        raise ValueError(f"pool_rows {pool_rows.shape[0]} != {nblocks * pn}")
    cap = pc * cw
    inv_b = 1.0 / (n * (window + 1))
    if cap > _SLOT_MASK:
        raise ValueError(f"centers_per_block*2*window {cap} exceeds slot bits")

    # the bulk DMA retires both tables' copies on one semaphore with
    # equal-size waits — only sound when the row shapes/dtypes agree
    if in_table.shape[1:] != out_table.shape[1:] or in_table.dtype != out_table.dtype:
        raise ValueError(
            f"in/out tables must share row shape and dtype, got "
            f"{in_table.shape[1:]}/{in_table.dtype} vs "
            f"{out_table.shape[1:]}/{out_table.dtype}"
        )
    hot_n, ch = effective_hot_rows(hot_rows, in_table.shape[0], out_table.shape[0])
    if hot_n <= 0:
        raise ValueError("hot_rows too small; use fused_sgns_grouped_step")
    _check_resident_vmem(hot_n, pc, cap, pn, in_table.shape[1:], in_table.dtype)

    # [CW, PC] orientation throughout (PC = lanes): flat slot k = c*PC + p
    flat = (
        ctxs.reshape(nblocks, pc, cw).transpose(0, 2, 1).reshape(nblocks, cap)
    ).astype(jnp.int32)
    valid = flat >= 0
    is_hot = valid & (flat < hot_n)
    hot_u_idx = jnp.where(is_hot, flat, hot_n).astype(jnp.int32)
    cold_u = (valid & ~is_hot).astype(jnp.float32)  # [NB, cap] slot-major
    ctx_rows, ctx_slot, nctx, nwu = _cold_compact(flat, valid & ~is_hot)
    mask = valid.reshape(nblocks, cw, pc).astype(jnp.float32)

    c_blocks = centers.astype(jnp.int32).reshape(nblocks, pc)
    c_hot = c_blocks < hot_n
    hot_c_idx = jnp.where(c_hot, c_blocks, hot_n).astype(jnp.int32)
    cc_rows, cc_slot, ncc, nwc = _cold_compact(c_blocks, ~c_hot)

    p_blocks = pool_rows.astype(jnp.int32).reshape(nblocks, pn)
    p_hot = p_blocks < hot_n
    hot_p_idx = jnp.where(p_hot, p_blocks, hot_n).astype(jnp.int32)
    pc_rows, pc_slot, npc, nwp = _cold_compact(p_blocks, ~p_hot)

    kern = functools.partial(
        _resident_kernel, lam=lam, inv_b=inv_b, pc=pc, cw=cw, pool=pn,
        hot_n=hot_n, ch=ch,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=13,
        grid=(nblocks,),
        in_specs=[
            # [NB, 1, K] with block (1, 1, K): Mosaic wants the last two
            # block dims divisible by (8, 128) or equal to the array dims
            pl.BlockSpec((1, 1, pc), lambda i, *_: (i, 0, 0)),  # hot_c_idx
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # hot_u_idx
            pl.BlockSpec((1, 1, pn), lambda i, *_: (i, 0, 0)),  # hot_p_idx
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # cold_u
            pl.BlockSpec((1, cw, pc), lambda i, *_: (i, 0, 0)),  # mask
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pc) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((2, cap) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, pn) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((hot_n,) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((hot_n,) + out_table.shape[1:], out_table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    new_in, new_out, loss_parts = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(in_table.shape, in_table.dtype),
            jax.ShapeDtypeStruct(out_table.shape, out_table.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, 128), jnp.float32),
        ),
        input_output_aliases={18: 0, 19: 1},
        # resident buffers + double-buffered cold slots + expansion
        # intermediates exceed the default 16 MiB scoped-vmem budget; v5e has
        # 128 MiB VMEM — allow the kernel what it actually uses (same
        # constant the fail-fast budget check validates against)
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=_RESIDENT_VMEM_BYTES
        ),
        interpret=interpret,
    )(
        cc_rows.reshape(-1), cc_slot.reshape(-1), ncc, nwc,
        ctx_rows.reshape(-1), ctx_slot.reshape(-1), nctx, nwu,
        pc_rows.reshape(-1), pc_slot.reshape(-1), npc, nwp,
        jnp.asarray(lr, jnp.float32).reshape(1),
        hot_c_idx[:, None, :], hot_u_idx[:, None, :], hot_p_idx[:, None, :],
        cold_u[:, None, :], mask,
        in_table, out_table,
    )
    return new_in, new_out, loss_parts[:, 0, 0].sum()


def _dedup_kernel(c_rows_ref, u_list_ref, nu_ref,
                  ctx_rows_ref, ctx_slot_ref, nctx_ref, nwu_ref,
                  pool_rows_ref, lr_ref,
                  uidx_in, direct_in, mask_in, in_t_in, out_t_in,
                  in_table, out_table, loss_ref,
                  v_buf, u_buf, p_buf, u_uniq,
                  read_sems, write_sems,
                  *, lam, inv_b, pc, cw, pool, u_cap, ch):
    """Center-major fused SGNS with per-block READ dedup of context rows.

    With block-ordered batches (adjacent windows overlap), a block of PC
    consecutive centers touches ~PC DISTINCT context rows across ~PC*(w+1)
    real slots. Instead of one DMA per SLOT (the grouped kernel), each
    distinct row is DMA'd ONCE into a compacted unique buffer and broadcast
    to its slots by a one-hot MXU matmul; updates accumulate back through
    the transpose (exact merged gradients per distinct row — the
    reference's merge_push_value semantics, sparsetable.h:176-179 — written
    back with ONE DMA per distinct row). Rows beyond the ``u_cap`` static
    unique capacity fall back to the grouped kernel's per-slot hogwild
    treatment, so correctness never depends on the locality assumption.
    """
    del in_t_in, out_t_in
    lr = lr_ref[0]
    PC, CW, PN, UC, CH = pc, cw, pool, u_cap, ch
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)
    cap = PC * CW
    dp = in_table.shape[1] * in_table.shape[2]
    f32 = jnp.float32

    def dmas(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems

        def mk(buf_at, table, row):
            pair = (table.at[row], buf_at)
            src, dst = pair if read else pair[::-1]
            return pltpu.make_async_copy(src, dst, sems.at[slot])

        def v_dma(p, _):
            v = c_rows_ref[b * PC + p]
            if read:
                mk(v_buf.at[slot, p], in_table, v & _ROW_MASK).start()
            else:
                @pl.when((v >> 30) != 0)
                def _():
                    mk(v_buf.at[slot, p], in_table, v & _ROW_MASK).start()
            return 0

        def u_dma(k, _):  # direct (overflow) ctx slots, per-slot
            # two-segment order (_unique_prep): write prefix is exactly the
            # flagged last-occurrence entries — unconditional issue
            s = ctx_slot_ref[b * cap + k]
            row = ctx_rows_ref[b * cap + k]
            mk(u_buf.at[slot, s & _SLOT_MASK], out_table, row).start()
            return 0

        def p_dma(q, _):
            mk(p_buf.at[slot, q], out_table, pool_rows_ref[b * PN + q]).start()
            return 0

        def uq_dma(j, _):  # one DMA per DISTINCT ctx row
            mk(u_uniq.at[slot, j], out_table, u_list_ref[b * UC + j]).start()
            return 0

        jax.lax.fori_loop(0, PC, v_dma, 0)
        jax.lax.fori_loop(
            0, nctx_ref[b] if read else nwu_ref[b] & 0xFFFF, u_dma, 0)
        jax.lax.fori_loop(0, PN, p_dma, 0)
        jax.lax.fori_loop(0, nu_ref[b], uq_dma, 0)

    def wait_all(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems
        # nwu_ref packs direct-ctx writes (low 16 bits) and center
        # last-occurrence writes (high bits) — see the wrapper
        count = (
            PC + nctx_ref[b] + PN + nu_ref[b]
            if read
            else (nwu_ref[b] & 0xFFFF) + (nwu_ref[b] >> 16) + PN + nu_ref[b]
        )
        wc = min(_WAIT_CHUNK, cap)
        _wait_rows(v_buf.at[slot, 0], u_buf.at[slot, :wc],
                   sems.at[slot], count)

    @pl.when(i == 0)
    def _():
        dmas(0, 0, "read")

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait_all(i - 1, slot_next, "write")

        dmas(i + 1, slot_next, "read")

    slot = i % 2
    wait_all(i, slot, "read")

    # ---- broadcast unique rows to their slots (one-hot MXU) --------------
    uidx = uidx_in[0, 0]  # [cap] i32, sentinel UC on pads/direct
    direct_real = direct_in[0, 0][:, None] > 0  # [cap, 1]
    mask = mask_in[0]  # [CW, PC]

    acc = jnp.zeros((cap, dp), f32)
    for c0 in range(0, UC, CH):
        j = jax.lax.broadcasted_iota(jnp.int32, (cap, CH), 1) + c0
        h = (j == uidx[:, None]).astype(f32)
        # entries >= nu were never DMA'd: 0 * poison-NaN would still be
        # NaN, so zero them by value before the matmul
        ji = jax.lax.broadcasted_iota(jnp.int32, (CH, 1), 0) + c0
        uq = jnp.where(
            ji < nu_ref[i],
            u_uniq[slot, pl.ds(c0, CH)].reshape(CH, dp).astype(f32), 0.0)
        acc = acc + jax.lax.dot_general(
            h, uq, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    is_dedup = uidx[:, None] < UC  # [cap, 1]

    vv = v_buf[slot].astype(f32).reshape(PC, dp)
    uu = jnp.where(
        is_dedup, acc,
        jnp.where(direct_real, u_buf[slot].astype(f32).reshape(cap, dp), 0.0))
    pv = p_buf[slot].astype(f32).reshape(PN, dp)

    # ---- compute (identical math to the grouped kernel) ------------------
    uu3 = uu.reshape(CW, PC, dp)
    pos = jnp.sum(uu3 * vv[None, :, :], axis=-1)
    n_real = jnp.sum(mask, axis=0, keepdims=True)
    neg = jax.lax.dot_general(
        vv, pv, (((1,), (1,)), ((), ())), preferred_element_type=f32)

    g_pos = (jax.nn.sigmoid(pos) - 1.0) * inv_b * mask
    g_neg = (lam * inv_b) * jax.nn.sigmoid(neg) * n_real.reshape(PC, 1)

    dv = jnp.sum(g_pos[:, :, None] * uu3, axis=0) + jax.lax.dot_general(
        g_neg, pv, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    du_flat = (g_pos[:, :, None] * vv[None, :, :]).reshape(cap, dp)
    dq = jax.lax.dot_general(
        g_neg, vv, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    v_shape = v_buf[slot].shape
    v_buf[slot] = (vv - lr * dv).reshape(v_shape).astype(v_buf.dtype)
    u_buf[slot] = (
        (uu - lr * du_flat).reshape(u_buf[slot].shape).astype(u_buf.dtype))
    p_buf[slot] = (pv - lr * dq).reshape(p_buf[slot].shape).astype(p_buf.dtype)

    # ---- merged updates of the unique rows (one-hot transpose) -----------
    for c0 in range(0, UC, CH):
        jt = jax.lax.broadcasted_iota(jnp.int32, (CH, cap), 0) + c0
        ht = (jt == uidx[None, :]).astype(f32)
        d_u = jax.lax.dot_general(
            ht, du_flat, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        u_uniq[slot, pl.ds(c0, CH)] = (
            u_uniq[slot, pl.ds(c0, CH)].reshape(CH, dp).astype(f32) - lr * d_u
        ).reshape((CH,) + u_uniq.shape[2:]).astype(u_uniq.dtype)

    loss = -(
        jnp.sum(jax.nn.log_sigmoid(pos) * mask)
        + lam * jnp.sum(jax.nn.log_sigmoid(-neg) * n_real.reshape(PC, 1))
    )
    loss_ref[...] = jnp.full(loss_ref.shape, loss * inv_b, dtype=jnp.float32)

    dmas(i, slot, "write")

    @pl.when(i == nblocks - 1)
    def _():
        wait_all(i, slot, "write")

        @pl.when(nblocks >= 2)
        def _():
            wait_all(i - 1, (i - 1) % 2, "write")


@functools.partial(
    jax.jit,
    static_argnames=("lam", "centers_per_block", "pool_size", "window",
                     "u_cap", "interpret"),
    donate_argnums=(0, 1),
)
def fused_sgns_dedup_step(
    in_table: jax.Array,
    out_table: jax.Array,
    centers: jax.Array,  # [N] row ids
    ctxs: jax.Array,  # [N, CW] row ids, -1 = pad
    pool_rows: jax.Array,  # [N // centers_per_block * pool_size]
    lr,
    lam: float,
    window: int,
    centers_per_block: int = 256,
    pool_size: int = 64,
    u_cap: int = 512,
    interpret: bool = False,
):
    """Center-major fused substep with per-block context-read dedup.

    Returns (in_table, out_table, loss). Designed for BLOCK-ORDERED batches
    (``data.sampler.batch_stream_blocks``): consecutive windows overlap, so
    each block's ~PC*(w+1) real context slots hit only ~PC distinct rows —
    one read DMA + one merged write DMA per distinct row instead of one per
    slot. Distinct rows are assigned (in ascending row order) to the first
    ``u_cap`` unique buffer entries; overflow rows keep the grouped
    kernel's per-slot hogwild treatment. Semantics: deduped rows get exact
    merged gradient sums (deterministic); centers/pool/overflow match
    :func:`fused_sgns_grouped_step`.
    """
    n, cw = ctxs.shape
    pc, pn = centers_per_block, pool_size
    if n % pc:
        raise ValueError(f"centers {n} not a multiple of centers_per_block {pc}")
    nblocks = n // pc
    if pool_rows.shape[0] != nblocks * pn:
        raise ValueError(f"pool_rows {pool_rows.shape[0]} != {nblocks * pn}")
    if u_cap % 8 or u_cap <= 0:
        raise ValueError(f"u_cap must be a positive multiple of 8, got {u_cap}")
    cap = pc * cw
    inv_b = 1.0 / (n * (window + 1))
    # write counts pack (direct-ctx | centers << 16) into one i32, so the
    # per-block slot count must fit 16 bits (stricter than _SLOT_MASK)
    if cap >= (1 << 16):
        raise ValueError(
            f"centers_per_block*2*window {cap} exceeds the 16-bit write-count "
            "packing; lower centers_per_block")
    if in_table.shape[0] > _ROW_MASK or out_table.shape[0] > _ROW_MASK:
        raise ValueError("table capacity exceeds 2^30 (row-id flag bit)")
    if in_table.shape[1:] != out_table.shape[1:] or in_table.dtype != out_table.dtype:
        raise ValueError("in/out tables must share row shape and dtype")
    _check_dedup_vmem(u_cap, pc, cap, pn, in_table.shape[1:], in_table.dtype)

    (c_packed, u_list, nu, ctx_rows, ctx_slot, nctx_direct, nw_packed,
     uidx, direct_real, mask) = dedup_prep(centers, ctxs, pc, u_cap)

    # one-hot chunk size must DIVIDE u_cap (the ds() slices tile it exactly)
    ch = next(d for d in (256, 128, 64, 32, 16, 8) if u_cap % d == 0)
    kern = functools.partial(
        _dedup_kernel, lam=lam, inv_b=inv_b, pc=pc, cw=cw, pool=pn,
        u_cap=u_cap, ch=ch,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # uidx
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # direct
            pl.BlockSpec((1, cw, pc), lambda i, *_: (i, 0, 0)),  # mask
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pc) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((2, cap) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, pn) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, u_cap) + out_table.shape[1:], out_table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    new_in, new_out, loss_parts = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(in_table.shape, in_table.dtype),
            jax.ShapeDtypeStruct(out_table.shape, out_table.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, 128), jnp.float32),
        ),
        input_output_aliases={12: 0, 13: 1},
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=_RESIDENT_VMEM_BYTES
        ),
        interpret=interpret,
    )(
        c_packed,
        u_list.reshape(-1),
        nu,
        ctx_rows.reshape(-1),
        ctx_slot.reshape(-1),
        nctx_direct,
        nw_packed,
        pool_rows.astype(jnp.int32),
        jnp.asarray(lr, jnp.float32).reshape(1),
        uidx[:, None, :],
        direct_real[:, None, :],
        mask,
        in_table,
        out_table,
    )
    return new_in, new_out, loss_parts[:, 0, 0].sum()


def _dedup_resident_kernel(
        ccold_rows_ref, ccold_slot_ref, ncc_ref, nwc_ref,
        u_list_ref, nu_ref, nuc_ref,
        ctx_rows_ref, ctx_slot_ref, nctx_ref, nwu_ref,
        pcold_rows_ref, pcold_slot_ref, npc_ref, nwp_ref, lr_ref,
        u_list_in, uidx_in, direct_in, hot_c_in, hot_p_in, mask_in,
        in_t_in, out_t_in,
        in_table, out_table, loss_ref,
        v_buf, u_buf, p_buf, u_uniq, hot_in, hot_out,
        read_sems, write_sems, bulk_sem,
        *, lam, inv_b, pc, cw, pool, u_cap, ch, hot_n, ch_h):
    """Composed kernel: per-block context-read DEDUP + VMEM-RESIDENT head.

    The two round-3 kernels attack the same duplicate row traffic from
    different ends (docs/ARCHITECTURE.md "remaining lever"): dedup removes
    within-block duplicate context DMAs; residency removes ALL copies of
    the zipf head (rows < hot_n of both tables live in VMEM for the whole
    grid). Composed: context rows go through the unique list, and unique
    entries / centers / pool rows that are HOT source from (and update
    into) the resident buffers instead of DMA — on an unsubsampled zipf
    corpus the head carries ~half the row traffic, so this removes ~half
    of the dedup kernel's remaining copies.

    Semantics: hot rows (wherever they appear) get DETERMINISTIC
    sequential merged updates across blocks (merge_push_value parity,
    sparsetable.h:176-179); cold unique context rows get exact per-block
    merged updates; cold centers/pool and overflow context slots keep the
    grouped kernel's hogwild treatment.
    """
    del in_t_in, out_t_in
    lr = lr_ref[0]
    PC, CW, PN, UC, CH, HOT, CHH = pc, cw, pool, u_cap, ch, hot_n, ch_h
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)
    cap = PC * CW
    s_t, lanes = in_table.shape[1], in_table.shape[2]
    dp = s_t * lanes
    f32 = jnp.float32

    def bulk_start(table_dir):
        for tbl, buf in ((in_table, hot_in), (out_table, hot_out)):
            src, dst = (tbl.at[pl.ds(0, HOT)], buf)
            if table_dir == "write":
                src, dst = dst, src
            pltpu.make_async_copy(src, dst, bulk_sem).start()

    def bulk_wait():
        for _ in range(2):
            pltpu.make_async_copy(hot_in, hot_in, bulk_sem).wait()

    def dmas(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems

        def mk(buf_at, table, row):
            pair = (table.at[row], buf_at)
            src, dst = pair if read else pair[::-1]
            return pltpu.make_async_copy(src, dst, sems.at[slot])

        def cold_dma(rows_ref, slot_ref, buf, table, stride):
            # two-segment lists (_cold_compact/_unique_prep): write loops
            # are bounded by the flagged-write count, unconditional issue
            def go(k, _):
                row = rows_ref[b * stride + k]
                sl = slot_ref[b * stride + k]
                mk(buf.at[slot, sl & _SLOT_MASK], table, row).start()
                return 0
            return go

        def uq_dma(j, _):  # one DMA per DISTINCT COLD ctx row
            mk(u_uniq.at[slot, j], out_table, u_list_ref[b * UC + j]).start()
            return 0

        jax.lax.fori_loop(
            0, ncc_ref[b] if read else nwc_ref[b],
            cold_dma(ccold_rows_ref, ccold_slot_ref, v_buf, in_table, PC), 0)
        jax.lax.fori_loop(
            0, nctx_ref[b] if read else nwu_ref[b],
            cold_dma(ctx_rows_ref, ctx_slot_ref, u_buf, out_table, cap), 0)
        jax.lax.fori_loop(
            0, npc_ref[b] if read else nwp_ref[b],
            cold_dma(pcold_rows_ref, pcold_slot_ref, p_buf, out_table, PN), 0)
        # the hot-first sort key makes COLD uniques the [nu-nuc, nu) suffix
        # of the list — loop exactly that range, no per-entry hot branch
        jax.lax.fori_loop(nu_ref[b] - nuc_ref[b], nu_ref[b], uq_dma, 0)

    def wait_all(b, slot, table_dir):
        read = table_dir == "read"
        sems = read_sems if read else write_sems
        # nuc = DMA'd (cold) unique entries; hot entries never move per-row
        count = (
            ncc_ref[b] + nctx_ref[b] + npc_ref[b] + nuc_ref[b]
            if read
            else nwc_ref[b] + nwu_ref[b] + nwp_ref[b] + nuc_ref[b]
        )
        wc = min(_WAIT_CHUNK, cap)
        _wait_rows(v_buf.at[slot, 0], u_buf.at[slot, :wc],
                   sems.at[slot], count)

    @pl.when(i == 0)
    def _():
        bulk_start("read")
        dmas(0, 0, "read")
        bulk_wait()

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait_all(i - 1, slot_next, "write")

        dmas(i + 1, slot_next, "read")

    slot = i % 2
    wait_all(i, slot, "read")

    # ---- assemble unique-row values: resident head or DMA ---------------
    u_list_v = u_list_in[0, 0]  # [UC] i32 (0-padded past nu)
    uidx = uidx_in[0, 0]  # [cap] i32, sentinel UC on pads/direct
    direct_real = direct_in[0, 0][:, None] > 0  # [cap, 1]
    hot_c_idx = hot_c_in[0, 0]  # [PC] i32, sentinel HOT on cold
    hot_p_idx = hot_p_in[0, 0]  # [PN]
    mask = mask_in[0]  # [CW, PC]

    def expand(idx, buf, n_rows):
        """one_hot(idx) @ buf[0:HOT] -> [n_rows, dp]; zeros where idx>=HOT."""
        acc = jnp.zeros((n_rows, dp), f32)
        for c0 in range(0, HOT, CHH):
            j = jax.lax.broadcasted_iota(jnp.int32, (n_rows, CHH), 1) + c0
            h = (j == idx[:, None]).astype(f32)
            acc = acc + jax.lax.dot_general(
                h, buf[pl.ds(c0, CHH)].reshape(CHH, dp).astype(f32),
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
        return acc

    # entries >= nu were never DMA'd AND their u_list value (0) is hot, so
    # the where() below selects the (finite) expansion value — poison never
    # reaches arithmetic; their d_u is zero so nothing is written anywhere
    nu_here = nu_ref[i]
    is_hot_u = u_list_v[:, None] < HOT  # [UC, 1]
    u_hot_vals = expand(jnp.where(u_list_v < HOT, u_list_v, HOT), hot_out, UC)
    valid_j = (jax.lax.broadcasted_iota(jnp.int32, (UC, 1), 0) < nu_here)
    u_vals = jnp.where(
        is_hot_u, u_hot_vals,
        jnp.where(valid_j, u_uniq[slot].astype(f32).reshape(UC, dp), 0.0))

    # ---- broadcast unique rows to their slots (one-hot MXU) --------------
    acc = jnp.zeros((cap, dp), f32)
    for c0 in range(0, UC, CH):
        j = jax.lax.broadcasted_iota(jnp.int32, (cap, CH), 1) + c0
        h = (j == uidx[:, None]).astype(f32)
        # static value slice (c0/CH are trace-time ints): Mosaic TC has no
        # dynamic_slice lowering for VALUES (refs use pl.ds); lax.slice does
        acc = acc + jax.lax.dot_general(
            h, jax.lax.slice(u_vals, (c0, 0), (c0 + CH, dp)),
            (((1,), (0,)), ((), ())), preferred_element_type=f32)
    is_dedup = uidx[:, None] < UC

    vc_hot = expand(hot_c_idx, hot_in, PC)
    pv_hot = expand(hot_p_idx, hot_out, PN)
    is_hot_c = hot_c_idx[:, None] < HOT
    is_hot_p = hot_p_idx[:, None] < HOT

    vv = jnp.where(is_hot_c, vc_hot, v_buf[slot].astype(f32).reshape(PC, dp))
    uu = jnp.where(
        is_dedup, acc,
        jnp.where(direct_real, u_buf[slot].astype(f32).reshape(cap, dp), 0.0))
    pv = jnp.where(is_hot_p, pv_hot, p_buf[slot].astype(f32).reshape(PN, dp))

    # ---- compute (identical math to the grouped kernel) ------------------
    uu3 = uu.reshape(CW, PC, dp)
    pos = jnp.sum(uu3 * vv[None, :, :], axis=-1)
    n_real = jnp.sum(mask, axis=0, keepdims=True)
    neg = jax.lax.dot_general(
        vv, pv, (((1,), (1,)), ((), ())), preferred_element_type=f32)

    g_pos = (jax.nn.sigmoid(pos) - 1.0) * inv_b * mask
    g_neg = (lam * inv_b) * jax.nn.sigmoid(neg) * n_real.reshape(PC, 1)

    dv = jnp.sum(g_pos[:, :, None] * uu3, axis=0) + jax.lax.dot_general(
        g_neg, pv, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    du_flat = (g_pos[:, :, None] * vv[None, :, :]).reshape(cap, dp)
    dq = jax.lax.dot_general(
        g_neg, vv, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    v_shape = v_buf[slot].shape
    v_buf[slot] = (vv - lr * dv).reshape(v_shape).astype(v_buf.dtype)
    u_buf[slot] = (
        (uu - lr * du_flat).reshape(u_buf[slot].shape).astype(u_buf.dtype))
    p_buf[slot] = (pv - lr * dq).reshape(p_buf[slot].shape).astype(p_buf.dtype)

    # ---- merged updates of the unique rows (one-hot transpose) -----------
    # chunkwise transpose-accumulate, assembled with a static concatenate:
    # dynamic_update_slice on a VALUE has no Mosaic TC lowering
    d_u_chunks = []
    for c0 in range(0, UC, CH):
        jt = jax.lax.broadcasted_iota(jnp.int32, (CH, cap), 0) + c0
        ht = (jt == uidx[None, :]).astype(f32)
        d_u_chunks.append(
            jax.lax.dot_general(ht, du_flat, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32))
    d_u = (jnp.concatenate(d_u_chunks, axis=0) if len(d_u_chunks) > 1
           else d_u_chunks[0])
    new_u_vals = u_vals - lr * d_u
    u_uniq[slot] = new_u_vals.reshape(u_uniq[slot].shape).astype(u_uniq.dtype)

    # ---- hot-row merged updates into the resident buffers ----------------
    d_u_hot = jnp.where(is_hot_u, d_u, 0.0)
    for c0 in range(0, HOT, CHH):
        def acc_t(idx, grads, n_rows):
            jt = jax.lax.broadcasted_iota(jnp.int32, (CHH, n_rows), 0) + c0
            ht = (jt == idx[None, :]).astype(f32)
            return jax.lax.dot_general(
                ht, grads, (((1,), (0,)), ((), ())), preferred_element_type=f32)

        d_out = acc_t(u_list_v, d_u_hot, UC) + acc_t(hot_p_idx, dq, PN)
        hot_out[pl.ds(c0, CHH)] = (
            hot_out[pl.ds(c0, CHH)].reshape(CHH, dp).astype(f32) - lr * d_out
        ).reshape(CHH, s_t, lanes).astype(hot_out.dtype)
        d_in = acc_t(hot_c_idx, dv, PC)
        hot_in[pl.ds(c0, CHH)] = (
            hot_in[pl.ds(c0, CHH)].reshape(CHH, dp).astype(f32) - lr * d_in
        ).reshape(CHH, s_t, lanes).astype(hot_in.dtype)

    loss = -(
        jnp.sum(jax.nn.log_sigmoid(pos) * mask)
        + lam * jnp.sum(jax.nn.log_sigmoid(-neg) * n_real.reshape(PC, 1))
    )
    loss_ref[...] = jnp.full(loss_ref.shape, loss * inv_b, dtype=jnp.float32)

    dmas(i, slot, "write")

    @pl.when(i == nblocks - 1)
    def _():
        wait_all(i, slot, "write")

        @pl.when(nblocks >= 2)
        def _():
            wait_all(i - 1, (i - 1) % 2, "write")

        bulk_start("write")
        bulk_wait()


@functools.partial(
    jax.jit,
    static_argnames=("lam", "centers_per_block", "pool_size", "window",
                     "u_cap", "hot_rows", "interpret"),
    donate_argnums=(0, 1),
)
def fused_sgns_dedup_resident_step(
    in_table: jax.Array,
    out_table: jax.Array,
    centers: jax.Array,  # [N] row ids
    ctxs: jax.Array,  # [N, CW] row ids, -1 = pad
    pool_rows: jax.Array,  # [N // centers_per_block * pool_size]
    lr,
    lam: float,
    window: int,
    centers_per_block: int = 256,
    pool_size: int = 64,
    u_cap: int = 512,
    hot_rows: int = 512,
    interpret: bool = False,
):
    """Composed dedup + resident substep (see :func:`_dedup_resident_kernel`).

    Returns (in_table, out_table, loss). Requires frequency-ranked row ids
    for the perf win (the zipf head must be rows < hot_rows); correctness
    never depends on it. Block-ordered batches
    (``data.sampler.batch_stream_blocks``) supply the locality the unique
    list needs, exactly like :func:`fused_sgns_dedup_step`.
    """
    n, cw = ctxs.shape
    pc, pn = centers_per_block, pool_size
    if n % pc:
        raise ValueError(f"centers {n} not a multiple of centers_per_block {pc}")
    nblocks = n // pc
    if pool_rows.shape[0] != nblocks * pn:
        raise ValueError(f"pool_rows {pool_rows.shape[0]} != {nblocks * pn}")
    if u_cap % 8 or u_cap <= 0:
        raise ValueError(f"u_cap must be a positive multiple of 8, got {u_cap}")
    cap = pc * cw
    inv_b = 1.0 / (n * (window + 1))
    if cap > _SLOT_MASK:
        raise ValueError(f"centers_per_block*2*window {cap} exceeds slot bits")
    if in_table.shape[1:] != out_table.shape[1:] or in_table.dtype != out_table.dtype:
        raise ValueError("in/out tables must share row shape and dtype")
    if in_table.shape[0] > _ROW_MASK or out_table.shape[0] > _ROW_MASK:
        raise ValueError("table capacity exceeds 2^30 (cold sort bit)")
    hot_n, ch_h = effective_hot_rows(
        hot_rows, in_table.shape[0], out_table.shape[0])
    if hot_n <= 0:
        raise ValueError("hot_rows too small; use fused_sgns_dedup_step")
    if u_cap < hot_n:
        # hot rows rank FIRST into the unique list (below); u_cap >= hot_n
        # then guarantees every distinct hot row is in-list, so an overflow
        # (direct) slot can never carry a hot row — a direct-hot slot would
        # read stale HBM and its update would be clobbered by the final
        # bulk head writeback
        raise ValueError(
            f"composed kernel requires u_cap ({u_cap}) >= effective "
            f"hot_rows ({hot_n}); raise u_cap or lower hot_rows")
    _check_dedup_vmem(u_cap, pc, cap, pn, in_table.shape[1:], in_table.dtype,
                      hot_n=hot_n)

    flat = (
        ctxs.reshape(nblocks, pc, cw).transpose(0, 2, 1).reshape(nblocks, cap)
    ).astype(jnp.int32)
    valid = flat >= 0

    # sort key: hot rows first (cold bit above the row id), then by row —
    # distinct rows keep distinct keys, and every hot distinct row lands at
    # a rank < hot_n <= u_cap (the correctness guarantee above); one shared
    # single-sort pass yields list, ranks, and overflow compaction
    cold_bit = jnp.where(flat >= hot_n, jnp.int32(1 << 30), 0)
    keyed = jnp.where(valid, flat | cold_bit, _BIG)
    (u_list, nu, ctx_rows, ctx_slot, nctx_direct, nwu_direct,
     uidx) = _unique_prep(keyed, u_cap, row_mask=_ROW_MASK)
    direct_real = (valid & (uidx >= u_cap)).astype(jnp.float32)
    # DMA'd (cold) unique entries per block: rows >= hot_n within the list
    in_range = jnp.arange(u_cap)[None, :] < nu[:, None]
    nu_cold = (in_range & (u_list >= hot_n)).sum(axis=1).astype(jnp.int32)
    mask = valid.reshape(nblocks, cw, pc).astype(jnp.float32)

    c_blocks = centers.astype(jnp.int32).reshape(nblocks, pc)
    c_hot = c_blocks < hot_n
    hot_c_idx = jnp.where(c_hot, c_blocks, hot_n).astype(jnp.int32)
    cc_rows, cc_slot, ncc, nwc = _cold_compact(c_blocks, ~c_hot)

    p_blocks = pool_rows.astype(jnp.int32).reshape(nblocks, pn)
    p_hot = p_blocks < hot_n
    hot_p_idx = jnp.where(p_hot, p_blocks, hot_n).astype(jnp.int32)
    pc_rows, pc_slot, npc, nwp = _cold_compact(p_blocks, ~p_hot)

    ch = next(d for d in (256, 128, 64, 32, 16, 8) if u_cap % d == 0)
    kern = functools.partial(
        _dedup_resident_kernel, lam=lam, inv_b=inv_b, pc=pc, cw=cw, pool=pn,
        u_cap=u_cap, ch=ch, hot_n=hot_n, ch_h=ch_h,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=16,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, 1, u_cap), lambda i, *_: (i, 0, 0)),  # u_list
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # uidx
            pl.BlockSpec((1, 1, cap), lambda i, *_: (i, 0, 0)),  # direct
            pl.BlockSpec((1, 1, pc), lambda i, *_: (i, 0, 0)),  # hot_c_idx
            pl.BlockSpec((1, 1, pn), lambda i, *_: (i, 0, 0)),  # hot_p_idx
            pl.BlockSpec((1, cw, pc), lambda i, *_: (i, 0, 0)),  # mask
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pc) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((2, cap) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, pn) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((2, u_cap) + out_table.shape[1:], out_table.dtype),
            pltpu.VMEM((hot_n,) + in_table.shape[1:], in_table.dtype),
            pltpu.VMEM((hot_n,) + out_table.shape[1:], out_table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    new_in, new_out, loss_parts = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(in_table.shape, in_table.dtype),
            jax.ShapeDtypeStruct(out_table.shape, out_table.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, 128), jnp.float32),
        ),
        input_output_aliases={22: 0, 23: 1},
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, vmem_limit_bytes=_RESIDENT_VMEM_BYTES
        ),
        interpret=interpret,
    )(
        cc_rows.reshape(-1), cc_slot.reshape(-1), ncc, nwc,
        u_list.reshape(-1), nu, nu_cold,
        ctx_rows.reshape(-1), ctx_slot.reshape(-1), nctx_direct, nwu_direct,
        pc_rows.reshape(-1), pc_slot.reshape(-1), npc, nwp,
        jnp.asarray(lr, jnp.float32).reshape(1),
        u_list[:, None, :], uidx[:, None, :], direct_real[:, None, :],
        hot_c_idx[:, None, :], hot_p_idx[:, None, :], mask,
        in_table, out_table,
    )
    return new_in, new_out, loss_parts[:, 0, 0].sum()


@functools.partial(
    jax.jit,
    static_argnames=("lam", "pairs_per_block", "pool_size", "interpret"),
    donate_argnums=(0, 1),
)
def fused_sgns_step(
    in_table: jax.Array,
    out_table: jax.Array,
    in_rows: jax.Array,
    pos_rows: jax.Array,
    pool_rows: jax.Array,
    lr: float,
    lam: float,
    pairs_per_block: int = 512,
    pool_size: int = 64,
    interpret: bool = False,
):
    """One SGD substep over B pairs. Returns (in_table, out_table, loss).

    ``in_rows``/``pos_rows``: [B]; ``pool_rows``: [B//pairs_per_block *
    pool_size]; all row ids in-bounds. ``lam`` is the negative-term weight
    (``negatives / pool_size``); loss/grads are means over B.
    """
    b = in_rows.shape[0]
    p, pn = pairs_per_block, pool_size
    if b % p:
        raise ValueError(f"batch {b} not a multiple of pairs_per_block {p}")
    nblocks = b // p
    if pool_rows.shape[0] != nblocks * pn:
        raise ValueError(
            f"pool_rows {pool_rows.shape[0]} != nblocks*pool {nblocks * pn}"
        )
    if in_table.shape[1:] != out_table.shape[1:] or in_table.dtype != out_table.dtype:
        raise ValueError("in/out tables must share row shape and dtype")
    c, s, lanes = in_table.shape
    kern = functools.partial(
        _kernel, lam=lam, inv_b=1.0 / b, pairs=p, pool=pn
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, p, s, lanes), in_table.dtype),
            pltpu.VMEM((2, p, s, lanes), out_table.dtype),
            pltpu.VMEM((2, pn, s, lanes), out_table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    new_in, new_out, loss_parts = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(in_table.shape, in_table.dtype),
            jax.ShapeDtypeStruct(out_table.shape, out_table.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, 128), jnp.float32),
        ),
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(
        in_rows.astype(jnp.int32),
        pos_rows.astype(jnp.int32),
        pool_rows.astype(jnp.int32),
        jnp.asarray(lr, jnp.float32).reshape(1),
        in_table,
        out_table,
    )
    return new_in, new_out, loss_parts[:, 0, 0].sum()
