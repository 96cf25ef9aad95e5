"""Row-granularity DMA kernels on the packed table layout — the PS hot path.

The reference's server hot loop is a per-key hashmap probe under a lock
(``src/core/parameter/sparsetable.h:142-149`` find-or-init per pulled key;
``sparsetable.h:181-192`` apply per pushed key). The TPU equivalent of "one
key = one independent memory transaction" is one row DMA per key: XLA's own
gather/scatter on a ``[capacity, dim]`` table serializes at ~100-140 ns/row
on v5e (measured), so these kernels drive the DMA engines directly.

Layout: a **packed table** of shape ``[capacity, S, 128]`` (``S = ceil(dim/
128)``), i.e. one row = one ``(S, 128)`` tile. Mosaic requires DMA slices to
be tile-aligned in the last two dims — a row of a 2-D ``[C, D]`` table can
never be sliced alone (sublane tiling is 8), but a leading-dim slice of the
3-D layout is exactly one row with zero padding waste. Row elements live at
``packed[r, s, l] == row[s * 128 + l]``; all framework math (dots, grads,
optimizer rules) is layout-agnostic — padding lanes hold zeros and stay zero
under every access method whose update is ``f(grad) == 0`` at ``grad == 0``.

Kernels (both double-buffered, one DMA per row, shared per-slot semaphore —
the TPU's semaphore space caps out near 512, so per-row semaphores are not
an option; equal-sized copies make shared byte-accounting exact):

* :func:`gather_rows` — pull: for each of N row ids, DMA ``table[r]`` HBM ->
  VMEM, emitting ``[N, S, 128]``. Block ``i+1``'s row DMAs are issued before
  block ``i`` is consumed, so issue latency overlaps the output pipeline.
  Every slot is live, so a block's copies are started ``_START_UNROLL`` to a
  loop iteration and retired ``_WAIT_CHUNK`` rows to a wait (PR 29). Both
  halves of that loop, ``_start_rows`` and ``_wait_rows``, live in
  ``ops/fused_sgns.py`` beside the grouped SGNS kernel, which runs them too
  (PR 32), with the readings that chose their constants.
* :func:`scatter_add_rows` — push: read-modify-write ``table[r] += delta``
  per row, pipelined two blocks deep (reads of block ``i+1`` overlap writes
  of block ``i``). Rows MUST be unique (or >= capacity for padding slots,
  which are skipped): uniqueness is what makes the RMW race-free, and is
  guaranteed by the caller via ``merge_duplicate_rows`` (the reference's
  ``merge_push_value`` duplicate merge, ``sparsetable.h:176-179``).
* :func:`scatter_write_rows` — write-only scatter ``table[r] = value`` for
  unique rows. This is also the tiered store's slot-install path
  (``tiered/store.py::_scatter_rowdma``): faulted master rows land in the
  HBM cache plane from one fused host staging buffer, one DMA per row.
* :func:`scatter_adagrad_rows` / :func:`scatter_adagrad_fused_rows` —
  fused AdaGrad RMW (split param/accum buffers, or both packed into one
  stored tile so a single DMA pair moves them). The slot-fused kernel takes
  the live count as an operand: ``rows[:count]`` are unique and in range
  (``merge_duplicate_rows`` sorts them first), every slot after them is
  ignored and costs the scalar core nothing.

Which of these runs is decided in one place, :func:`on_tpu`: on a TPU
backend the callers in ``parallel/store.py`` always take these kernels; on
the CPU (tests) they take the XLA twins (`jnp.take` / `.at[].add`) there,
and tests drive the kernels themselves with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.fused_sgns import _WAIT_CHUNK, _start_rows, _wait_rows

ROW_LANES = 128


def packed_shape(capacity: int, dim: int):
    """[capacity, S, 128] shape for a logical [capacity, dim] table."""
    s = -(-dim // ROW_LANES)
    return (capacity, s, ROW_LANES)


def pack_rows(rows2d: jax.Array) -> jax.Array:
    """[N, dim] -> [N, S, 128] with zero padding lanes."""
    n, dim = rows2d.shape
    s = -(-dim // ROW_LANES)
    pad = s * ROW_LANES - dim
    if pad:
        rows2d = jnp.pad(rows2d, ((0, 0), (0, pad)))
    return rows2d.reshape(n, s, ROW_LANES)


def unpack_rows(rows3d: jax.Array, dim: int) -> jax.Array:
    """[N, S, 128] -> [N, dim]."""
    n = rows3d.shape[0]
    return rows3d.reshape(n, -1)[:, :dim]


# --------------------------------------------------------------- gather ---


def _gather_kernel(rows_ref, table_ref, out_ref, scratch, sems):
    R = scratch.shape[1]
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    def start_block(b, slot):
        # every slot of a gather is live: no count, no per-slot test
        _start_rows(R, lambda j: pltpu.make_async_copy(
            table_ref.at[rows_ref[b * R + j]], scratch.at[slot, j], sems.at[slot]
        ).start())

    @pl.when(i == 0)
    def _():
        start_block(0, 0)

    @pl.when(i + 1 < nblocks)
    def _():
        start_block(i + 1, (i + 1) % 2)

    slot = i % 2
    # equal-sized copies on the slot's semaphore: retired by descriptor
    # size, a chunk of rows at a time (4.91 -> 3.37 ms at the cell's shapes;
    # chunks of 128 or one wait on the whole block: 3.36, 3.35; PR 29)
    _wait_rows(scratch.at[slot, 0], scratch.at[slot, :min(_WAIT_CHUNK, R)],
               sems.at[slot], R)
    out_ref[...] = scratch[slot]


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def gather_rows(
    table: jax.Array, rows: jax.Array, block_rows: int = 512, interpret: bool = False
) -> jax.Array:
    """``table[rows]`` for a packed ``[C, S, 128]`` table -> ``[N, S, 128]``.

    ``N`` must be a multiple of ``block_rows``; rows must be in
    ``[0, capacity)``. One DMA per row, double-buffered across blocks.
    """
    n = rows.shape[0]
    c, s, lanes = table.shape
    if n % block_rows:
        raise ValueError(f"N={n} not a multiple of block_rows={block_rows}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, s, lanes), lambda i, rows_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, s, lanes), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, s, lanes), table.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), table)


# ---------------------------------------------------------- scatter-add ---


def _scatter_kernel(rows_ref, table_in_ref, deltas_ref, table_ref,
                    scratch, read_sems, write_sems):
    # table_ref is the aliased output (same HBM buffer as table_in_ref).
    del table_in_ref
    R = scratch.shape[1]
    C = table_ref.shape[0]
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    def read_dma(b, slot, j):
        return pltpu.make_async_copy(
            table_ref.at[rows_ref[b * R + j]], scratch.at[slot, j], read_sems.at[slot]
        )

    def write_dma(b, slot, j):
        return pltpu.make_async_copy(
            scratch.at[slot, j], table_ref.at[rows_ref[b * R + j]], write_sems.at[slot]
        )

    def for_valid(b, fn):
        def body(j, _):
            @pl.when(rows_ref[b * R + j] < C)
            def _():
                fn(j)
            return 0
        jax.lax.fori_loop(0, R, body, 0)

    @pl.when(i == 0)
    def _():
        for_valid(0, lambda j: read_dma(0, 0, j).start())

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        # block i-1 used slot_next; its writebacks must land before we
        # overwrite the slot's scratch with new reads.
        @pl.when(i >= 1)
        def _():
            for_valid(i - 1, lambda j: write_dma(i - 1, slot_next, j).wait())

        for_valid(i + 1, lambda j: read_dma(i + 1, slot_next, j).start())

    slot = i % 2

    def rmw(j):
        read_dma(i, slot, j).wait()
        scratch[slot, j] = scratch[slot, j] + deltas_ref[j]
        write_dma(i, slot, j).start()

    for_valid(i, rmw)

    @pl.when(i == nblocks - 1)
    def _():
        for_valid(i, lambda j: write_dma(i, slot, j).wait())

        @pl.when(nblocks >= 2)
        def _():
            for_valid(i - 1, lambda j: write_dma(i - 1, (i - 1) % 2, j).wait())


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "interpret"),
    donate_argnums=(0,),
)
def scatter_add_rows(
    table: jax.Array,
    rows: jax.Array,
    deltas: jax.Array,
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``table[rows] += deltas`` in place for UNIQUE rows (packed layout).

    Rows ``>= capacity`` are padding and skipped (the ``mode='drop'``
    equivalent). The table buffer is donated and aliased — no copy.
    """
    n = rows.shape[0]
    c, s, lanes = table.shape
    if n % block_rows:
        raise ValueError(f"N={n} not a multiple of block_rows={block_rows}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_rows, s, lanes), lambda i, rows_ref: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, s, lanes), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(rows.astype(jnp.int32), table, deltas)


# -------------------------------------------------------- scatter-write ---


def _write_kernel(rows_ref, table_in_ref, values_ref, table_ref, sems):
    # Write-only scatter: each valid row of the streamed-in values block is
    # DMA'd VMEM -> HBM. Unique rows => no write races. All of a block's
    # writes are issued, then drained before the body returns: the input
    # pipeline prefetches block i+1 over block i-1's buffer while body i
    # runs, so writes must never outlive their own block's body.
    del table_in_ref
    R = values_ref.shape[0]
    C = table_ref.shape[0]
    i = pl.program_id(0)

    def write_dma(j):
        return pltpu.make_async_copy(
            values_ref.at[j], table_ref.at[rows_ref[i * R + j]], sems.at[0]
        )

    def for_valid(fn):
        def body(j, _):
            @pl.when(rows_ref[i * R + j] < C)
            def _():
                fn(j)
            return 0
        jax.lax.fori_loop(0, R, body, 0)

    for_valid(lambda j: write_dma(j).start())
    for_valid(lambda j: write_dma(j).wait())


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "interpret"),
    donate_argnums=(0,),
)
def scatter_write_rows(
    table: jax.Array,
    rows: jax.Array,
    values: jax.Array,
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``table[rows] = values`` in place for UNIQUE rows (packed layout).

    Write-only half of a generic pull-compute-writeback update (AdaGrad and
    friends); rows ``>= capacity`` are skipped.
    """
    n = rows.shape[0]
    c, s, lanes = table.shape
    if n % block_rows:
        raise ValueError(f"N={n} not a multiple of block_rows={block_rows}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_rows, s, lanes), lambda i, rows_ref: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(rows.astype(jnp.int32), table, values)


# ------------------------------------------------------- fused AdaGrad ---


def _adagrad_kernel(rows_ref, lr_ref, table_in, accum_in, deltas_ref,
                    table_ref, accum_ref, p_scr, a_scr, read_sems, write_sems,
                    *, eps):
    """Read-modify-write AdaGrad on UNIQUE rows, slot math in-kernel.

    Per row: DMA param + accum in, ``accum += g²``,
    ``param -= lr * g * rsqrt(accum + eps)``, DMA both back — one kernel
    launch for table AND slot (the unfused path costs 2 launches per slot
    array, docs/ARCHITECTURE.md known-limitations r2). Same double-buffered
    schedule as ``_scatter_kernel``; both DMAs of a row share the per-slot
    semaphore (equal sizes — param and accum rows are same shape/dtype).
    """
    del table_in, accum_in
    lr = lr_ref[0]
    R = p_scr.shape[1]
    C = table_ref.shape[0]
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    def dma(b, slot, j, buf, hbm, read):
        pair = (hbm.at[rows_ref[b * R + j]], buf.at[slot, j])
        src, dst = pair if read else pair[::-1]
        sems = read_sems if read else write_sems
        return pltpu.make_async_copy(src, dst, sems.at[slot])

    def for_valid(b, fn):
        def body(j, _):
            @pl.when(rows_ref[b * R + j] < C)
            def _():
                fn(j)
            return 0
        jax.lax.fori_loop(0, R, body, 0)

    def start_reads(b, slot):
        def go(j):
            dma(b, slot, j, p_scr, table_ref, True).start()
            dma(b, slot, j, a_scr, accum_ref, True).start()
        for_valid(b, go)

    def wait(b, slot, read):
        def go(j):
            for _ in range(2):  # param + accum copies, equal sizes
                sems = read_sems if read else write_sems
                pltpu.make_async_copy(
                    p_scr.at[slot, 0], p_scr.at[slot, 0], sems.at[slot]
                ).wait()
        for_valid(b, go)

    @pl.when(i == 0)
    def _():
        start_reads(0, 0)

    @pl.when(i + 1 < nblocks)
    def _():
        slot_next = (i + 1) % 2

        @pl.when(i >= 1)
        def _():
            wait(i - 1, slot_next, False)

        start_reads(i + 1, slot_next)

    slot = i % 2
    wait(i, slot, True)

    g = deltas_ref[...].astype(jnp.float32)
    accum = a_scr[slot].astype(jnp.float32) + g * g
    step = lr * g * jax.lax.rsqrt(accum + eps)
    p_scr[slot] = (p_scr[slot].astype(jnp.float32) - step).astype(p_scr.dtype)
    a_scr[slot] = accum.astype(a_scr.dtype)

    def writeback(j):
        dma(i, slot, j, p_scr, table_ref, False).start()
        dma(i, slot, j, a_scr, accum_ref, False).start()
    for_valid(i, writeback)

    @pl.when(i == nblocks - 1)
    def _():
        wait(i, slot, False)

        @pl.when(nblocks >= 2)
        def _():
            wait(i - 1, (i - 1) % 2, False)


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_rows", "interpret"),
    donate_argnums=(0, 1),
)
def scatter_adagrad_rows(
    table: jax.Array,
    accum: jax.Array,
    rows: jax.Array,
    grads: jax.Array,
    lr,
    eps: float = 1e-8,
    block_rows: int = 512,
    interpret: bool = False,
):
    """Fused AdaGrad RMW for UNIQUE rows: ``accum += g²; table -= lr * g *
    rsqrt(accum + eps)`` in one kernel launch (packed layout, both buffers
    donated/aliased). Rows ``>= capacity`` are padding and skipped. ``accum``
    must match ``table``'s shape/dtype (the shared-semaphore byte accounting
    relies on it). Exact merged-AdaGrad semantics for pre-merged rows —
    bit-identical to ``AdaGradAccess.apply_push_value`` on the same inputs.
    """
    n = rows.shape[0]
    c, s, lanes = table.shape
    if n % block_rows:
        raise ValueError(f"N={n} not a multiple of block_rows={block_rows}")
    if accum.shape != table.shape or accum.dtype != table.dtype:
        raise ValueError(
            f"accum {accum.shape}/{accum.dtype} must match table "
            f"{table.shape}/{table.dtype}"
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_rows, s, lanes), lambda i, *_: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, s, lanes), table.dtype),
            pltpu.VMEM((2, block_rows, s, lanes), accum.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_adagrad_kernel, eps=eps),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(accum.shape, accum.dtype),
        ),
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(
        rows.astype(jnp.int32),
        jnp.asarray(lr, jnp.float32).reshape(1),
        table,
        accum,
        grads.astype(table.dtype),
    )


# -------------------------------------------- slot-fused AdaGrad (1 tile) ---

def _adagrad_fused_kernel(rows_ref, lr_ref, count_ref, table_in, deltas_ref,
                          table_ref, scratch, read_sems, write_sems,
                          *, eps):
    """AdaGrad RMW where param AND accum live in ONE stored tile
    (``table[r] = [param_row, accum_row]`` along the sublane axis): one read
    DMA + one write DMA per row moves both, halving the issue-bound DMA
    count of the split-buffer kernel.

    Only ``rows[:count]`` are live (unique, in range); the scalar core does
    no per-slot work beyond them: a block past the count is an empty grid
    step, the last live block loops over its live rows only, and no slot is
    tested against the capacity."""
    del table_in
    lr = lr_ref[0]
    count = count_ref[0]
    R = scratch.shape[1]
    i = pl.program_id(0)
    nlive = pl.cdiv(count, R)  # live blocks; the last one may be partial

    def live_rows(b):
        return jnp.minimum(R, count - b * R)

    def start(b, slot, read):
        def one(j):
            pair = (table_ref.at[rows_ref[b * R + j]], scratch.at[slot, j])
            src, dst = pair if read else pair[::-1]
            sems = read_sems if read else write_sems
            pltpu.make_async_copy(src, dst, sems.at[slot]).start()

        _start_rows(live_rows(b), one)

    def wait(b, slot, read):
        # equal-sized copies on a shared semaphore: retired by descriptor
        # size, a chunk of rows at a time
        sems = read_sems if read else write_sems
        _wait_rows(scratch.at[slot, 0], scratch.at[slot, :min(_WAIT_CHUNK, R)],
                   sems.at[slot], live_rows(b))

    @pl.when(i < nlive)
    def _():
        @pl.when(i == 0)
        def _():
            start(0, 0, True)

        @pl.when(i + 1 < nlive)
        def _():
            slot_next = (i + 1) % 2

            # block i-1 used slot_next; its writebacks must land before we
            # overwrite the slot's scratch with new reads.
            @pl.when(i >= 1)
            def _():
                wait(i - 1, slot_next, False)

            start(i + 1, slot_next, True)

        slot = i % 2
        wait(i, slot, True)

        g = deltas_ref[...].astype(jnp.float32)  # [R, 1, 128]
        tile = scratch[slot].astype(jnp.float32)  # [R, 2, 128]
        accum = tile[:, 1:2, :] + g * g
        param = tile[:, 0:1, :] - lr * g * jax.lax.rsqrt(accum + eps)
        scratch[slot] = jnp.concatenate([param, accum], axis=1).astype(scratch.dtype)

        start(i, slot, False)

        @pl.when(i == nlive - 1)
        def _():
            wait(i, slot, False)

            @pl.when(i >= 1)
            def _():
                wait(i - 1, (i - 1) % 2, False)


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_rows", "interpret"),
    donate_argnums=(0,),
)
def scatter_adagrad_fused_rows(
    table: jax.Array,  # [C, 2, 128]: sublane 0 = param, sublane 1 = accum
    rows: jax.Array,
    grads: jax.Array,  # [N, 1, 128]
    lr,
    count,
    eps: float = 1e-8,
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Slot-fused AdaGrad RMW; see ``_adagrad_fused_kernel``.

    ``rows[:count]`` must be unique and in ``[0, capacity)`` (what
    ``store.merge_duplicate_rows`` puts first, counted by
    ``store.live_count``); every slot from ``count`` on is ignored, whatever
    id and gradient it holds."""
    n = rows.shape[0]
    c, s, lanes = table.shape
    if s != 2:
        raise ValueError(f"slot-fused table must be [C, 2, 128], got {table.shape}")
    if n % block_rows:
        raise ValueError(f"N={n} not a multiple of block_rows={block_rows}")

    def deltas_block(i, rows_ref, lr_ref, count_ref):
        # dead blocks re-use the last live block's index: nothing is fetched
        last = jnp.maximum(pl.cdiv(count_ref[0], block_rows) - 1, 0)
        return (jnp.minimum(i, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_rows, 1, lanes), deltas_block),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, 2, lanes), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_adagrad_fused_kernel, eps=eps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(
        rows.astype(jnp.int32),
        jnp.asarray(lr, jnp.float32).reshape(1),
        jnp.asarray(count, jnp.int32).reshape(1),
        table,
        grads.astype(table.dtype),
    )


def on_tpu() -> bool:
    """The one kernel dispatch switch: compiled Mosaic kernels exactly when
    the default backend is a TPU. Off the chip (CPU tests) the fused SGNS
    steps run the same kernels in interpret mode and the pull/push planes
    take their XLA twins in ``parallel/store.py``; on the chip nothing
    catches a kernel failure and retries another way."""
    return jax.default_backend() == "tpu"
