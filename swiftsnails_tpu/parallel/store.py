"""The sharded parameter store — the PS data plane, TPU-native.

Replaces the reference's entire parameter layer (SURVEY §2.5):

* ``SparseTable`` / ``SparseTableShard`` (lock-striped hashmaps,
  ``src/core/parameter/sparsetable.h``) -> one pre-initialized dense
  ``jax.Array`` of shape ``[capacity, dim]``, row-sharded over the mesh's
  ``model`` axis (the hashing trick: row = murmur(key) % capacity,
  :func:`swiftsnails_tpu.ops.hashing.hash_row`);
* ``GlobalPullAccess::pull_with_barrier`` (per-server RPC fan-out,
  ``global_pull_access.h:40-55``) -> :func:`pull`, an XLA gather whose
  cross-shard movement compiles to ICI collectives under pjit;
* ``GlobalPushAccess::push_with_barrier`` + server-side
  ``apply_push_value`` loop (``global_push_access.h:36-53``,
  ``server/init.h:115-135``) -> :func:`push`, a segment-sum duplicate merge
  followed by one gather-update-scatter of the batch's unique rows;
* ``merge_push_value`` duplicate-gradient combining
  (``sparsetable.h:176-179``) -> :func:`merge_duplicate_rows` (sort +
  segment-sum; additive, batch-wide, deterministic).

Design note (the central memory/performance decision): trainers differentiate
w.r.t. the *pulled rows* (a batch-sized tensor — the analog of the reference's
worker-side ``GlobalParamCache``) and call :func:`push` explicitly. Autodiff
through a ``[capacity, dim]`` gather would build table-shaped gradients, which
is a non-starter at the 1B-row Criteo config; this keeps every per-step tensor
O(batch), exactly like the reference's wire protocol.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from swiftsnails_tpu.parallel.access import AccessMethod, Slots
from swiftsnails_tpu.parallel.mesh import table_sharding


@contextlib.contextmanager
def _sharding_invariant_rng():
    """Pin the partitionable threefry lowering around table init.

    Under the default (non-partitionable) lowering, XLA specializes the
    random-bit computation to the ``out_shardings`` layout, so the same seed
    yields a DIFFERENT table on every mesh shape — which breaks mesh-shape
    invariance (a 1x1 and a 2x4 run could never match) and makes resharded
    restarts non-reproducible. The partitionable lowering is
    sharding-invariant by construction; scoping it here keeps every other
    RNG stream (samplers, dropout, dither) on the process-wide default."""
    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        yield
    finally:
        jax.config.update("jax_threefry_partitionable", old)


def _scoped(name: str):
    """Label a pull/push path for the compiled-HLO communication audit
    (``telemetry.audit`` groups collective bytes by these ``ssn_*`` scopes)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


class TableState(NamedTuple):
    """Sharded parameter table + row-aligned optimizer slots (a pytree)."""

    table: jax.Array  # [capacity, dim]
    slots: Slots  # each [capacity, dim]

    @property
    def capacity(self) -> int:
        return self.table.shape[0]

    @property
    def dim(self) -> int:
        return self.table.shape[1]


def create_table(
    capacity: int,
    dim: int,
    access: AccessMethod,
    mesh: Optional[Mesh] = None,
    dtype=jnp.float32,
    seed: int = 0,
    init_scale: Optional[float] = None,
) -> TableState:
    """Create a fully-initialized sharded table.

    Replaces lazy per-key ``init_param`` (``sparsetable.h:142-149``) with eager
    whole-table init — on TPU a pre-initialized dense table costs one pass of
    HBM writes and removes every data-dependent branch from the hot path.

    With a mesh, initialization runs *sharded* (jit with out_shardings), so no
    host ever materializes the full table — required at 1B-row capacities.
    """
    shape = (capacity, dim)

    if mesh is None:
        with _sharding_invariant_rng():
            return _init_table(shape, access, dtype, seed, init_scale)
    sharding = table_sharding(mesh)
    # enumerate slot keys without allocating (the table may be 1B rows)
    slot_spec = jax.eval_shape(lambda: access.init_slots(shape, dtype))
    with _sharding_invariant_rng():
        return _sharded_init(
            shape, access, dtype, seed, init_scale, sharding,
            tuple(sorted(slot_spec)))()


def _init_impl(shape, access, dtype, seed, init_scale):
    rng = jax.random.PRNGKey(seed)
    param = access.init_param(rng, shape, dtype)
    if init_scale is not None:
        param = param * init_scale
    return TableState(table=param, slots=access.init_slots(shape, dtype))


# jitted ONCE per (shape, access, ...) key: the old ``jax.jit(closure)()``
# form compiled afresh on every call — a fixed quarter-second XLA tax per
# ``TrainLoop.run`` that dominated short bench legs
_init_table = jax.jit(_init_impl, static_argnums=(0, 1, 2, 3, 4))


@functools.lru_cache(maxsize=64)
def _sharded_init(shape, access, dtype, seed, init_scale, sharding,
                  slot_keys):
    """Cached jit wrapper for the sharded-init path (``out_shardings`` is a
    jit parameter, so each distinct sharding needs its own wrapper)."""
    state_shardings = TableState(
        table=sharding, slots={k: sharding for k in slot_keys})
    return jax.jit(
        functools.partial(_init_impl, shape, access, dtype, seed, init_scale),
        out_shardings=state_shardings)


def pull(state: TableState, rows: jax.Array, access: Optional[AccessMethod] = None) -> jax.Array:
    """Gather rows from the table (``GlobalPullAccess`` equivalent).

    ``rows`` are table row ids (already hashed — see
    :func:`swiftsnails_tpu.ops.hashing.hash_row`). Under pjit with a
    row-sharded table, XLA lowers this to shard-local gathers + ICI
    collectives — the entire WORKER_PULL_REQUEST round trip (§3.4 of the
    survey) in one fused op.
    """
    with jax.named_scope("ssn_pull"):
        if isinstance(state.table, np.ndarray):
            # host master-backed state (table_tier: host, end of run): read
            # straight from host RAM — the full table may not fit a device
            vals = jnp.asarray(
                np.take(state.table, np.asarray(rows), axis=0))
        else:
            vals = state.table.at[rows].get(mode="promise_in_bounds")
        if access is not None:
            vals = access.get_pull_value(vals)
        return vals


def merge_duplicate_rows(
    rows: jax.Array, grads: jax.Array, invalid_row: int
) -> Tuple[jax.Array, jax.Array]:
    """Combine gradients of duplicate rows (``merge_push_value`` parity).

    Returns ``(uniq_rows, merged)`` of the same length as the input: slot
    ``i < n_unique`` holds a distinct row id and the sum of its gradients;
    remaining slots hold ``invalid_row`` (and zero gradient) so a subsequent
    ``mode='drop'`` scatter ignores them. Static shapes throughout — this is
    the jit-compatible replacement for per-key hashmap merging
    (``sparsetable.h:176-179``), and it makes duplicate handling additive and
    deterministic rather than last-write-wins.
    """
    n = rows.shape[0]
    order = jnp.argsort(rows)
    r = rows[order]
    g = grads[order]
    head = jnp.concatenate([jnp.ones((1,), dtype=bool), r[1:] != r[:-1]])
    seg = jnp.cumsum(head) - 1  # [n], segment id per sorted element
    merged = jax.ops.segment_sum(g, seg, num_segments=n)
    uniq = jnp.full((n,), invalid_row, dtype=rows.dtype)
    uniq = uniq.at[seg].set(r, mode="drop")  # duplicate writes carry equal values
    return uniq, merged


def live_count(uniq: jax.Array, invalid_row: int) -> jax.Array:
    """How many leading slots of a merged ``uniq`` hold a row to update.

    :func:`merge_duplicate_rows` sorts, so the distinct rows below
    ``invalid_row`` fill slots ``0..count-1`` and everything else (padding
    ids ``>= invalid_row``, the fill) comes after them: the count is all a
    kernel needs to know where the work ends
    (``ops/rowdma.scatter_adagrad_fused_rows``)."""
    return jnp.sum(uniq < invalid_row, dtype=jnp.int32)


def apply_rows(
    table: jax.Array,
    slots: "Slots",
    uniq: jax.Array,
    merged: jax.Array,
    access: AccessMethod,
    lr,
):
    """gather current rows/slots -> access update rule -> scatter back.

    Shared body of :func:`push` and the shard-local update in
    :func:`swiftsnails_tpu.parallel.transfer.push_collective`. ``uniq`` must
    contain each row at most once (see :func:`merge_duplicate_rows`), so the
    gather-update-scatter is race-free; out-of-range padding rows read as
    zeros and are dropped on write.
    """
    cur_param = table.at[uniq].get(mode="fill", fill_value=0)
    cur_slots = {k: v.at[uniq].get(mode="fill", fill_value=0) for k, v in slots.items()}
    new_param, new_slots = access.apply_push_value(cur_param, cur_slots, merged, lr)
    new_table = table.at[uniq].set(new_param, mode="drop")
    out_slots = {k: slots[k].at[uniq].set(new_slots[k], mode="drop") for k in slots}
    return new_table, out_slots


def push(
    state: TableState,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    exact: bool = False,
) -> TableState:
    """Apply sparse gradients (``GlobalPushAccess`` + server apply equivalent).

    Fast path (default): the access method's sort-free ``scatter_update``
    when it has one — for SGD bit-identical to the exact path, for AdaGrad
    the per-sample-accumulator variant (see ``AccessMethod.scatter_update``).

    Exact path (``exact=True`` or no scatter rule): merge duplicates
    (argsort + segment-sum, the reference's ``merge_push_value`` semantics)
    -> :func:`apply_rows`, each unique row touched exactly once.

    Under pjit either path compiles to the reduce/scatter collectives that
    replace every WORKER_PUSH_REQUEST (§3.4).
    """
    with jax.named_scope("ssn_push"):
        if not exact:
            fast = access.scatter_update(state.table, state.slots, rows, grads, lr)
            if fast is not None:
                table, slots = fast
                return TableState(table=table, slots=slots)
        uniq, merged = merge_duplicate_rows(rows, grads, invalid_row=state.capacity)
        table, slots = apply_rows(state.table, state.slots, uniq, merged, access, lr)
        return TableState(table=table, slots=slots)


def export_rows(state: TableState, rows: jax.Array) -> jax.Array:
    """Raw row read (no pull transform) — used by checkpoint/text export."""
    return state.table.at[rows].get(mode="fill", fill_value=0)


# ---------------------------------------------------- tiered cache plane ---
#
# Host-tier support (swiftsnails_tpu/tiered): the HBM working-set cache is a
# smaller table of the SAME layout, so pull/push above run verbatim in
# cache-slot space — capacity and the invalid-row sentinel already derive
# from table.shape[0]. The two jit'd movers below are the tier's fault/flush
# data plane on a single device (the mesh twin is
# transfer.scatter_slots_collective): an OOB-drop scatter that installs
# faulted rows (pad index == shape[0] drops the update) and a fill-0 gather
# for dirty-slot read-back. Callers bucket the index length (pow2) so the
# trace cache stays logarithmic in fault-batch size.


@jax.jit
def scatter_rows(plane: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    """Install rows into a cache plane: ``plane[idx] = vals`` with
    out-of-range indices dropped (the fault path's padding sentinel)."""
    return plane.at[idx].set(vals.astype(plane.dtype), mode="drop")


@jax.jit
def gather_rows(plane: jax.Array, idx: jax.Array) -> jax.Array:
    """Read rows back from a cache plane (dirty-slot flush); out-of-range
    padding reads zeros and is sliced off by the caller."""
    return plane.at[idx].get(mode="fill", fill_value=0)


# ------------------------------------------------ small-row packed plane ---
#
# CTR tables are narrow (Criteo W&D: table_dim 17; FM/FFM similar). The
# word2vec packed layout would burn a whole [1, 128] tile per row (7.5x
# memory at dim 17) — so until round 3 the CTR families ran on the 2-D XLA
# plane whose gather serializes at ~100-140 ns/row (VERDICT r2 missing #3).
# This plane packs G = 128 // stride logical rows per 128-lane tile
# (stride = smallest power-of-two lane group >= dim): row r lives in tile
# r // G at lanes (r % G) * stride. One tile DMA serves one logical row
# (issue-bound, same cost as a wide row), the memory waste drops to
# stride/dim, and the lane groups are disjoint — so merging duplicates BY
# TILE is exactly merging by row, and lanewise AdaGrad on a tile is exact
# per-row AdaGrad. Push is one fused kernel: scatter-add (SGD) or the
# in-kernel slot-math AdaGrad RMW (ops/rowdma.scatter_adagrad_rows).


def small_group(dim: int) -> int:
    """Logical rows per 128-lane tile for a width-``dim`` table."""
    if dim > 128:
        raise ValueError(f"small-row plane requires dim <= 128, got {dim}")
    g = 1
    while g < 128 and 128 // (2 * g) >= dim:
        g *= 2
    return g


def _fuse_small_slots(access: AccessMethod, dtype) -> bool:
    """Slot-fused storage: param + AdaGrad accum share one stored tile
    (``[T, 2, 128]``, sublane 0 = param, 1 = accum) so ONE DMA moves both —
    the RMW drops from 4 to 2 issue-bound copies per row
    (ops/rowdma.scatter_adagrad_fused_rows). Only when the slot dtype
    matches the table's (a bf16-slot config keeps the split layout)."""
    from swiftsnails_tpu.parallel.access import AdaGradAccess

    return isinstance(access, AdaGradAccess) and (
        access.slot_dtype is None or access.slot_dtype == dtype
    )


def create_packed_small_table(
    capacity: int,
    dim: int,
    access: AccessMethod,
    mesh: Optional[Mesh] = None,
    dtype=jnp.float32,
    seed: int = 0,
    init_scale: Optional[float] = None,
) -> PackedTableState:
    """[T, S, 128] table holding ``capacity`` logical ``dim``-rows, G per
    tile; S=2 with the AdaGrad accumulator fused in (see
    :func:`_fuse_small_slots`), else S=1 with separate slot arrays."""
    from swiftsnails_tpu.ops.rowdma import ROW_LANES

    g = small_group(dim)
    stride = ROW_LANES // g
    t = -(-capacity // g)  # round UP: trailing group slots are dead padding
    fused = _fuse_small_slots(access, dtype)
    shape = (t, 2 if fused else 1, ROW_LANES)

    def init():
        rng = jax.random.PRNGKey(seed)
        param = access.init_param(rng, (t, ROW_LANES), dtype, fan_in=dim)
        if init_scale is not None:
            param = param * init_scale
        lane = (jnp.arange(ROW_LANES) % stride) < dim
        param = jnp.where(lane[None, :], param, 0).reshape(t, 1, ROW_LANES)
        if fused:
            accum = jnp.zeros((t, 1, ROW_LANES), dtype)
            return PackedTableState(
                table=jnp.concatenate([param, accum], axis=1), slots={}
            )
        slots = access.init_slots((t, ROW_LANES), dtype)
        slots = {k: v.reshape(shape) for k, v in slots.items()}
        return PackedTableState(table=param, slots=slots)

    if mesh is None:
        with _sharding_invariant_rng():
            return jax.jit(init)()
    sharding = table_sharding(mesh)
    if fused:
        state_shardings = PackedTableState(table=sharding, slots={})
    else:
        slot_spec = jax.eval_shape(lambda: access.init_slots((t, ROW_LANES), dtype))
        state_shardings = PackedTableState(
            table=sharding, slots={k: sharding for k in slot_spec}
        )
    with _sharding_invariant_rng():
        return jax.jit(init, out_shardings=state_shardings)()


@_scoped("ssn_pull_packed_small")
def pull_packed_small(
    state: PackedTableState, rows: jax.Array, dim: int,
    block_rows: int = 512, kernel: bool = True,
) -> jax.Array:
    """Gather logical rows -> [N, dim] (tile DMA + in-register lane select).

    ``kernel=False`` forces the XLA gather — required when the table is a
    GLOBAL sharded array outside shard_map (e.g. text export under a mesh),
    where the row-DMA kernel cannot be auto-partitioned."""
    from swiftsnails_tpu.ops import rowdma
    from swiftsnails_tpu.ops.rowdma import ROW_LANES

    g = small_group(dim)
    stride = ROW_LANES // g
    n = rows.shape[0]
    tiles = rows // g
    if rowdma.on_tpu() and kernel:
        padded, _ = _pad_to_block(tiles, 0, block_rows)
        gathered = rowdma.gather_rows(state.table, padded, block_rows=block_rows)[:n]
    else:
        gathered = state.table.at[tiles].get(mode="promise_in_bounds")
    # sublane 0 holds the params (sublane 1, when present, is the fused
    # AdaGrad accumulator — it rides the same DMA and is sliced off here)
    groups = gathered[:, 0, :].reshape(n, g, stride)
    vals = jnp.take_along_axis(groups, (rows % g)[:, None, None], axis=1)
    return vals[:, 0, :dim]


@_scoped("ssn_push_packed_small")
def push_packed_small(
    state: PackedTableState,
    rows: jax.Array,
    grads: jax.Array,  # [N, dim]
    access: AccessMethod,
    lr,
    dim: int,
    block_rows: int = 512,
) -> Tuple[PackedTableState, jax.Array]:
    """Merge-by-tile -> one fused RMW kernel (SGD add / in-kernel AdaGrad).

    Returns the new state and the number of distinct tiles the push touched
    (:func:`live_count`): the fused AdaGrad kernel does per-slot work for
    that many slots only, and ``train_step`` reports it over the slots as
    ``push_live_share``."""
    from swiftsnails_tpu.ops.rowdma import ROW_LANES

    g = small_group(dim)
    stride = ROW_LANES // g
    n = rows.shape[0]
    t = state.table.shape[0]

    pad_w = stride - dim
    grads_s = jnp.pad(grads, ((0, 0), (0, pad_w))) if pad_w else grads
    onehot = (jnp.arange(g)[None, :] == (rows % g)[:, None]).astype(grads_s.dtype)
    tile_grads = (onehot[:, :, None] * grads_s[:, None, :]).reshape(n, ROW_LANES)
    tiles = rows // g
    # lane groups are disjoint, so tile-level merge == per-row merge
    uniq, merged = merge_duplicate_rows(tiles, tile_grads, invalid_row=t)
    live = live_count(uniq, t)
    new = _apply_merged_small(
        state, uniq, merged.reshape(n, 1, ROW_LANES), live, access, lr, block_rows)
    return new, live


def _apply_merged_small(
    state: PackedTableState,
    uniq: jax.Array,  # [N] merged tile ids: ``live`` distinct ones, then ``t``
    merged3: jax.Array,  # [N, 1, 128]
    live: jax.Array,
    access: AccessMethod,
    lr,
    block_rows: int,
) -> PackedTableState:
    """The update half of :func:`push_packed_small`: one RMW kernel on the
    chip, its XLA twin off it."""
    from swiftsnails_tpu.ops import rowdma
    from swiftsnails_tpu.ops.rowdma import (
        ROW_LANES, scatter_adagrad_fused_rows, scatter_adagrad_rows)
    from swiftsnails_tpu.parallel.access import AdaGradAccess, SgdAccess

    t = state.table.shape[0]
    fused_slots = state.table.shape[1] == 2 and not state.slots

    if fused_slots:
        if not _fuse_small_slots(access, state.table.dtype):
            raise ValueError(
                "slot-fused table pushed with a non-AdaGrad access method")
        eps = access.eps
        if not rowdma.on_tpu():
            g32 = merged3.astype(jnp.float32)
            safe = jnp.where(uniq < t, uniq, 0)  # invalid: computed, dropped
            cur = state.table.at[safe].get(
                mode="promise_in_bounds").astype(jnp.float32)
            accum = cur[:, 1:2, :] + g32 * g32
            param = cur[:, 0:1, :] - lr * g32 * jax.lax.rsqrt(accum + eps)
            new = jnp.concatenate([param, accum], axis=1).astype(state.table.dtype)
            table = state.table.at[uniq].set(new, mode="drop")
            return PackedTableState(table=table, slots={})
        uniq, _ = _pad_to_block(uniq, t, block_rows)
        if uniq.shape[0] != merged3.shape[0]:
            pad = uniq.shape[0] - merged3.shape[0]
            merged3 = jnp.concatenate(
                [merged3, jnp.zeros((pad, 1, ROW_LANES), merged3.dtype)]
            )
        table = scatter_adagrad_fused_rows(
            state.table, uniq, merged3, lr, live, eps=eps, block_rows=block_rows
        )
        return PackedTableState(table=table, slots={})

    if not rowdma.on_tpu():
        table, slots = apply_rows(state.table, state.slots, uniq, merged3, access, lr)
        return PackedTableState(table=table, slots=slots)

    uniq, n_real = _pad_to_block(uniq, t, block_rows)
    if uniq.shape[0] != merged3.shape[0]:
        pad = uniq.shape[0] - merged3.shape[0]
        merged3 = jnp.concatenate(
            [merged3, jnp.zeros((pad, 1, ROW_LANES), merged3.dtype)]
        )

    if isinstance(access, SgdAccess) and not state.slots:
        deltas = (-lr * merged3).astype(state.table.dtype)
        table = rowdma.scatter_add_rows(state.table, uniq, deltas, block_rows=block_rows)
        return PackedTableState(table=table, slots=state.slots)
    if (
        isinstance(access, AdaGradAccess)
        and set(state.slots) == {"accum"}
        and state.slots["accum"].dtype == state.table.dtype
    ):
        table, accum = scatter_adagrad_rows(
            state.table, state.slots["accum"], uniq, merged3, lr,
            eps=access.eps, block_rows=block_rows,
        )
        return PackedTableState(table=table, slots={"accum": accum})

    safe = jnp.where(uniq < t, uniq, 0)
    cur = rowdma.gather_rows(state.table, safe, block_rows=block_rows)
    cur_slots = {
        k: rowdma.gather_rows(v, safe, block_rows=block_rows)
        for k, v in state.slots.items()
    }
    new_param, new_slots = access.apply_push_value(cur, cur_slots, merged3, lr)
    table = rowdma.scatter_write_rows(
        state.table, uniq, new_param.astype(state.table.dtype), block_rows=block_rows)
    slots = {
        k: rowdma.scatter_write_rows(
            state.slots[k], uniq, new_slots[k].astype(state.slots[k].dtype),
            block_rows=block_rows)
        for k in state.slots
    }
    return PackedTableState(table=table, slots=slots)


# ------------------------------------------------------- packed variant ---
#
# The DMA-kernel data plane (ops/rowdma.py): rows live as [S, 128] tiles of
# a [capacity, S, 128] table so one key == one row DMA, replacing XLA's
# serialized gather/scatter (~100-140 ns/row on v5e) with pipelined row DMAs.
# Padding lanes hold zeros and stay zero: every access rule satisfies
# update(grad=0) == 0. Same pull/push contract as the 2-D table above.


class PackedTableState(NamedTuple):
    """Packed sharded table [capacity, S, 128] + row-aligned slots.

    The logical row width (dim) is not part of the state — trainers own it;
    padding lanes are zero by construction and stay zero.
    """

    table: jax.Array
    slots: Slots

    @property
    def capacity(self) -> int:
        return self.table.shape[0]


def create_packed_table(
    capacity: int,
    dim: int,
    access: AccessMethod,
    mesh: Optional[Mesh] = None,
    dtype=jnp.float32,
    seed: int = 0,
    init_scale: Optional[float] = None,
) -> PackedTableState:
    """Packed-layout twin of :func:`create_table` (padding lanes zeroed)."""
    from swiftsnails_tpu.ops.rowdma import ROW_LANES, packed_shape

    shape = packed_shape(capacity, dim)
    s = shape[1]

    if mesh is None:
        with _sharding_invariant_rng():
            return _init_packed_table(shape, dim, access, dtype, seed,
                                      init_scale)
    sharding = table_sharding(mesh)  # rows sharded over "model"; S,128 whole
    slot_spec = jax.eval_shape(
        lambda: access.init_slots((capacity, s * ROW_LANES), dtype))
    with _sharding_invariant_rng():
        return _sharded_packed_init(
            shape, dim, access, dtype, seed, init_scale, sharding,
            tuple(sorted(slot_spec)))()


def _init_packed_impl(shape, dim, access, dtype, seed, init_scale):
    from swiftsnails_tpu.ops.rowdma import ROW_LANES

    capacity, s, _ = shape
    rng = jax.random.PRNGKey(seed)
    # init as if [capacity, dim]: same distribution, packed placement
    # (fan_in=dim — scaling by the padded width s*128 would start the
    # table up to 128/dim too small, see test_path_quality)
    param = access.init_param(rng, (capacity, s * ROW_LANES), dtype, fan_in=dim)
    if init_scale is not None:
        param = param * init_scale
    lane = jnp.arange(s * ROW_LANES) < dim
    param = jnp.where(lane[None, :], param, 0).reshape(shape)
    slots = access.init_slots((capacity, s * ROW_LANES), dtype)
    slots = {k: v.reshape(shape) for k, v in slots.items()}
    return PackedTableState(table=param, slots=slots)


# same once-per-key jit caching as _init_table (see the comment there)
_init_packed_table = jax.jit(
    _init_packed_impl, static_argnums=(0, 1, 2, 3, 4, 5))


@functools.lru_cache(maxsize=64)
def _sharded_packed_init(shape, dim, access, dtype, seed, init_scale,
                         sharding, slot_keys):
    state_shardings = PackedTableState(
        table=sharding, slots={k: sharding for k in slot_keys})
    return jax.jit(
        functools.partial(
            _init_packed_impl, shape, dim, access, dtype, seed, init_scale),
        out_shardings=state_shardings)


def _pad_to_block(rows: jax.Array, invalid_row: int, block: int):
    n = rows.shape[0]
    padded = -(-n // block) * block
    if padded == n:
        return rows, n
    return jnp.concatenate(
        [rows, jnp.full((padded - n,), invalid_row, rows.dtype)]
    ), n


@_scoped("ssn_pull_packed")
def pull_packed(state: PackedTableState, rows: jax.Array,
                block_rows: int = 512) -> jax.Array:
    """Gather packed rows -> [N, S, 128] (pull protocol, DMA kernel on TPU)."""
    from swiftsnails_tpu.ops import rowdma

    if rowdma.on_tpu():
        padded, n = _pad_to_block(rows, 0, block_rows)
        out = rowdma.gather_rows(state.table, padded, block_rows=block_rows)
        return out[:n]
    return state.table.at[rows].get(mode="promise_in_bounds")


@_scoped("ssn_push_packed")
def push_packed(
    state: PackedTableState,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    block_rows: int = 512,
) -> PackedTableState:
    """Merge duplicates -> apply access rule -> row-DMA writeback.

    ``grads`` is [N, S, 128]. The merge (argsort + segment-sum) implements
    ``merge_push_value`` exactly; unique rows make the DMA writeback
    race-free. SGD takes the add-only RMW kernel (one launch); other access
    methods gather current rows+slots, apply, and write back.
    """
    from swiftsnails_tpu.ops import rowdma
    from swiftsnails_tpu.parallel.access import SgdAccess

    cap = state.capacity
    uniq, merged = merge_duplicate_rows(rows, grads, invalid_row=cap)
    if not rowdma.on_tpu():
        table, slots = apply_rows(state.table, state.slots, uniq, merged, access, lr)
        return PackedTableState(table=table, slots=slots)

    uniq, n = _pad_to_block(uniq, cap, block_rows)
    if n != merged.shape[0]:
        pad = uniq.shape[0] - merged.shape[0]
        merged = jnp.concatenate([merged, jnp.zeros((pad,) + merged.shape[1:], merged.dtype)])

    if isinstance(access, SgdAccess) and not state.slots:
        deltas = (-lr * merged).astype(state.table.dtype)
        table = rowdma.scatter_add_rows(state.table, uniq, deltas, block_rows=block_rows)
        return PackedTableState(table=table, slots=state.slots)

    safe = jnp.where(uniq < cap, uniq, 0)
    cur = rowdma.gather_rows(state.table, safe, block_rows=block_rows)
    cur_slots = {
        k: rowdma.gather_rows(v, safe, block_rows=block_rows)
        for k, v in state.slots.items()
    }
    new_param, new_slots = access.apply_push_value(cur, cur_slots, merged, lr)
    table = rowdma.scatter_write_rows(state.table, uniq, new_param.astype(state.table.dtype),
                                       block_rows=block_rows)
    slots = {
        k: rowdma.scatter_write_rows(state.slots[k], uniq,
                                     new_slots[k].astype(state.slots[k].dtype),
                                     block_rows=block_rows)
        for k in state.slots
    }
    return PackedTableState(table=table, slots=slots)
