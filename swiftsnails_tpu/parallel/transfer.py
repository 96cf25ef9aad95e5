"""Explicit-collective pull/push — the Transfer/RPC layer, TPU-native.

The reference's universal substrate is an async RPC round trip (survey §3.4):
``Transfer::send`` -> ZeroMQ -> remote handler -> response callback, fanned out
per server and joined on a ``StateBarrier`` (``src/core/transfer/transfer.h:55-268``,
``global_pull_access.h:40-55``, ``global_push_access.h:36-53``).

Here the same two protocols are written as explicit XLA collectives inside
``shard_map`` over a ``(data, model)`` mesh, so the communication pattern is
visible and pinned rather than left to the SPMD partitioner:

* **pull**  (WORKER_PULL_REQUEST): every model shard gathers the rows it owns
  for the local data shard's keys, others contribute zeros; a ``psum`` over
  ``model`` assembles full rows on every device. One all-reduce over ICI
  replaces the per-server request/response fan-out.
* **push**  (WORKER_PUSH_REQUEST): the (rows, grads) batch is ``all_gather``\\ ed
  along ``data`` (workers "send" their gradients), then each model shard
  merges duplicates and applies its owned rows through the access method.
  Replica consistency over ``data`` is by construction: every replica sees the
  same gathered batch and computes the identical update.

:func:`swiftsnails_tpu.parallel.store.pull` / ``push`` are the pjit
auto-partitioned equivalents; tests assert both paths agree bit-for-bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftsnails_tpu.parallel.access import AccessMethod
from swiftsnails_tpu.parallel.comm import (
    all_gather_quantized,
    psum_quantized,
    resolve_comm_dtype,
    stochastic_wire,
)
from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from swiftsnails_tpu.parallel.store import TableState, apply_rows, merge_duplicate_rows

# Payload compression (``comm_dtype`` kwarg on every collective below): the
# (rows, grads) / assembled-row payloads quantize JUST before the
# all_gather/psum and dequantize into f32 accumulation at the owner shard —
# the master table and all shard-local math stay full precision. "float32"
# (the default) takes the original code path untouched, so existing callers
# are bit-identical. See parallel/comm.py for the wire formats and
# docs/SCALING.md for semantics; the int8 ``seed`` operand drives the
# stochastic rounding of gradients (replicated uint32 scalar, salted with
# the data-shard index inside the codec).


def _seed_operand(comm_dtype: str, seed):
    """(extra_args, extra_specs) for the optional int8/int4 dither seed."""
    if not stochastic_wire(comm_dtype):
        return (), ()
    s = jnp.uint32(0) if seed is None else jnp.asarray(seed).astype(jnp.uint32)
    return (s,), (P(),)


def _rows_per_shard(capacity: int, mesh: Mesh) -> int:
    model = mesh.shape[MODEL_AXIS]
    if capacity % model != 0:
        raise ValueError(f"capacity {capacity} not divisible by model axis {model}")
    return capacity // model


def bucket_capacity(local_n: int, model: int, slack: float) -> int:
    """Static per-sender bucket size for the owner-bucketed push.

    Mean occupancy after dedup is ``<= local_n / model`` under hashed (uniform)
    row placement; ``slack`` (default 2) puts the cap at slack x mean, rounded
    up to a multiple of 8 (sublane-friendly), clamped to ``local_n`` (at which
    point the bucketed path degenerates to the exact all_gather).
    """
    if model <= 1:
        return local_n
    cap = -(-int(slack * local_n) // model)
    # floor at one sublane group: slack * local_n < 1 must not produce a
    # zero-row bucket (empty buckets break the gather shapes downstream)
    cap = max(-(-cap // 8) * 8, 8)
    return min(cap, local_n)


def _compact_owned(uniq, merged, m, per, cap, invalid):
    """Select the rows of a deduped batch owned by model shard ``m``,
    compacted (stable, owned-first) into a static ``[cap]`` bucket.

    Returns ``(bucket_rows, bucket_grads, overflow)`` where ``overflow`` is
    the number of distinct owned rows that did not fit (their gradients are
    dropped by the caller — see :func:`push_collective_bucketed`).
    """
    local = uniq - m * per
    owned = (local >= 0) & (local < per)
    order = jnp.argsort(~owned, stable=True)  # owned first, original order
    take = order[:cap]
    ok = owned[take]
    b_rows = jnp.where(ok, uniq[take], invalid)
    mask = ok.reshape(ok.shape + (1,) * (merged.ndim - 1))
    b_grads = jnp.where(mask, merged[take], 0)
    overflow = jnp.maximum(owned.sum() - cap, 0)
    return b_rows, b_grads, overflow


def pull_collective(
    mesh: Mesh, state: TableState, rows: jax.Array,
    comm_dtype: str = "float32",
) -> jax.Array:
    """Sharded gather with explicit psum-over-model (pull protocol)."""
    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)

    def local_pull(table_shard, rows_local):
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_local - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        vals = table_shard.at[jnp.where(owned, local_ids, 0)].get(mode="promise_in_bounds")
        vals = jnp.where(owned[:, None], vals, 0)
        return psum_quantized(vals, MODEL_AXIS, comm_dtype)

    fn = jax.shard_map(
        local_pull,
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    with jax.named_scope("ssn_pull_collective"):
        return fn(state.table, rows)


def push_collective(
    mesh: Mesh,
    state: TableState,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    exact: bool = False,
    comm_dtype: str = "float32",
    seed=None,
) -> TableState:
    """Sharded scatter-update with explicit all_gather-over-data (push protocol).

    Uses the same fast/exact update paths as :func:`~swiftsnails_tpu.parallel.
    store.push`, applied per model shard, so both data planes stay equivalent.
    """
    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *dither):
        rows_all = lax.all_gather(rows_local, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            grads_local, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_all - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        local_ids = jnp.where(owned, local_ids, per)  # unowned -> out of range
        grads_all = jnp.where(owned[:, None], grads_all, 0)
        if not exact:
            fast = access.scatter_update(table_shard, slot_shards, local_ids, grads_all, lr)
            if fast is not None:
                return fast
        uniq, merged = merge_duplicate_rows(local_ids, grads_all, invalid_row=per)
        return apply_rows(table_shard, slot_shards, uniq, merged, access, lr)

    shard_spec = P(MODEL_AXIS, None)
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective"):
        table, slots = fn(state.table, dict(state.slots), rows, grads, *extra)
    return TableState(table=table, slots=slots)


# ------------------------------------------------------- packed variants ---
#
# Same two protocols over the packed [capacity, S, 128] layout: the local
# shard work inside shard_map goes through the row-DMA kernel data plane
# (ops/rowdma via store.pull_packed/push_packed) on TPU, XLA fallback on CPU.
# The cross-device movement is identical to the 2-D path: pull assembles
# full rows with one psum over `model`; push all_gathers the (rows, grads)
# batch over `data` and every model shard updates only the rows it owns.


def pull_collective_packed(
    mesh: Mesh, state, rows: jax.Array, comm_dtype: str = "float32",
) -> jax.Array:
    """Sharded packed gather -> [N, S, 128] (pull protocol)."""
    from swiftsnails_tpu.parallel.store import PackedTableState, pull_packed

    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)

    def local_pull(table_shard, rows_local):
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_local - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        shard_state = PackedTableState(table=table_shard, slots={})
        vals = pull_packed(shard_state, jnp.where(owned, local_ids, 0))
        vals = jnp.where(owned[:, None, None], vals, 0)
        return psum_quantized(vals, MODEL_AXIS, comm_dtype)

    fn = jax.shard_map(
        local_pull,
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None, None), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS, None, None),
        check_vma=False,
    )
    with jax.named_scope("ssn_pull_collective_packed"):
        return fn(state.table, rows)


def push_collective_packed(
    mesh: Mesh,
    state,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    comm_dtype: str = "float32",
    seed=None,
):
    """Sharded packed push: all_gather over data, row-DMA update of owned rows."""
    from swiftsnails_tpu.parallel.store import PackedTableState, push_packed

    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *dither):
        rows_all = lax.all_gather(rows_local, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            grads_local, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_all - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        local_ids = jnp.where(owned, local_ids, per)  # unowned -> padding
        grads_all = jnp.where(owned[:, None, None], grads_all, 0)
        shard_state = PackedTableState(table=table_shard, slots=slot_shards)
        new = push_packed(shard_state, local_ids, grads_all, access, lr)
        return new.table, dict(new.slots)

    shard_spec = P(MODEL_AXIS, None, None)
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective_packed"):
        table, slots = fn(state.table, dict(state.slots), rows, grads, *extra)
    return PackedTableState(table=table, slots=slots)


# -------------------------------------------- small-row packed variants ---
#
# The CTR plane's collective twins (VERDICT r3 missing #2): the [T, S, 128]
# small-row table (G logical rows per 128-lane tile, store.small_group)
# shards at TILE granularity over `model` — tile t lives on shard t // perT,
# so logical row r (tile r // G) is owned by shard (r // G) // perT, i.e.
# shards own CONTIGUOUS logical row ranges of perT * G rows. Inside each
# shard the row movement is the same tile-DMA pull / fused-AdaGrad RMW push
# the single-device plane runs (store.pull_packed_small/push_packed_small);
# across shards it is the identical two collectives as every other plane
# (psum over `model` on pull, all_gather over `data` on push). This is the
# distributed serving loop of the reference's LR/CTR tables
# (src/core/parameter/sparsetable.h:123-222) on the packed layout.


def _tiles_per_shard(state, mesh: Mesh, dim: int) -> tuple:
    """(tiles per model shard, logical rows per model shard, G)."""
    from swiftsnails_tpu.parallel.store import small_group

    g = small_group(dim)
    t = state.table.shape[0]
    model = mesh.shape[MODEL_AXIS]
    if t % model != 0:
        raise ValueError(
            f"small-row tile count {t} not divisible by model axis {model}")
    per_t = t // model
    return per_t, per_t * g, g


def pull_collective_packed_small(
    mesh: Mesh, state, rows: jax.Array, dim: int,
    comm_dtype: str = "float32",
) -> jax.Array:
    """Sharded small-row gather -> [N, dim] (pull protocol)."""
    from swiftsnails_tpu.parallel.store import PackedTableState, pull_packed_small

    _, per_rows, _ = _tiles_per_shard(state, mesh, dim)
    comm_dtype = resolve_comm_dtype(comm_dtype)

    def local_pull(table_shard, rows_local):
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_local - m * per_rows
        owned = (local_ids >= 0) & (local_ids < per_rows)
        shard_state = PackedTableState(table=table_shard, slots={})
        vals = pull_packed_small(
            shard_state, jnp.where(owned, local_ids, 0), dim)
        vals = jnp.where(owned[:, None], vals, 0)
        return psum_quantized(vals, MODEL_AXIS, comm_dtype)

    fn = jax.shard_map(
        local_pull,
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None, None), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    with jax.named_scope("ssn_pull_collective_packed_small"):
        return fn(state.table, rows)


def push_collective_packed_small(
    mesh: Mesh,
    state,
    rows: jax.Array,
    grads: jax.Array,  # [N, dim]
    access: AccessMethod,
    lr,
    dim: int,
    comm_dtype: str = "float32",
    seed=None,
):
    """Sharded small-row push: all_gather over data, fused RMW of owned rows."""
    from swiftsnails_tpu.parallel.store import PackedTableState, push_packed_small

    _, per_rows, _ = _tiles_per_shard(state, mesh, dim)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *dither):
        rows_all = lax.all_gather(rows_local, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            grads_local, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_all - m * per_rows
        owned = (local_ids >= 0) & (local_ids < per_rows)
        # unowned -> per_rows: maps to tile per_t == shard tile count, the
        # invalid row the local plane's merge already drops
        local_ids = jnp.where(owned, local_ids, per_rows)
        grads_all = jnp.where(owned[:, None], grads_all, 0)
        shard_state = PackedTableState(table=table_shard, slots=slot_shards)
        new, _ = push_packed_small(shard_state, local_ids, grads_all, access, lr, dim)
        return new.table, dict(new.slots)

    shard_spec = P(MODEL_AXIS, None, None)
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective_packed_small"):
        table, slots = fn(state.table, dict(state.slots), rows, grads, *extra)
    return PackedTableState(table=table, slots=slots)


# --------------------------------------------------- owner-bucketed push ---
#
# The all_gather push above moves every data shard's FULL (rows, grads) batch
# to every model shard, then masks to the ~1/model owned fraction — O(B*dim*
# data) received per device, the naive version of the survey's bucketed
# design (SURVEY §2.3 Transfer row: all_to_all of (key,grad) buckets by
# owner; reference shape: per-server request batching in
# src/core/parameter/global_push_access.h:58-99).
#
# Bucketed variant: the batch is replicated over `model` inside each data
# shard, so every sender can locally (a) merge duplicates, then (b) compact
# the rows owned by ITS OWN model index into a static [cap] bucket. The
# all_gather over `data` then carries cap rows instead of the full local
# batch — a ~model/slack traffic reduction, the exact sparse analog of
# reduce_scatter-by-owner. No model-axis collective is needed at all: the
# "send to owner" hop of the reference protocol is free here because the
# batch is already replicated over `model`.
#
# Static-shape overflow contract (same tradeoff as MoE expert-capacity
# dispatch): a bucket can hold at most `cap` DISTINCT owned rows; rows
# beyond that are dropped for the step and counted in the returned
# `dropped` scalar (replicated). With murmur-hashed placement the owned
# count concentrates at local_n/model (binomial), so slack=2 makes overflow
# probability astronomically small; cap == local_n (slack >= model) is
# byte-exact always. Callers surface `dropped` as a metric so a silent
# quality regression is impossible.


def push_collective_bucketed(
    mesh: Mesh,
    state: TableState,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    slack: float = 2.0,
    comm_dtype: str = "float32",
    seed=None,
):
    """Owner-bucketed sharded push. Returns ``(new_state, dropped)``."""
    per = _rows_per_shard(state.capacity, mesh)
    model = mesh.shape[MODEL_AXIS]
    local_n = rows.shape[0] // mesh.shape[DATA_AXIS]
    cap = bucket_capacity(local_n, model, slack)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    invalid = state.capacity
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *dither):
        m = lax.axis_index(MODEL_AXIS)
        uniq_l, merged_l = merge_duplicate_rows(rows_local, grads_local, invalid_row=invalid)
        b_rows, b_grads, overflow = _compact_owned(uniq_l, merged_l, m, per, cap, invalid)
        rows_all = lax.all_gather(b_rows, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            b_grads, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        local_ids = rows_all - m * per  # all owned-by-m or invalid padding
        owned = (local_ids >= 0) & (local_ids < per)
        local_ids = jnp.where(owned, local_ids, per)
        uniq, merged = merge_duplicate_rows(local_ids, grads_all, invalid_row=per)
        table, slots = apply_rows(table_shard, slot_shards, uniq, merged, access, lr)
        dropped = lax.psum(lax.psum(overflow, DATA_AXIS), MODEL_AXIS)
        return table, slots, dropped

    shard_spec = P(MODEL_AXIS, None)
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}, P()),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective_bucketed"):
        table, slots, dropped = fn(state.table, dict(state.slots), rows, grads,
                                   *extra)
    return TableState(table=table, slots=slots), dropped


# ------------------------------------------------- dedup'd packed planes ---
#
# The single-chip headline lever (the dedup kernels' one-DMA-per-distinct-row
# treatment, ops/fused_sgns.py) translated to the collective grouped plane
# (VERDICT r4 #4): each DATA shard builds a shard-local static unique list of
# its row ids, so the `model` psum on pull and the `data` all_gather on push
# carry ``u_cap`` merged rows instead of the full local batch. The cut is the
# STATIC shape ratio n_local/u_cap — verified from compiled psum+all-gather
# bytes (`tools/kernel_lab.py --dedup-traffic`: 4.00x at u_cap=1024, 8.00x at
# u_cap=512, both legs) — and is only real when the unique list does not
# overflow; the same lab asserts zero overflow on a block-ordered zipf window
# batch at the production duplicate rate (4.9% distinct). The reference's analogous
# dedup-before-transfer is the per-server key grouping of
# ``src/core/parameter/global_pull_access.h:58-72`` (one request per server
# carries each key once) and the duplicate merge of ``merge_push_value``
# (``sparsetable.h:176-179``).
#
# Static-capacity contract (same as the bucketed push): a shard's DISTINCT
# row count beyond ``u_cap`` overflows — overflow slots pull zero rows /
# drop their gradients for the step, and the count is returned so callers
# surface it as a metric. Semantics for in-cap rows are the DETERMINISTIC
# merged update, identical to the plain collective plane.


def _unique_static(rows: jax.Array, cap: int, invalid: int):
    """Shard-local static-size dedup.

    Returns ``(uniq [cap], inv [n], overflow)``: ``uniq`` holds the distinct
    row ids in sorted order (``invalid``-padded past the distinct count),
    ``inv[i]`` is the position of ``rows[i]`` in ``uniq`` — or ``cap`` (one
    past the end) when that row's group overflowed — and ``overflow`` counts
    the distinct rows that did not fit.
    """
    n = rows.shape[0]
    order = jnp.argsort(rows)
    sorted_rows = rows[order]
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_rows[1:] != sorted_rows[:-1]])
    grp = jnp.cumsum(is_first) - 1  # unique-group index per sorted position
    n_uniq = grp[-1] + 1
    uniq = jnp.full((cap,), invalid, rows.dtype).at[
        jnp.where(grp < cap, grp, cap)
    ].set(sorted_rows, mode="drop")
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.where(grp < cap, grp, cap).astype(jnp.int32))
    overflow = jnp.maximum(n_uniq - cap, 0)
    return uniq, inv, overflow


def pull_collective_packed_dedup(
    mesh: Mesh, state, rows: jax.Array, u_cap: int,
    comm_dtype: str = "float32",
):
    """Dedup'd sharded packed gather (pull protocol over a unique list).

    Returns ``(vals [N, S, 128], (uniq, inv), overflow)``; overflowed slots
    pull zeros. ``(uniq, inv)`` is the shard-local unique index (data-axis
    sharded) — pass it to :func:`push_collective_packed_dedup` for the same
    ``rows`` to skip the duplicate sort there and avoid double-counting the
    overflow metric.
    """
    from swiftsnails_tpu.parallel.store import PackedTableState, pull_packed

    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    invalid = state.capacity

    def local_pull(table_shard, rows_local):
        uniq, inv, overflow = _unique_static(rows_local, u_cap, invalid)
        m = lax.axis_index(MODEL_AXIS)
        local_ids = uniq - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        shard_state = PackedTableState(table=table_shard, slots={})
        vals = pull_packed(shard_state, jnp.where(owned, local_ids, 0))
        vals = jnp.where(owned[:, None, None], vals, 0)
        vals = psum_quantized(vals, MODEL_AXIS, comm_dtype)  # [u_cap, S, L]
        # expand unique rows back to their slots; overflow slots (inv ==
        # u_cap) read the appended zero row
        vals = jnp.concatenate(
            [vals, jnp.zeros((1,) + vals.shape[1:], vals.dtype)])
        out = vals.at[inv].get(mode="promise_in_bounds")
        return out, uniq, inv, lax.psum(overflow, DATA_AXIS)

    fn = jax.shard_map(
        local_pull,
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None, None), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS), P(DATA_AXIS), P()),
        check_vma=False,
    )
    with jax.named_scope("ssn_pull_collective_packed_dedup"):
        vals, uniq, inv, overflow = fn(state.table, rows)
    return vals, (uniq, inv), overflow


def push_collective_packed_dedup(
    mesh: Mesh,
    state,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    u_cap: int,
    index=None,
    comm_dtype: str = "float32",
    seed=None,
):
    """Sender-dedup'd packed push: duplicates merge into the unique list
    BEFORE the all_gather over ``data``. Returns ``(new_state, dropped)``.

    ``index``: the ``(uniq, inv)`` pair a prior
    :func:`pull_collective_packed_dedup` over the SAME ``rows`` returned —
    skips the duplicate shard-local sort and returns ``dropped = 0`` (the
    pull already counted those distinct-row overflow events; counting both
    legs would double the metric)."""
    from swiftsnails_tpu.parallel.store import PackedTableState, push_packed

    per = _rows_per_shard(state.capacity, mesh)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    invalid = state.capacity
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *rest):
        dither = rest[-1:] if extra else ()
        idx = rest[: len(rest) - len(dither)]
        if idx:
            uniq, inv = idx
            overflow = jnp.int32(0)
        else:
            uniq, inv, overflow = _unique_static(rows_local, u_cap, invalid)
            overflow = lax.psum(overflow, DATA_AXIS)
        merged = jnp.zeros(
            (u_cap,) + grads_local.shape[1:], grads_local.dtype
        ).at[inv].add(grads_local, mode="drop")
        rows_all = lax.all_gather(uniq, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            merged, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        m = lax.axis_index(MODEL_AXIS)
        local_ids = rows_all - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        local_ids = jnp.where(owned, local_ids, per)  # unowned -> padding
        grads_all = jnp.where(owned[:, None, None], grads_all, 0)
        shard_state = PackedTableState(table=table_shard, slots=slot_shards)
        new = push_packed(shard_state, local_ids, grads_all, access, lr)
        return new.table, dict(new.slots), overflow

    shard_spec = P(MODEL_AXIS, None, None)
    idx_args = () if index is None else tuple(index)
    idx_specs = () if index is None else (P(DATA_AXIS), P(DATA_AXIS))
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + idx_specs + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}, P()),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective_packed_dedup"):
        table, slots, dropped = fn(
            state.table, dict(state.slots), rows, grads, *idx_args, *extra)
    return PackedTableState(table=table, slots=slots), dropped


def push_collective_packed_bucketed(
    mesh: Mesh,
    state,
    rows: jax.Array,
    grads: jax.Array,
    access: AccessMethod,
    lr,
    slack: float = 2.0,
    comm_dtype: str = "float32",
    seed=None,
):
    """Owner-bucketed packed push ([N, S, 128] grads). Returns ``(state, dropped)``."""
    from swiftsnails_tpu.parallel.store import PackedTableState, push_packed

    per = _rows_per_shard(state.capacity, mesh)
    model = mesh.shape[MODEL_AXIS]
    local_n = rows.shape[0] // mesh.shape[DATA_AXIS]
    cap = bucket_capacity(local_n, model, slack)
    comm_dtype = resolve_comm_dtype(comm_dtype)
    slot_keys = sorted(state.slots.keys())
    invalid = state.capacity
    extra, extra_specs = _seed_operand(comm_dtype, seed)

    def local_push(table_shard, slot_shards, rows_local, grads_local, *dither):
        m = lax.axis_index(MODEL_AXIS)
        uniq_l, merged_l = merge_duplicate_rows(rows_local, grads_local, invalid_row=invalid)
        b_rows, b_grads, overflow = _compact_owned(uniq_l, merged_l, m, per, cap, invalid)
        rows_all = lax.all_gather(b_rows, DATA_AXIS, tiled=True)
        grads_all = all_gather_quantized(
            b_grads, DATA_AXIS, comm_dtype, stochastic=True,
            seed=dither[0] if dither else None)
        local_ids = rows_all - m * per
        owned = (local_ids >= 0) & (local_ids < per)
        local_ids = jnp.where(owned, local_ids, per)
        grads_all = jnp.where(owned[:, None, None], grads_all, 0)
        shard_state = PackedTableState(table=table_shard, slots=slot_shards)
        new = push_packed(shard_state, local_ids, grads_all, access, lr)
        dropped = lax.psum(lax.psum(overflow, DATA_AXIS), MODEL_AXIS)
        return new.table, dict(new.slots), dropped

    shard_spec = P(MODEL_AXIS, None, None)
    fn = jax.shard_map(
        local_push,
        mesh=mesh,
        in_specs=(shard_spec, {k: shard_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(shard_spec, {k: shard_spec for k in slot_keys}, P()),
        check_vma=False,
    )
    with jax.named_scope("ssn_push_collective_packed_bucketed"):
        table, slots, dropped = fn(state.table, dict(state.slots), rows, grads,
                                   *extra)
    return PackedTableState(table=table, slots=slots), dropped


# ---------------------------------------------------- tiered cache plane ---
#
# Slot-indexed twins for the host tier (swiftsnails_tpu/tiered): under a
# mesh the HBM working-set cache is a row-sharded plane like any other
# table, and because capacity and the invalid-row sentinel derive from
# table.shape[0], the pull/push collectives above already operate correctly
# in cache-slot space. The named wrappers pin that contract; the scatter
# below is the genuinely new mover — the batched host->device fault path
# installing gathered master rows shard-local (no resharding round trip).


def pull_collective_slots(mesh: Mesh, cache_state, slots: jax.Array,
                          comm_dtype: str = "float32") -> jax.Array:
    """Slot-indexed pull over a tiered cache plane.

    Identical protocol to :func:`pull_collective`; ``slots`` are cache-slot
    ids produced by the host-side remap (``tiered.TieredTable.remap``), and
    the per-shard row count derives from the CACHE capacity, so no resident
    assumptions leak in. The packed twins dispatch the same way — a cache
    plane is indistinguishable from a small table.
    """
    return pull_collective(mesh, cache_state, slots, comm_dtype=comm_dtype)


def push_collective_slots(
    mesh: Mesh, cache_state, slots: jax.Array, grads: jax.Array,
    access: AccessMethod, lr, comm_dtype: str = "float32", seed=None,
):
    """Slot-indexed push over a tiered cache plane (see
    :func:`pull_collective_slots`); the invalid-row sentinel is the cache
    budget, so padded/dropped slots behave exactly as on the resident path."""
    return push_collective(mesh, cache_state, slots, grads, access, lr,
                           comm_dtype=comm_dtype, seed=seed)


@functools.partial(jax.jit, static_argnums=(0,))
def scatter_slots_collective(mesh: Mesh, plane: jax.Array, slot_ids,
                             values) -> jax.Array:
    """Install faulted rows into a row-sharded cache plane, shard-local.

    ``slot_ids``/``values`` are replicated (the fault batch is tiny relative
    to the plane); each model shard keeps only its owned slice via an
    OOB-drop scatter, so the plane's sharding is preserved and no
    cross-shard traffic moves table bytes twice. Out-of-range ids
    (``plane.shape[0]`` padding) are dropped everywhere.
    """
    from swiftsnails_tpu.parallel.mesh import MODEL_AXIS as _M

    model = mesh.shape[_M]
    if plane.shape[0] % model:
        raise ValueError(
            f"cache budget {plane.shape[0]} not divisible by model axis {model}")
    per = plane.shape[0] // model
    spec = P(_M, *([None] * (plane.ndim - 1)))

    def body(shard, ids, vals):
        m = lax.axis_index(_M)
        local = ids - m * per
        local = jnp.where((local >= 0) & (local < per), local, per)
        return shard.at[local].set(vals.astype(shard.dtype), mode="drop")

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, P(), P()),
        out_specs=spec,
        check_vma=False,
    )
    with jax.named_scope("ssn_tier_fault_scatter"):
        return fn(plane, jnp.asarray(slot_ids), jnp.asarray(values))
