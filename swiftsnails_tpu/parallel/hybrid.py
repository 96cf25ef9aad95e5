"""Hybrid head/tail placement: replicate the zipf head, shard the tail.

Uniform hash sharding (the reference's ``hashfrag``) treats every row the
same, so the zipf head of a skewed vocabulary pays gather/scatter collective
indirection on every substep even though a handful of rows absorb most of
the traffic. Parallax's observation (PAPERS.md) is that placement should
follow sparsity: rows accessed densely want *replication* + a dense
gradient all-reduce (no indirection, no per-row ids on the wire), rows
accessed sparsely want the sharded pull/push protocol.

This module implements that split on top of the existing store/transfer
planes:

* **head** — the first ``cut`` logical rows, replicated on every device
  (``P()``). Pulls are shard-local gathers (ZERO collective bytes); pushes
  scatter-add the batch gradients into a dense ``[cut, ...]`` f32 buffer and
  reduce it once over ``data`` — through the same quantized wire options
  (:func:`~swiftsnails_tpu.parallel.comm.reduce_sum_quantized`) as the
  sharded path.
* **tail** — everything past the cut, kept in today's model-sharded layout.
  Row ids are remapped to *tail slot space* (``row - cut``; head rows map to
  the tail's invalid sentinel, mirroring the tiered remap pattern) and flow
  through the unmodified collective twins. The packed plane additionally
  routes through the dedup twins with a statically smaller unique capacity
  (``tail_cap``) sized from the head's access coverage — this is where the
  wire bytes actually shrink: collective payloads are static shapes, so
  only a statically smaller tail batch cuts audited exchange bytes.

``HybridTableState`` carries ONLY array leaves (head plane, head slots,
tail table state) so it is a well-formed jit/scan pytree; all static
geometry (cut, layout, group) is derived from the leaf shapes or passed by
the caller. Checkpoints never see this type: :func:`merge_table` rebuilds
the uniform layout bit-exactly (split/merge are value-preserving slices
along the stored leading axis), so serving, tiered mode, and resume stay
transparent — see framework/checkpoint.py.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftsnails_tpu.parallel.access import AccessMethod
from swiftsnails_tpu.parallel.comm import (
    reduce_scatter_quantized,
    reduce_sum_quantized,
    resolve_comm_dtype,
)
from swiftsnails_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    replicated,
    table_sharding,
)
from swiftsnails_tpu.parallel.store import PackedTableState, TableState
from swiftsnails_tpu.parallel.transfer import (
    _seed_operand,
    pull_collective,
    pull_collective_packed_dedup,
    push_collective,
    push_collective_packed_bucketed,
    push_collective_packed_dedup,
    push_collective_packed_small,
    pull_collective_packed_small,
)

ROW_LANES = 128


class HybridTableState(NamedTuple):
    """Split table: replicated head plane + model-sharded tail state.

    ``head`` is the stored-layout prefix (``[cut, dim]`` dense,
    ``[cut, S, 128]`` packed, ``[cut_tiles, S, 128]`` small-row);
    ``head_slots`` are the matching optimizer-slot prefixes; ``tail`` is a
    regular :class:`TableState` / :class:`PackedTableState` over the
    remaining rows. Only array leaves — safe as a jit donation target and a
    ``lax.scan`` carry.
    """

    head: jax.Array
    head_slots: Dict[str, jax.Array]
    tail: Union[TableState, PackedTableState]


def is_hybrid(state) -> bool:
    return isinstance(state, HybridTableState)


# ------------------------------------------------------------ split/merge ---


def split_table(state, cut: int, mesh=None, group: int = 1) -> HybridTableState:
    """Uniform layout -> hybrid, value-preserving (eager, outside jit).

    ``cut`` counts LOGICAL rows; for the small-row plane it must be a
    multiple of ``group`` so the split lands on a tile boundary (the slice
    index is ``cut // group`` stored tiles). Head leaves are replicated,
    tail leaves keep the model-axis table sharding.
    """
    if cut % group:
        raise ValueError(f"cut {cut} not aligned to small-row group {group}")
    row_cut = cut // group
    head = state.table[:row_cut]
    head_slots = {k: v[:row_cut] for k, v in state.slots.items()}
    tail_table = state.table[row_cut:]
    tail_slots = {k: v[row_cut:] for k, v in state.slots.items()}
    if mesh is not None:
        rep, shard = replicated(mesh), table_sharding(mesh)
        head = jax.device_put(head, rep)
        head_slots = {k: jax.device_put(v, rep) for k, v in head_slots.items()}
        tail_table = jax.device_put(tail_table, shard)
        tail_slots = {k: jax.device_put(v, shard) for k, v in tail_slots.items()}
    tail = state._replace(table=tail_table, slots=tail_slots)
    return HybridTableState(head=head, head_slots=head_slots, tail=tail)


def merge_table(hs: HybridTableState, mesh=None):
    """Hybrid -> uniform layout, bit-exact inverse of :func:`split_table`.

    The concat happens HOST-side: a device ``jnp.concatenate`` of a
    replicated head with a model-sharded tail is exactly the mixed-lineage
    GSPMD shape XLA miscompiles (docs/SCALING.md "sharp edges"; the same
    hazard ``_mesh_safe_cat`` works around in the word2vec model). Merge is
    an eager boundary op (checkpoint/export/end-of-run), so the host
    round-trip costs nothing on the training path.
    """
    import numpy as np

    def cat(a, b):
        return np.concatenate([np.asarray(a), np.asarray(b)], axis=0)

    table = cat(hs.head, hs.tail.table)
    slots = {k: cat(hs.head_slots[k], v) for k, v in hs.tail.slots.items()}
    if mesh is not None:
        shard = table_sharding(mesh)
        table = jax.device_put(table, shard)
        slots = {k: jax.device_put(v, shard) for k, v in slots.items()}
    else:
        table = jnp.asarray(table)
        slots = {k: jnp.asarray(v) for k, v in slots.items()}
    return hs.tail._replace(table=table, slots=slots)


# ------------------------------------------------------------- tail remap ---


def tail_ids(rows: jax.Array, cut: int, tail_sentinel) -> jax.Array:
    """Row ids -> tail slot space: ``row - cut`` for tail rows, the tail's
    invalid sentinel for head rows (the collective twins own-mask them to
    no-ops, mirroring the tiered remap's treatment of out-of-cache ids).
    A uniform-space invalid sentinel (``capacity``) lands on the tail
    sentinel by construction: ``capacity - cut == tail_capacity``."""
    return jnp.where(rows >= cut, rows - cut, tail_sentinel)


# -------------------------------------------------------------- head pull ---
#
# The head plane is replicated, so a pull is a shard-local gather — no
# collective is emitted and the comm audit sees zero bytes for it. Rows at
# or past the cut (tail rows, pad sentinels) read zero; the combined value
# is head_vals + tail_vals since exactly one side is nonzero per row.


def head_pull(mesh: Mesh, head: jax.Array, rows: jax.Array,
              layout: str, dim: int = 0, group: int = 1) -> jax.Array:
    cut_t = head.shape[0]  # rows (dense/packed) or tiles (small)

    def local(head, rows):
        if layout == "small":
            tiles = rows // group
            ok = (rows >= 0) & (tiles < cut_t)
            safe = jnp.clip(tiles, 0, cut_t - 1)
            gathered = head.at[safe].get(mode="promise_in_bounds")
            stride = ROW_LANES // group
            groups = gathered[:, 0, :].reshape(-1, group, stride)
            vals = jnp.take_along_axis(
                groups, (rows % group)[:, None, None], axis=1)[:, 0, :dim]
            return jnp.where(ok[:, None], vals, 0)
        ok = (rows >= 0) & (rows < cut_t)
        safe = jnp.clip(rows, 0, cut_t - 1)
        vals = head.at[safe].get(mode="promise_in_bounds")
        mask = ok[:, None, None] if head.ndim == 3 else ok[:, None]
        return jnp.where(mask, vals, 0)

    out_spec = P(DATA_AXIS, None, None) if (
        layout == "packed") else P(DATA_AXIS, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=out_spec,
        check_vma=False,
    )
    with jax.named_scope("ssn_hybrid_head_pull"):
        return fn(head, rows)


# -------------------------------------------------------------- head push ---
#
# All data shards contribute gradients for the same replicated rows, so the
# owner-exclusive psum_quantized contract does NOT hold here — the dense
# reduce goes through comm.reduce_sum_quantized (f32 psum, or quantize-
# per-shard + all_gather + f32 sum for bf16/int8, same stochastic-rounding
# dither as the sharded push wire). Scatter-adds use mode="drop": tail rows
# and pad sentinels index past the head buffer and fall out naturally, so
# callers pass the UNSPLIT (rows, grads) batch. Duplicate rows merge in the
# scatter-add before the optimizer update — the same merge-before-update
# semantics as the sharded twins' merge_duplicate_rows.


def head_push(mesh: Mesh, head: jax.Array, head_slots: Dict[str, jax.Array],
              rows: jax.Array, grads: jax.Array, access: AccessMethod, lr,
              layout: str, dim: int = 0, group: int = 1,
              comm_dtype: str = "float32", seed=None, zero: bool = False):
    comm_dtype = resolve_comm_dtype(comm_dtype)
    data = mesh.shape[DATA_AXIS]
    cut_t = head.shape[0]
    slot_keys = sorted(head_slots)
    extra, extra_specs = _seed_operand(comm_dtype, seed)
    fused_small = (
        layout == "small" and head.ndim == 3 and head.shape[1] == 2
        and not head_slots
    )
    # duplicate-merge parity per layout: the packed/small planes' sharded
    # twins merge duplicates BEFORE the optimizer update (apply_push_value on
    # merged grads), but the 2-D dense plane updates through the per-sample
    # accumulator variant (AdaGradAccess.scatter_update: ``accum += Σ g_i²``,
    # then one step at the final accumulator). The head must follow whichever
    # rule its tail/uniform baseline uses or hybrid-vs-uniform drifts on
    # every duplicated hot row.
    per_sample = layout == "dense" and "accum" in slot_keys
    # ZeRO update sharding (arXiv 2004.13336): the summed grad arrives via
    # reduce-scatter, each data shard updates only its owned 1/data row
    # slice of the head plane, and only the PARAM slice is all-gathered back
    # (exact f32 concat — bit-identical to the replicated update). Slot
    # planes stay resident as shards (out spec P(data)): that is the HBM
    # win. The param must stay replicated because head_pull is a
    # zero-collective local gather.
    if zero and cut_t % data:
        raise ValueError(
            f"optimizer_sharding: zero needs head rows ({cut_t}) aligned to "
            f"the data axis ({data}); widen placement alignment")

    def local(head, slots, rows, grads, *dither):
        if layout == "small":
            stride = ROW_LANES // group
            pad_w = stride - dim
            g_s = jnp.pad(grads, ((0, 0), (0, pad_w))) if pad_w else grads
            onehot = (jnp.arange(group)[None, :]
                      == (rows % group)[:, None]).astype(g_s.dtype)
            flat = (onehot[:, :, None] * g_s[:, None, :]).reshape(-1, ROW_LANES)
            idx = jnp.where(rows >= 0, rows // group, cut_t)
            buf = jnp.zeros((cut_t, ROW_LANES), jnp.float32).at[idx].add(
                flat.astype(jnp.float32), mode="drop")
        else:
            idx = jnp.where(rows >= 0, rows, cut_t)
            buf = jnp.zeros((cut_t,) + grads.shape[1:], jnp.float32).at[
                idx].add(grads.astype(jnp.float32), mode="drop")

        if zero:
            own = cut_t // data
            p = lax.dynamic_slice_in_dim(
                head, lax.axis_index(DATA_AXIS) * own, own, axis=0)

            def reduce(b, s):
                return reduce_scatter_quantized(
                    b, DATA_AXIS, comm_dtype, axis_size=data,
                    stochastic=True, seed=s)
        else:
            p = head

            def reduce(b, s):
                return reduce_sum_quantized(
                    b, DATA_AXIS, comm_dtype, axis_size=data,
                    stochastic=True, seed=s)

        tot = reduce(buf, dither[0] if dither else None)
        if per_sample:
            buf2 = jnp.zeros((cut_t,) + grads.shape[1:], jnp.float32).at[
                idx].add(jnp.square(grads.astype(jnp.float32)), mode="drop")
            tot2 = reduce(
                buf2, dither[0] + jnp.uint32(1) if dither else None)
            accum = slots["accum"].astype(jnp.float32) + tot2
            step = lr * tot * lax.rsqrt(accum + access.eps)
            new_p = p - step.astype(p.dtype)
            out = {"accum": accum.astype(slots["accum"].dtype)}
            new_s = {k: out.get(k, slots[k]) for k in slot_keys}
        elif fused_small:
            cur = p.astype(jnp.float32)
            accum = cur[:, 1, :] + tot * tot
            param = cur[:, 0, :] - lr * tot * lax.rsqrt(accum + access.eps)
            new_p = jnp.stack([param, accum], axis=1).astype(p.dtype)
            new_s = {}
        else:
            merged = tot.reshape(
                (p.shape[0], 1, ROW_LANES)) if layout == "small" else tot
            new_p, ns = access.apply_push_value(p, slots, merged, lr)
            new_s = {k: ns[k] for k in slot_keys}
        if zero:
            new_p = lax.all_gather(new_p, DATA_AXIS, tiled=True)
        return new_p, new_s

    slot_spec = P(DATA_AXIS) if zero else P()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), {k: slot_spec for k in slot_keys},
                  P(DATA_AXIS), P(DATA_AXIS)) + extra_specs,
        out_specs=(P(), {k: slot_spec for k in slot_keys}),
        check_vma=False,
    )
    scope = "ssn_zero_head_push" if zero else "ssn_hybrid_head_push"
    with jax.named_scope(scope):
        return fn(head, dict(head_slots), rows, grads, *extra)


# ------------------------------------------------------------ dense plane ---


def pull_hybrid(mesh: Mesh, hs: HybridTableState, rows: jax.Array,
                comm_dtype: str = "float32") -> jax.Array:
    """Hybrid twin of transfer.pull_collective over the 2-D dense plane."""
    cut = hs.head.shape[0]
    head_vals = head_pull(mesh, hs.head, rows, layout="dense")
    t_ids = tail_ids(rows, cut, hs.tail.capacity)
    tail_vals = pull_collective(mesh, hs.tail, t_ids, comm_dtype=comm_dtype)
    return head_vals + tail_vals


def push_hybrid(mesh: Mesh, hs: HybridTableState, rows: jax.Array,
                grads: jax.Array, access: AccessMethod, lr,
                exact: bool = False, comm_dtype: str = "float32",
                seed=None, zero: bool = False) -> HybridTableState:
    cut = hs.head.shape[0]
    t_ids = tail_ids(rows, cut, hs.tail.capacity)
    tail = push_collective(mesh, hs.tail, t_ids, grads, access, lr,
                           exact=exact, comm_dtype=comm_dtype, seed=seed)
    head, head_slots = head_push(
        mesh, hs.head, hs.head_slots, rows, grads, access, lr,
        layout="dense", comm_dtype=comm_dtype, seed=seed, zero=zero)
    return HybridTableState(head=head, head_slots=head_slots, tail=tail)


# ----------------------------------------------------------- packed plane ---
#
# The packed tail rides the dedup twins with a static ``tail_cap`` unique
# capacity sized from the head's coverage (placement.tail_cap): the psum /
# all_gather payloads shrink from [n_local, S, 128] to [tail_cap, S, 128].
# This is the structural byte win — the head absorbs most accesses, so a
# small tail_cap still fits the distinct tail rows of a batch; overflow is
# counted (rows drop their update, never corrupt) exactly like the dedup
# lane.


def pull_hybrid_packed(mesh: Mesh, hs: HybridTableState, rows: jax.Array,
                       tail_cap: int, comm_dtype: str = "float32"):
    """-> (vals [N, S, 128], tail (uniq, inv) index, overflow)."""
    cut = hs.head.shape[0]
    head_vals = head_pull(mesh, hs.head, rows, layout="packed")
    t_ids = tail_ids(rows, cut, hs.tail.capacity)
    tail_vals, index, overflow = pull_collective_packed_dedup(
        mesh, hs.tail, t_ids, tail_cap, comm_dtype=comm_dtype)
    return head_vals + tail_vals, index, overflow


def push_hybrid_packed(mesh: Mesh, hs: HybridTableState, rows: jax.Array,
                       grads: jax.Array, access: AccessMethod, lr,
                       tail_cap: int, index=None,
                       comm_dtype: str = "float32", seed=None,
                       zero: bool = False):
    """-> (new_state, dropped). ``index`` reuses a pull's (uniq, inv)."""
    cut = hs.head.shape[0]
    t_ids = tail_ids(rows, cut, hs.tail.capacity)
    tail, dropped = push_collective_packed_dedup(
        mesh, hs.tail, t_ids, grads, access, lr, tail_cap, index=index,
        comm_dtype=comm_dtype, seed=seed)
    head, head_slots = head_push(
        mesh, hs.head, hs.head_slots, rows, grads, access, lr,
        layout="packed", comm_dtype=comm_dtype, seed=seed, zero=zero)
    return HybridTableState(head=head, head_slots=head_slots, tail=tail), dropped


def push_hybrid_packed_bucketed(mesh: Mesh, hs: HybridTableState,
                                rows: jax.Array, grads: jax.Array,
                                access: AccessMethod, lr,
                                slack: float = 2.0,
                                comm_dtype: str = "float32", seed=None,
                                zero: bool = False):
    cut = hs.head.shape[0]
    t_ids = tail_ids(rows, cut, hs.tail.capacity)
    tail, dropped = push_collective_packed_bucketed(
        mesh, hs.tail, t_ids, grads, access, lr, slack=slack,
        comm_dtype=comm_dtype, seed=seed)
    head, head_slots = head_push(
        mesh, hs.head, hs.head_slots, rows, grads, access, lr,
        layout="packed", comm_dtype=comm_dtype, seed=seed, zero=zero)
    return HybridTableState(head=head, head_slots=head_slots, tail=tail), dropped


# -------------------------------------------------------- small-row plane ---


def pull_hybrid_packed_small(mesh: Mesh, hs: HybridTableState,
                             rows: jax.Array, dim: int,
                             comm_dtype: str = "float32") -> jax.Array:
    from swiftsnails_tpu.parallel.store import small_group

    g = small_group(dim)
    cut = hs.head.shape[0] * g
    sentinel = hs.tail.table.shape[0] * g
    head_vals = head_pull(mesh, hs.head, rows, layout="small", dim=dim, group=g)
    t_ids = tail_ids(rows, cut, sentinel)
    tail_vals = pull_collective_packed_small(
        mesh, hs.tail, t_ids, dim, comm_dtype=comm_dtype)
    return head_vals + tail_vals


def push_hybrid_packed_small(mesh: Mesh, hs: HybridTableState,
                             rows: jax.Array, grads: jax.Array,
                             access: AccessMethod, lr, dim: int,
                             comm_dtype: str = "float32", seed=None,
                             zero: bool = False):
    from swiftsnails_tpu.parallel.store import small_group

    g = small_group(dim)
    cut = hs.head.shape[0] * g
    sentinel = hs.tail.table.shape[0] * g
    t_ids = tail_ids(rows, cut, sentinel)
    tail = push_collective_packed_small(
        mesh, hs.tail, t_ids, grads, access, lr, dim,
        comm_dtype=comm_dtype, seed=seed)
    head, head_slots = head_push(
        mesh, hs.head, hs.head_slots, rows, grads, access, lr,
        layout="small", dim=dim, group=g, comm_dtype=comm_dtype, seed=seed,
        zero=zero)
    return HybridTableState(head=head, head_slots=head_slots, tail=tail)
