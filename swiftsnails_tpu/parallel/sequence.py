"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence models at all (survey §5: "no attention, no
notion of sequence length"); its only long axis is vocabulary. This module is
the framework's forward-looking long-context layer so transformer workloads
scale the same way the parameter table does — by adding a mesh axis:

* :func:`ring_attention` — blockwise flash-style attention where K/V shards
  rotate around the ``seq`` mesh axis via ``lax.ppermute`` (one ICI hop per
  step), with online-softmax accumulation. Memory per device stays
  O(L/P · L/P block), enabling sequences P× longer than one device's HBM
  would allow. Causal masking is applied per block pair.
* :func:`ulysses_attention` — the all-to-all alternative: reshard
  (seq-sharded, all heads) -> (full seq, head-sharded) with
  ``lax.all_to_all``, run exact local attention per head group, reshard
  back. Cheaper at moderate L (two all-to-alls), requires heads % P == 0.

Both are written against a named ``seq`` axis inside ``shard_map`` (mesh from
:func:`swiftsnails_tpu.parallel.mesh.make_mesh` with a ``seq`` axis) and are
differentiable (scan-based ring), so they drop into a jit'd train step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftsnails_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = False) -> jax.Array:
    """Dense softmax attention (the single-device ground truth).

    Shapes: q [B, Lq, H, Dk], k [B, Lk, H, Dk], v [B, Lk, H, Dv] -> [B, Lq, H, Dv].
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_update(q, k, v, o, l, m, block_mask):
    """One online-softmax accumulation step (flash-attention recurrence).

    q [B, Lq, H, Dk]; k [B, Lk, H, Dk]; v [B, Lk, H, Dv]; o running output
    [B, Lq, H, Dv]; l running
    denominator [B, H, Lq]; m running max [B, H, Lq]; block_mask [Lq, Lk]
    boolean or None.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Lq, Lk]
    if block_mask is not None:
        s = jnp.where(block_mask[None, None, :, :], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard: fully-masked rows keep m at -inf; exp underflows to 0 safely
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v
    )
    return o_new, l_new, m_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """shard_map body: q/k are the local sequence shards [B, Lb, H, Dk], v
    [B, Lb, H, Dv]; the running output takes the values' width (latent
    attention has 192-wide keys and 128-wide values)."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, lb, h, _ = q.shape

    o0 = jnp.zeros((b, lb, h, v.shape[-1]), jnp.float32)
    l0 = jnp.zeros((b, h, lb), dtype=jnp.float32)
    m0 = jnp.full((b, h, lb), _NEG_INF, dtype=jnp.float32)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    qf = q.astype(jnp.float32)

    def step(carry, i):
        o, l, m, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % axis_size  # whose K/V shard we hold this step
        if causal:
            # block-level causality on global positions
            q_pos = my_idx * lb + jnp.arange(lb)  # [Lb]
            k_pos = kv_idx * lb + jnp.arange(lb)
            block_mask = q_pos[:, None] >= k_pos[None, :]
        else:
            block_mask = None
        o2, l2, m2 = _block_update(
            qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32), o, l, m, block_mask
        )
        if causal:
            # skip blocks strictly in the future (all-masked): keep carry
            keep = (kv_idx <= my_idx)
            o2 = jnp.where(keep, o2, o)
            l2 = jnp.where(keep, l2, l)
            m2 = jnp.where(keep, m2, m)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o2, l2, m2, k_next, v_next), ()

    (o, l, m, _, _), _ = lax.scan(step, (o0, l0, m0, k, v), jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Ring attention over the ``seq`` mesh axis.

    Inputs are globally [B, L, H, D] sharded on L; output has the same
    sharding. L must divide evenly by the seq axis size.
    """
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool):
    """shard_map body: seq-sharded in, all-to-all to head-sharded, exact
    attention over the full sequence, and back."""
    axis_size = lax.psum(1, axis_name)

    def seq_to_heads(x):  # [B, Lb, H, D] -> [B, L, H/P, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # [B, L, H/P, D] -> [B, Lb, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = reference_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(out)


def ulysses_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence-parallel attention.

    Requires num_heads % seq_axis_size == 0.
    """
    if q.shape[2] % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"heads {q.shape[2]} not divisible by {axis_name} axis {mesh.shape[axis_name]}"
        )
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
