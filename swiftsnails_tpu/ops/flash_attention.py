"""Causal attention in blocks, forward and backward, as Mosaic kernels.

``parallel/sequence.reference_attention`` materialises ``[H, L, L]`` scores:
at 16 heads and 8,192 positions 4.3 GB in float32, and again in the backward
pass. Here a ``[block, block]`` tile of scores lives in VMEM only, with the
online-softmax recurrence of ``sequence._block_update`` (running max, running
denominator, running output), and the block pairs above the diagonal are
never visited: their grid steps do nothing and fetch nothing (the index map
points them at the block the diagonal step already holds).

Keys and values may differ in width (latent attention: 192-wide keys, 128-wide
values); the accumulator takes the values' width.

* :func:`flash_attention` — ``q [H, L, Dk]``, ``k [H, L, Dk]``, ``v [H, L, Dv]``
  -> ``[H, L, Dv]`` float32, differentiable (``custom_vjp``): the forward
  kernel keeps the row-wise log-sum-exp, the backward pass is two kernels,
  one walking the key blocks of a query block (``dq``), one the query blocks
  of a key block (``dk``, ``dv``), both recomputing the tile's probabilities.
  Operands are rounded to ``dtype`` (bfloat16) for the MXU; scores, softmax
  statistics, accumulators and every result are float32.
* :func:`attention_flops` — the operations the causal product needs, for the
  benchmark's roofline.

Off the chip the same kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.rowdma import on_tpu

_NEG_INF = -1e30
_LANES = 128
_TRANS_B = (((1,), (1,)), ((), ()))  # a @ b.T
_TRANS_A = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


BLOCK = 512  # queries and keys a step: [BLOCK, BLOCK] scores in VMEM


def attention_flops(seq_len: int, heads: int, dk: int, dv: int) -> dict:
    """Operations of one call of each kernel, counting the position pairs a
    causal product needs (``L (L + 1) / 2``, not the masked half of the
    diagonal blocks): scores and weighted values forward; scores, ``dp`` and
    ``dq`` in the dq kernel; scores, ``dp``, ``dv`` and ``dk`` in the dkv
    kernel."""
    pairs = heads * seq_len * (seq_len + 1) / 2
    return {"fwd": 2.0 * pairs * (dk + dv),
            "dq": 2.0 * pairs * (2 * dk + dv),
            "dkv": 2.0 * pairs * (2 * dk + 2 * dv)}


def _scores(q, k, qi, kj, block, scale):
    """A tile's masked scores [block, block], float32."""
    s = jax.lax.dot_general(q, k, _TRANS_B, preferred_element_type=jnp.float32) * scale
    row = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col <= row, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, block, scale):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kj <= qi)
    def _():
        s = _scores(q_ref[...], k_ref[...], qi, kj, block, scale)
        m_old = m_ref[...]  # [block, 128], every lane alike
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == qi)  # the diagonal is a query block's last key block
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, block, scale):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kj <= qi)
    def _():
        k = k_ref[...]
        s = _scores(q_ref[...], k, qi, kj, block, scale)
        p = jnp.exp(s - lse_ref[:, :1])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _TRANS_B,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(kj == qi)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, block, scale):
    kj, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= kj)
    def _():
        q, do = q_ref[...], do_ref[...]
        s = _scores(q, k_ref[...], qi, kj, block, scale)
        p = jnp.exp(s - lse_ref[:, :1])
        dv_acc[...] += jax.lax.dot_general(p.astype(do.dtype), do, _TRANS_A,
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[...], _TRANS_B, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        dk_acc[...] += jax.lax.dot_general(ds.astype(q.dtype), q, _TRANS_A,
                                           preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _block_of(seq_len: int, block: int) -> int:
    block = min(block, seq_len)
    if seq_len % block:
        raise ValueError(f"sequence length {seq_len} is no multiple of the block {block}")
    return block


def _params(interpret):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _specs(block):
    """Block specs of a grid (head, i, j): ``outer(width)`` follows ``i``;
    ``inner(width, clamp)`` follows ``j`` clamped against ``i`` (a block on
    the far side of the diagonal is the diagonal's own: nothing new is
    fetched)."""
    outer = lambda w: pl.BlockSpec((None, block, w), lambda hh, i, j: (hh, i, 0))  # noqa: E731
    inner = lambda w, clamp: pl.BlockSpec(  # noqa: E731
        (None, block, w), lambda hh, i, j: (hh, clamp(j, i), 0))
    return outer, inner


def _forward(q, k, v, block, interpret):
    h, seq, dk = q.shape
    dv = v.shape[-1]
    block = _block_of(seq, block)
    n = seq // block
    rows, inner = _specs(block)
    cols = lambda w: inner(w, jnp.minimum)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, scale=dk ** -0.5),
        grid=(h, n, n),
        in_specs=[rows(dk), cols(dk), cols(dv)],
        out_specs=[rows(dv), rows(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((h, seq, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, seq, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        name="flash_attention_fwd",
        **_params(interpret),
    )(q, k, v)


def _backward(q, k, v, lse, do, delta, block, interpret):
    h, seq, dk = q.shape
    dv = v.shape[-1]
    block = _block_of(seq, block)
    n = seq // block
    scale = dk ** -0.5
    rows, inner = _specs(block)
    cols = lambda w: inner(w, jnp.minimum)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block=block, scale=scale),
        grid=(h, n, n),
        in_specs=[rows(dk), cols(dk), cols(dv), rows(dv), rows(_LANES), rows(_LANES)],
        out_specs=rows(dk),
        out_shape=jax.ShapeDtypeStruct((h, seq, dk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32)],
        name="flash_attention_dq",
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    # here the grid is (head, key block, query block): a query block before
    # the diagonal is the diagonal's own
    keys = rows
    qrows = lambda w: inner(w, jnp.maximum)  # noqa: E731
    dk_, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, block=block, scale=scale),
        grid=(h, n, n),
        in_specs=[qrows(dk), keys(dk), keys(dv), qrows(dv), qrows(_LANES), qrows(_LANES)],
        out_specs=[keys(dk), keys(dv)],
        out_shape=[jax.ShapeDtypeStruct((h, seq, dk), jnp.float32),
                   jax.ShapeDtypeStruct((h, seq, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        name="flash_attention_dkv",
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk_, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, block, dtype, interpret):
    return _forward(q.astype(dtype), k.astype(dtype), v.astype(dtype), block, interpret)[0]


def _attend_fwd(q, k, v, block, dtype, interpret):
    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    o, lse = _forward(q, k, v, block, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(block, dtype, interpret, res, do):
    q, k, v, o, lse = res
    delta = jnp.broadcast_to(jnp.sum(do * o, axis=-1, keepdims=True), lse.shape)
    return _backward(q, k, v, lse, do.astype(dtype), delta, block, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, block: int = BLOCK, dtype=jnp.bfloat16, interpret=None):
    """Causal softmax attention, scores scaled by ``Dk ** -0.5``.

    ``q``, ``k`` ``[H, L, Dk]`` and ``v`` ``[H, L, Dv]`` float32 -> ``[H, L, Dv]``
    float32, the operands rounded to ``dtype`` for the MXU. ``L`` is a
    multiple of ``block`` (or under it)."""
    if interpret is None:
        interpret = not on_tpu()
    return _attend(q, k, v, block, jnp.dtype(dtype), interpret)
