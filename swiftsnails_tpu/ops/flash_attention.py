"""Attention in blocks, forward and backward, as Mosaic kernels: causal, or
under the block-diffusion training mask; one key/value head per query head or
per group of them.

``parallel/sequence.reference_attention`` materialises ``[H, L, L]`` scores:
at 16 heads and 8,192 positions 4.3 GB in float32, and again in the backward
pass. Here a ``[block, block]`` tile of scores lives in VMEM only, with the
online-softmax recurrence of ``sequence._block_update`` (running max, running
denominator, running output), and the block pairs above the diagonal are
never visited: their grid steps do nothing and fetch nothing (the index map
points them at the block the diagonal step already holds).

Keys and values may differ in width (latent attention: 192-wide keys, 128-wide
values); the accumulator takes the values' width. With fewer key/value heads
than query heads (grouped queries) query head ``i`` reads key/value head ``i //
group`` through the index maps, and the ``dkv`` kernel walks the group's query
heads one after another into the same accumulators: keys and values are never
copied out ``group`` times.

Which tiles a query tile visits, in which order, and which pairs of a visited
tile count, is a mask's (:class:`Causal`, :class:`BlockDiffusion`); the three
kernels' bodies are the same under either. Block diffusion (Arriola et al.,
arXiv:2503.09573) runs the noised copy of a sequence (positions ``0..L-1``) and
the clean copy (``L..2L-1``) together; with ``b = (pos mod L) // B``: a noised
query sees the noised keys of its own block and the clean keys of the blocks
before it, a clean query the clean keys of the blocks up to its own, nothing
else: ``L (L + B)`` pairs a head in three regions, of which the noised-noised
one is a thin block diagonal that costs a whole tile per query tile.

* :func:`flash_attention` — ``q [H, L, Dk]``, ``k [Hkv, L, Dk]``, ``v [Hkv, L, Dv]``
  -> ``[H, L, Dv]`` float32, differentiable (``custom_vjp``): the forward
  kernel keeps the row-wise log-sum-exp, the backward pass is two kernels,
  one walking the key blocks of a query block (``dq``), one the query blocks
  of a key block (``dk``, ``dv``), both recomputing the tile's probabilities.
  Operands are rounded to ``dtype`` (bfloat16) for the MXU; scores, softmax
  statistics, accumulators and every result are float32.
* :func:`attention_flops` — the operations the pairs a mask allows need, for
  the benchmark's roofline.

Off the chip the same kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import dataclasses
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


def attention_flops(seq_len: int, heads: int, dk: int, dv: int, diffusion_block=None) -> dict:
    """Operations of one call of each kernel over ``seq_len`` positions,
    counting the position pairs the mask allows (causal: ``L (L + 1) / 2``, not
    the masked half of the diagonal blocks; block diffusion over the two
    copies of ``L = seq_len / 2`` tokens: ``L (L + B)``): scores and weighted
    values forward; scores, ``dp`` and ``dq`` in the dq kernel; scores, ``dp``,
    ``dv`` and ``dk`` in the dkv kernel. ``heads`` are the query heads."""
    pairs = heads * _mask_of(diffusion_block).pairs(seq_len)
    return {"fwd": 2.0 * pairs * (dk + dv),
            "dq": 2.0 * pairs * (2 * dk + dv),
            "dkv": 2.0 * pairs * (2 * dk + 2 * dv)}


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


@dataclasses.dataclass(frozen=True)
class Causal:
    """A query sees the keys at or before it. Of ``n`` tiles a side, query
    tile ``i`` visits key tiles ``0..i``; key tile ``j`` is visited by query
    tiles ``j..n-1``. A grid step past those is dead: it computes nothing and
    its index map names the last live tile, so nothing is fetched."""

    def pairs(self, seq_len):
        return seq_len * (seq_len + 1) / 2

    def tile(self, seq_len, block):
        return _block_of(seq_len, block)

    def key_steps(self, n):
        """Grid steps a query tile needs for its key tiles (a key tile takes
        ``n`` for its query tiles under either mask)."""
        return n

    def key_tile(self, qi, j, n):
        """(the key tile of query tile ``qi``'s step ``j``, whether the step
        is live, whether it is the query tile's last live one)."""
        return jnp.minimum(j, qi), j <= qi, j == qi

    def query_tile(self, kj, i, n):
        """(the query tile of key tile ``kj``'s step ``i``, whether it is live)."""
        return jnp.maximum(i, kj), i >= kj

    def keep(self, qi, kj, n, shape):
        block = shape[0]
        return kj * block + _iota(shape, 1) <= qi * block + _iota(shape, 0)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The block-diffusion training mask over a noised copy followed by the
    clean copy, ``block_length`` tokens a block (this module's head). Of ``n``
    tiles a side (``n / 2`` a copy; a tile never straddles the copies, and
    holds whole blocks), noised query tile ``i`` visits its own noised tile,
    then clean tiles ``0..i``; clean query tile ``i`` visits clean tiles
    ``0..i``; a noised key tile is visited by its own query tile, clean key
    tile ``j`` by the noised and the clean query tiles ``j..n/2-1``. Every
    row's first live tile holds a pair it may see, so the running maximum is
    finite from the first step on."""

    block_length: int

    def pairs(self, seq_len):
        return (seq_len // 2) * (seq_len // 2 + self.block_length)

    def tile(self, seq_len, block):
        if seq_len % 2:
            raise ValueError(f"{seq_len} positions are no two copies of a sequence")
        block = _block_of(seq_len // 2, block)
        if block % self.block_length:
            raise ValueError(f"a tile of {block} holds no whole blocks of {self.block_length}")
        return block

    def key_steps(self, n):
        return n // 2 + 1

    def key_tile(self, qi, j, n):
        half = n // 2
        noised = qi < half
        visits = jnp.where(noised, qi + 2, qi - half + 1)
        step = jnp.minimum(j, visits - 1)
        clean = half + step - jnp.where(noised, 1, 0)
        return jnp.where(noised & (step == 0), qi, clean), j < visits, j == visits - 1

    def query_tile(self, kj, i, n):
        half = n // 2
        after = n - kj  # clean key tile kj - half: the query tiles at or after it, a copy
        visits = jnp.where(kj < half, 1, 2 * after)
        step = jnp.minimum(i, visits - 1)
        clean_key = jnp.where(step < after, kj - half + step, step + 2 * (kj - half))
        return jnp.where(kj < half, kj, clean_key), i < visits

    def keep(self, qi, kj, n, shape):
        half, per = n // 2, shape[0] // self.block_length
        # the query's block less the key's, each within its own copy
        ahead = ((qi % half) - (kj % half)) * per + (
            _iota(shape, 0) // self.block_length - _iota(shape, 1) // self.block_length)
        noised_key = kj < half
        least = jnp.where((qi < half) & ~noised_key, 1, 0)  # clean keys of EARLIER blocks
        most = jnp.where(noised_key, 0, n * per)  # noised keys of the SAME block
        return (ahead >= least) & (ahead <= most)


def _mask_of(diffusion_block):
    return Causal() if diffusion_block is None else BlockDiffusion(int(diffusion_block))


def _scores(q, k, keep, scale):
    """A tile's masked scores [block, block], float32."""
    s = jax.lax.dot_general(q, k, _TRANS_B, preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, mask, n, scale):
    qi, j = pl.program_id(1), pl.program_id(2)
    kj, live, last = mask.key_tile(qi, j, n)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        q, k = q_ref[...], k_ref[...]
        s = _scores(q, k, mask.keep(qi, kj, n, (q.shape[0], k.shape[0])), scale)
        m_old = m_ref[...]  # [block, 128], every lane alike
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(last)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, mask, n, scale):
    qi, j = pl.program_id(1), pl.program_id(2)
    kj, live, last = mask.key_tile(qi, j, n)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        q, k = q_ref[...], k_ref[...]
        s = _scores(q, k, mask.keep(qi, kj, n, (q.shape[0], k.shape[0])), scale)
        p = jnp.exp(s - lse_ref[:, :1])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _TRANS_B,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, mask, n, group, steps, scale):
    # the last grid axis walks the query heads of this key/value head's
    # group, and under each the query tiles of the key tile
    kj, t = pl.program_id(1), pl.program_id(2)
    # (one head to a key/value head keeps its plain indices, here and in the
    # index maps: `t % steps`, `hh // 1`, `t // steps` in every grid step cost
    # Moonlight's cell 0.77 ms of `step.attn_ms`, 194.489 -> 195.262: PERF.md, PR 34)
    qi, live = mask.query_tile(kj, t if group == 1 else t % steps, n)

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(live)
    def _():
        q, do, k = q_ref[...], do_ref[...], k_ref[...]
        s = _scores(q, k, mask.keep(qi, kj, n, (q.shape[0], k.shape[0])), scale)
        p = jnp.exp(s - lse_ref[:, :1])
        dv_acc[...] += jax.lax.dot_general(p.astype(do.dtype), do, _TRANS_A,
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[...], _TRANS_B, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        dk_acc[...] += jax.lax.dot_general(ds.astype(q.dtype), q, _TRANS_A,
                                           preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
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


def _layout(q, k, block, mask):
    """(tile, tiles a side, query heads to a key/value head)."""
    (h, seq, _), hkv = q.shape, k.shape[0]
    if h % hkv:
        raise ValueError(f"{h} query heads do not split over {hkv} key/value heads")
    block = mask.tile(seq, block)
    return block, seq // block, h // hkv


def _query_side_specs(block, n, group, mask):
    """Block specs of a grid (query head, query tile, step): ``rows(width)``
    follows the query tile; ``cols(width)`` is the key/value head's tile that
    the mask gives the step (a dead step names the last live tile: nothing
    new is fetched)."""
    rows = lambda w: pl.BlockSpec((None, block, w), lambda hh, i, j: (hh, i, 0))  # noqa: E731
    cols = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block, w),
        lambda hh, i, j: (hh if group == 1 else hh // group, mask.key_tile(i, j, n)[0], 0))
    return rows, cols


def _forward(q, k, v, block, interpret, mask):
    h, seq, dk = q.shape
    dv = v.shape[-1]
    block, n, group = _layout(q, k, block, mask)
    rows, cols = _query_side_specs(block, n, group, mask)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mask=mask, n=n, scale=dk ** -0.5),
        grid=(h, n, mask.key_steps(n)),
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


def _backward(q, k, v, lse, do, delta, block, interpret, mask):
    h, seq, dk = q.shape
    hkv, _, dv = v.shape
    block, n, group = _layout(q, k, block, mask)
    scale = dk ** -0.5
    rows, cols = _query_side_specs(block, n, group, mask)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, mask=mask, n=n, scale=scale),
        grid=(h, n, mask.key_steps(n)),
        in_specs=[rows(dk), cols(dk), cols(dv), rows(dv), rows(_LANES), rows(_LANES)],
        out_specs=rows(dk),
        out_shape=jax.ShapeDtypeStruct((h, seq, dk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32)],
        name="flash_attention_dq",
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    # here the grid is (key/value head, key tile, group's query head x step):
    # the query tile is the one the mask gives the key tile's step, of n
    steps = n
    keys = lambda w: pl.BlockSpec((None, block, w), lambda hh, j, t: (hh, j, 0))  # noqa: E731
    if group == 1:
        head_step = lambda hh, t: (hh, t)  # noqa: E731
    else:
        head_step = lambda hh, t: (hh * group + t // steps, t % steps)  # noqa: E731

    def qrows(w):
        def index(hh, j, t):
            head, step = head_step(hh, t)
            return head, mask.query_tile(j, step, n)[0], 0

        return pl.BlockSpec((None, block, w), index)

    dk_, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, mask=mask, n=n, group=group, steps=steps, scale=scale),
        grid=(hkv, n, group * steps),
        in_specs=[qrows(dk), keys(dk), keys(dv), qrows(dv), qrows(_LANES), qrows(_LANES)],
        out_specs=[keys(dk), keys(dv)],
        out_shape=[jax.ShapeDtypeStruct((hkv, seq, dk), jnp.float32),
                   jax.ShapeDtypeStruct((hkv, seq, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        name="flash_attention_dkv",
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk_, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attend(q, k, v, block, dtype, interpret, mask):
    return _forward(q.astype(dtype), k.astype(dtype), v.astype(dtype), block, interpret, mask)[0]


def _attend_fwd(q, k, v, block, dtype, interpret, mask):
    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    o, lse = _forward(q, k, v, block, interpret, mask)
    return o, (q, k, v, o, lse)


def _attend_bwd(block, dtype, interpret, mask, res, do):
    q, k, v, o, lse = res
    delta = jnp.broadcast_to(jnp.sum(do * o, axis=-1, keepdims=True), lse.shape)
    return _backward(q, k, v, lse, do.astype(dtype), delta, block, interpret, mask)


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, block: int = BLOCK, dtype=jnp.bfloat16, interpret=None,
                    diffusion_block=None):
    """Softmax attention, scores scaled by ``Dk ** -0.5``: causal, or, with
    ``diffusion_block`` (the block length), under the block-diffusion mask
    over a noised copy followed by the clean copy (:class:`BlockDiffusion`).

    ``q`` ``[H, L, Dk]``, ``k`` ``[Hkv, L, Dk]`` and ``v`` ``[Hkv, L, Dv]``
    float32 -> ``[H, L, Dv]`` float32, the operands rounded to ``dtype`` for
    the MXU; query head ``i`` reads key/value head ``i // (H / Hkv)``. ``L``
    (a copy's, under block diffusion) is a multiple of ``block`` (or under it)."""
    if interpret is None:
        interpret = not on_tpu()
    return _attend(q, k, v, block, jnp.dtype(dtype), interpret, _mask_of(diffusion_block))
