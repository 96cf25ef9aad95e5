"""Attention in blocks, forward and backward, as Mosaic kernels: causal, or
under the block-diffusion training mask; one key/value head per query head or
per group of them.

``parallel/sequence.reference_attention`` materialises ``[H, L, L]`` scores:
at 16 heads and 8,192 positions 4.3 GB in float32, and again in the backward
pass. Here a ``[block, block]`` tile of scores lives in VMEM only, with the
online-softmax recurrence of ``sequence._block_update`` (running max, running
denominator, running output), and a tile that holds no pair the mask allows
(the block pairs above the diagonal) is never visited.

Keys and values may differ in width (latent attention: 192-wide keys, 128-wide
values); the accumulator takes the values' width. With fewer key/value heads
than query heads (grouped queries) query head ``i`` reads key/value head ``i //
group`` through the index maps, and the ``dkv`` kernel walks the group's query
heads one after another into the same accumulators: keys and values are never
copied out ``group`` times.

Which tiles a query tile visits, in which order, and which pairs of a visited
tile count, is a mask's (:class:`Causal`, :class:`BlockDiffusion`); the three
kernels' bodies are the same under either. **A tile has a class, and what is
done for it follows the class** (:func:`_steps`, :func:`_by_class`; the times
are a kernel's own on a TPU v5e at the benchmark's shapes, PERF.md, PR 37):

* *dead* (the mask's walk has a step for it and nothing to do there): no grid
  step. A kernel's grid is (head, live step); the steps' table (query tile,
  key tile, class, first and last step of the accumulated tile) is built from
  the mask's walk at trace time, handed to the kernel in scalar memory, and
  read by the index maps and the body. Under block diffusion over two copies
  of 4,096 a query head's walk had 144 steps for 80 live ones and a key/value
  head's 256 a query head; each dead one cost its step and its index maps'
  arithmetic, and left the fetch of the next tile's operands exposed: the
  kernels fell by 12%, 22% and 21%.
* *whole* (the mask allows every pair: a causal tile below the diagonal, a
  clean key tile of earlier blocks under block diffusion) and *lower* (nothing
  above the tile's own diagonal: the diagonal tiles of either mask): one
  piece, scores under ``mask.keep``. The mask is free: a body without iota,
  compare and select for the whole tiles moved no kernel by more than 0.5%,
  and strips that leave a lower tile's empty sub-tiles out cost the ``dkv``
  kernel more than they saved the other two, so neither is here.
* *diagonal* (the noised copy's own tile under block diffusion, a thin block
  diagonal): only the sub-tiles on its diagonal (:func:`_pieces`), a quarter
  of the products and of the softmax, for 2% of a kernel's time (four
  ``[128, 128]`` products fill the MXU worse than one ``[512, 512]``).

The results are the same bit for bit whichever way a tile is walked or cut.
:func:`tile_classes` counts the steps by class: at 8,192 causal positions and
tiles of 512, 120 of a head's 136 live steps are whole; under block diffusion
over two copies of 4,096, 56 of 80.

Block diffusion (Arriola et al., arXiv:2503.09573) runs the noised copy of a
sequence (positions ``0..L-1``) and the clean copy (``L..2L-1``) together;
with ``b = (pos mod L) // B``: a noised query sees the noised keys of its own
block and the clean keys of the blocks before it, a clean query the clean keys
of the blocks up to its own, nothing else: ``L (L + B)`` pairs a head in three
regions, of which the noised-noised one is a thin block diagonal that costs a
whole tile per query tile.

* :func:`flash_attention` — ``q [H, L, Dk]``, ``k [Hkv, L, Dk]``, ``v [Hkv, L, Dv]``
  -> ``[H, L, Dv]`` float32, differentiable (``custom_vjp``): the forward
  kernel keeps the row-wise log-sum-exp, the backward pass is two kernels,
  one walking the key blocks of a query block (``dq``), one the query blocks
  of a key block (``dk``, ``dv``), both recomputing the tile's probabilities.
  Operands are rounded to ``dtype`` (bfloat16) for the MXU; scores, softmax
  statistics, accumulators and every result are float32.
* :func:`attention_flops` — the operations the pairs a mask allows need, for
  the benchmark's roofline.
* :func:`tile_classes` — a head's steps by class (live, whole, cut, dead).

Off the chip the same kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
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


# a visited tile's class, as a mask names it and the step tables number it
_KINDS = ("whole", "lower", "diagonal")


@dataclasses.dataclass(frozen=True)
class Causal:
    """A query sees the keys at or before it. Of ``n`` tiles a side, query
    tile ``i`` visits key tiles ``0..i``; key tile ``j`` is visited by query
    tiles ``j..n-1``; a step of either walk past those is dead."""

    unit = 1  # a sub-tile's edge is a multiple of it

    def pairs(self, seq_len):
        return seq_len * (seq_len + 1) / 2

    def tile(self, seq_len, block):
        return _block_of(seq_len, block)

    def key_steps(self, n):
        """Steps a query tile's walk over its key tiles may take (a key
        tile's walk over its query tiles takes ``n`` under either mask)."""
        return n

    def key_tile(self, qi, j, n):
        """(the key tile of query tile ``qi``'s step ``j``, whether the step
        is live). Numbers or numpy arrays, at trace time (:func:`_steps`)."""
        return np.minimum(j, qi), j <= qi

    def query_tile(self, kj, i, n):
        """(the query tile of key tile ``kj``'s step ``i``, whether it is live)."""
        return np.maximum(i, kj), i >= kj

    def classes(self, qi, kj, n):
        """The class of the VISITED tile (query tile ``qi``, key tile ``kj``),
        a truth a class of ``_KINDS``: a tile below the diagonal is allowed
        whole, a diagonal one holds nothing above its own diagonal."""
        return {"whole": kj != qi, "lower": kj == qi}

    def keep(self, qi, kj, n, block, rows, cols):
        """The pairs that count among rows ``rows`` and columns ``cols``
        (each a start and a size) of the visited tile of ``block``; in the
        kernel, ``qi`` and ``kj`` scalars of the grid step."""
        shape = (rows[1], cols[1])
        return kj * block + cols[0] + _iota(shape, 1) <= qi * block + rows[0] + _iota(shape, 0)


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

    @property
    def unit(self):
        return self.block_length

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
        visits = np.where(noised, qi + 2, qi - half + 1)
        step = np.minimum(j, visits - 1)
        clean = half + step - np.where(noised, 1, 0)
        return np.where(noised & (step == 0), qi, clean), j < visits

    def query_tile(self, kj, i, n):
        half = n // 2
        after = n - kj  # clean key tile kj - half: the query tiles at or after it, a copy
        visits = np.where(kj < half, 1, 2 * after)
        step = np.minimum(i, visits - 1)
        clean_key = np.where(step < after, kj - half + step, step + 2 * (kj - half))
        return np.where(kj < half, kj, clean_key), i < visits

    def classes(self, qi, kj, n):
        # a visited tile at another place of its copy than the query tile's
        # holds clean keys of earlier blocks only; at the same place the noised
        # keys are the query's own blocks (a block diagonal), the clean keys
        # the blocks before (a noised query) or up to (a clean query) its own
        half = n // 2
        same, noised_key = qi % half == kj % half, kj < half
        return {"whole": np.logical_not(same), "diagonal": same & noised_key,
                "lower": same & np.logical_not(noised_key)}

    def keep(self, qi, kj, n, block, rows, cols):
        half, per, shape = n // 2, block // self.block_length, (rows[1], cols[1])
        # the query's block less the key's, each within its own copy
        ahead = ((qi % half) - (kj % half)) * per + (
            (rows[0] + _iota(shape, 0)) // self.block_length
            - (cols[0] + _iota(shape, 1)) // self.block_length)
        noised_key = kj < half
        least = jnp.where((qi < half) & ~noised_key, 1, 0)  # clean keys of EARLIER blocks
        most = jnp.where(noised_key, 0, n * per)  # noised keys of the SAME block
        return (ahead >= least) & (ahead <= most)


def _mask_of(diffusion_block):
    return Causal() if diffusion_block is None else BlockDiffusion(int(diffusion_block))


_QI, _KJ, _KIND, _FIRST, _LAST, _HEAD = range(6)  # the rows of a step table


def _steps(mask, n, group=None):
    """The live steps of a walk in the order they run, one column a step,
    int32 ``[6, steps]``: the query tile, the key tile, the visited tile's
    class (its place in ``_KINDS``), whether the step is the first and the
    last of its accumulation, and the query head within its group. Without
    ``group`` a query head's walk (the forward and the ``dq`` kernel: by
    query tile, its key tiles in the mask's order, accumulating a query
    tile); with it a key/value head's (the ``dkv`` kernel: by key tile, under
    it the group's query heads, under each the key tile's query tiles,
    accumulating a key tile). The dead steps of the masks' walks are not in
    it: the grid is the table's length."""
    if group is None:
        qi, j = np.meshgrid(np.arange(n), np.arange(mask.key_steps(n)), indexing="ij")
        (kj, live), head, run = mask.key_tile(qi, j, n), 0 * qi, qi
    else:
        kj, head, i = np.meshgrid(np.arange(n), np.arange(group), np.arange(n), indexing="ij")
        (qi, live), run = mask.query_tile(kj, i, n), kj
    classes = mask.classes(qi, kj, n)
    kind = sum(i * classes.get(name, False) for i, name in enumerate(_KINDS))
    qi, kj, kind, head, run = (np.broadcast_to(t, live.shape)[live] for t in (qi, kj, kind, head, run))
    edge = np.flatnonzero(np.diff(run)) + 1  # where the accumulated tile changes
    first, last = np.zeros_like(run), np.zeros_like(run)
    first[np.r_[0, edge]] = last[np.r_[edge - 1, -1]] = 1
    return np.stack([qi, kj, kind, first, last, head]).astype(np.int32)


def tile_classes(seq_len: int, block: int = BLOCK, diffusion_block=None) -> dict:
    """A query head's steps over ``seq_len`` positions by class: the ``live``
    ones, which the grids walk (``whole``: the mask allows every pair of the
    visited tile; ``cut``: it does not), and the ``dead`` ones of the mask's
    rectangular walk (``n`` query tiles x ``key_steps``), which no grid
    holds. Counted from the kernels' own step table."""
    mask = _mask_of(diffusion_block)
    n = seq_len // mask.tile(seq_len, block)
    kind = _steps(mask, n)[_KIND]
    whole = int(np.sum(kind == _KINDS.index("whole")))
    return {"live": kind.size, "whole": whole, "cut": kind.size - whole,
            "dead": n * mask.key_steps(n) - kind.size}


_PARTS = 4  # sub-tiles a side of a tile: [128, 128] of [512, 512]


def _parts(mask, block, interpret) -> int:
    """Sub-tiles a side: whole blocks of the mask and, where Mosaic compiles,
    whole lanes; otherwise every tile is one piece."""
    sub = block // _PARTS
    fits = block % _PARTS == 0 and sub % mask.unit == 0 and (interpret or sub % _LANES == 0)
    return _PARTS if fits else 1


def _pieces(kind, block, parts):
    """The pieces of a visited tile that are computed, as ``((first row,
    rows), (first column, columns))``, static: of a ``diagonal`` tile the
    ``parts`` sub-tiles on its diagonal, which hold every pair its mask
    allows; any other tile whole. A piece's products run over a stretch of
    the whole tile's and what is left out is exact zeros there, so the
    results are the whole tile's bit for bit."""
    if kind != "diagonal":
        return (((0, block), (0, block)),)
    sub = block // parts
    return tuple(((i * sub, sub), (i * sub, sub)) for i in range(parts))


def _by_class(mask, kinds, step, n, block, parts, piece):
    """Run ``piece(rows, cols, keep)`` over the pieces of the visited tile's
    class: the body is written once, and traced a second time only where the
    walk's tiles (of ``kinds``) have a diagonal one to cut up."""
    def run(kind):
        for rows, cols in _pieces(kind, block, parts):
            piece(pl.ds(*rows), pl.ds(*cols), mask.keep(step[_QI], step[_KJ], n, block, rows, cols))

    diagonal = _KINDS.index("diagonal")
    if parts == 1 or diagonal not in kinds:
        run("whole")
        return
    pl.when(step[_KIND] == diagonal)(lambda: run("diagonal"))
    pl.when(step[_KIND] != diagonal)(lambda: run("whole"))


def _scores(q, k, keep, scale):
    """A piece's masked scores, float32."""
    s = jax.lax.dot_general(q, k, _TRANS_B, preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, _NEG_INF)


def _step_of(steps_ref):
    """The grid step's column of its table (the grid's last axis walks it)."""
    t = pl.program_id(1)
    return [steps_ref[row, t] for row in range(steps_ref.shape[0])]


def _fwd_kernel(steps_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, mask, kinds, n, parts, scale):
    step = _step_of(steps_ref)

    @pl.when(step[_FIRST] == 1)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def piece(rows, cols, keep):
        s = _scores(q_ref[rows, :], k_ref[cols, :], keep, scale)
        m_old = m_ref[rows, :]  # [rows, 128], every lane alike
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows, :] = alpha[:, :1] * acc_ref[rows, :] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[cols, :], preferred_element_type=jnp.float32)
        m_ref[rows, :] = m_new

    _by_class(mask, kinds, step, n, q_ref.shape[0], parts, piece)

    @pl.when(step[_LAST] == 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _dq_kernel(steps_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
               *, mask, kinds, n, parts, scale):
    step = _step_of(steps_ref)

    @pl.when(step[_FIRST] == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def piece(rows, cols, keep):
        k = k_ref[cols, :]
        p = jnp.exp(_scores(q_ref[rows, :], k, keep, scale) - lse_ref[rows, :1])
        dp = jax.lax.dot_general(do_ref[rows, :], v_ref[cols, :], _TRANS_B,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[rows, :1]) * scale
        acc_ref[rows, :] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    _by_class(mask, kinds, step, n, q_ref.shape[0], parts, piece)

    @pl.when(step[_LAST] == 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(steps_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, mask, kinds, n, parts, scale):
    step = _step_of(steps_ref)

    @pl.when(step[_FIRST] == 1)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def piece(rows, cols, keep):
        q, do = q_ref[rows, :], do_ref[rows, :]
        p = jnp.exp(_scores(q, k_ref[cols, :], keep, scale) - lse_ref[rows, :1])
        dv_acc[cols, :] += jax.lax.dot_general(p.astype(do.dtype), do, _TRANS_A,
                                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[cols, :], _TRANS_B, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[rows, :1]) * scale
        dk_acc[cols, :] += jax.lax.dot_general(ds.astype(q.dtype), q, _TRANS_A,
                                               preferred_element_type=jnp.float32)

    _by_class(mask, kinds, step, n, q_ref.shape[0], parts, piece)

    @pl.when(step[_LAST] == 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _block_of(seq_len: int, block: int) -> int:
    block = min(block, seq_len)
    if seq_len % block:
        raise ValueError(f"sequence length {seq_len} is no multiple of the block {block}")
    return block


def _layout(q, k, block, mask):
    """(tile, tiles a side, query heads to a key/value head)."""
    (h, seq, _), hkv = q.shape, k.shape[0]
    if h % hkv:
        raise ValueError(f"{h} query heads do not split over {hkv} key/value heads")
    block = mask.tile(seq, block)
    return block, seq // block, h // hkv


def _call(kernel, name, steps, heads, interpret, *, mask, n, block, scale,
          in_specs, out_specs, out_shape, scratch_shapes):
    """``pallas_call`` of a kernel over the grid (head, step of ``steps``),
    the table handed ahead of the operands into scalar memory, where the
    index maps and the kernel read the step's column."""
    kinds = tuple(int(kind) for kind in np.unique(steps[_KIND]))
    params = {"interpret": True} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}
    call = pl.pallas_call(
        functools.partial(kernel, mask=mask, kinds=kinds, n=n, parts=_parts(mask, block, interpret), scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads, steps.shape[1]), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, name=name, **params)
    return functools.partial(call, jnp.asarray(steps))


def _query_side_specs(block, group):
    """Block specs of a grid (query head, step): ``rows(width)`` follows the
    step's query tile, ``cols(width)`` is the key/value head's tile the step
    visits."""
    rows = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block, w), lambda hh, t, steps: (hh, steps[_QI, t], 0))
    cols = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block, w), lambda hh, t, steps: (hh if group == 1 else hh // group, steps[_KJ, t], 0))
    return rows, cols


def _forward(q, k, v, block, interpret, mask):
    h, seq, dk = q.shape
    dv = v.shape[-1]
    block, n, group = _layout(q, k, block, mask)
    rows, cols = _query_side_specs(block, group)
    return _call(
        _fwd_kernel, "flash_attention_fwd", _steps(mask, n), h, interpret,
        mask=mask, n=n, block=block, scale=dk ** -0.5,
        in_specs=[rows(dk), cols(dk), cols(dv)],
        out_specs=[rows(dv), rows(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((h, seq, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, seq, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
    )(q, k, v)


def _backward(q, k, v, lse, do, delta, block, interpret, mask):
    h, seq, dk = q.shape
    hkv, _, dv = v.shape
    block, n, group = _layout(q, k, block, mask)
    common = dict(mask=mask, n=n, block=block, scale=dk ** -0.5)
    rows, cols = _query_side_specs(block, group)
    dq = _call(
        _dq_kernel, "flash_attention_dq", _steps(mask, n), h, interpret, **common,
        in_specs=[rows(dk), cols(dk), cols(dv), rows(dv), rows(_LANES), rows(_LANES)],
        out_specs=rows(dk),
        out_shape=jax.ShapeDtypeStruct((h, seq, dk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32)],
    )(q, k, v, do, lse, delta)
    # here the grid is (key/value head, step): the step's key tile, and the
    # query tile of the query head that the step names within the group
    # (one head to a key/value head keeps its plain index: scalar arithmetic
    # in an index map is not free, 0.77 ms of Moonlight's `step.attn_ms` for
    # `hh // 1` and two more in every grid step: PERF.md, PR 34)
    keys = lambda w: pl.BlockSpec((None, block, w), lambda hh, t, steps: (hh, steps[_KJ, t], 0))  # noqa: E731
    qrows = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block, w),
        lambda hh, t, steps: (hh if group == 1 else hh * group + steps[_HEAD, t], steps[_QI, t], 0))
    dk_, dv_ = _call(
        _dkv_kernel, "flash_attention_dkv", _steps(mask, n, group), hkv, interpret, **common,
        in_specs=[qrows(dk), keys(dk), keys(dv), qrows(dv), qrows(_LANES), qrows(_LANES)],
        out_specs=[keys(dk), keys(dv)],
        out_shape=[jax.ShapeDtypeStruct((hkv, seq, dk), jnp.float32),
                   jax.ShapeDtypeStruct((hkv, seq, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, dk), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
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
