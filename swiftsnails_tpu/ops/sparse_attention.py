"""Learned sparse attention (DeepSeek-V3.2's DSA): a lightning indexer scores
every causal key of a query, the ``topk`` best are the query's selection
``S_t``, and the grouped-query attention reads those keys only
(``flash_attention.selected_attention``).

The indexer has ``HI`` heads of width ``DI`` over one key head: with query
heads ``qI [HI, L, DI]``, keys ``kI [L, DI]`` and head weights ``w [L, HI]``
(the ``1 / sqrt(HI)`` already in ``w``), ``I[t, s] = sum_j w[t, j]
ReLU(qI[j, t] . kI[s]) / sqrt(DI)`` for ``s <= t``. Its loss is DSA's
sparse-stage one, ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])``,
``p_t`` the main attention's probabilities over ``S_t`` summed over its heads
and divided by their number, held constant.

Everything here works tile by tile (``block`` a side); nothing of ``[L, L]``
in float32 exists:

* :func:`select` — the indexer's scores by query chunks of ``block``
  (:func:`index_scores`, a kernel over the chunk's causal key tiles: ``[block,
  L]`` float32 at a time), the exact top ``topk`` of each query's causal keys
  (:func:`sparse_top_k`, a kernel over the same tiles; ties to the lower
  index as ``lax.top_k`` breaks them; every causal key where ``t < topk``),
  packed into the selection's words ``[L / 32, L]`` int32, and which (query
  tile, key tile) pairs hold a selected pair.
* :func:`walks` — from those live tiles, the two step tables of the attention
  kernels (``flash_attention.Selection``), in the order the causal walks take.
* :func:`probability_pass` — per query, over ``S_t`` and tile by tile: the
  head-summed probability ``p`` (from the main attention's queries, keys and
  log-sum-exp), the indexer's log-sum-exp, ``sum p log p`` and ``sum p I``;
  ``L_I``'s value is their row sums (:func:`indexer_loss`).
* :func:`indexer_loss` — ``L_I`` from the pass's rows, differentiable in
  ``qI``, ``kI`` and ``w`` alone: its backward kernel walks the selected
  tiles again, recomputes ``p`` and ``I``, and gives ``dqI`` and ``dw`` by
  query tile and ``dkI`` by (query tile, key tile), summed afterwards.

Operands are rounded to ``dtype`` (bfloat16) for the MXU, products accumulate
in float32; the selection, the softmax of ``L_I`` and every statistic are
float32. Off the chip the kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.flash_attention import (
    _FIRST, _HEAD, _KIND, _KJ, _LANES, _LAST, _NEG_INF, _QI, _SKIP, _SPARSE, _TRANS_A, _TRANS_B,
    _VMEM_LIMIT, Selection, unpack_bits)
from swiftsnails_tpu.ops.rowdma import on_tpu

# the lanes of the probability pass's rows
STAT_LSE, STAT_PLOGP, STAT_PI = 0, 1, 2


def _params(interpret, semantics):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=semantics,
                                                    vmem_limit_bytes=_VMEM_LIMIT)}


def _index_heads(qi_ref, ki, j):
    """``qI[j] . kI^T`` of a tile, float32 ``[rows, cols]``."""
    return jax.lax.dot_general(qi_ref[j], ki, _TRANS_B, preferred_element_type=jnp.float32)


def _index_tile(qi_ref, ki_ref, w_ref):
    """The indexer's scores of a tile: ``sum_j w_j ReLU(qI_j . kI) / sqrt(DI)``."""
    ki = ki_ref[...]
    w = w_ref[...]
    out = None
    for j in range(qi_ref.shape[0]):
        term = w[:, j:j + 1] * jnp.maximum(_index_heads(qi_ref, ki, j), 0.0)
        out = term if out is None else out + term
    return out * (ki.shape[-1] ** -0.5)


# ------------------------------------------------------------ the scores ---


def _scores_kernel(chunk_ref, qi_ref, ki_ref, w_ref, out_ref):
    kj, c = pl.program_id(0), chunk_ref[0]

    @pl.when(kj <= c)
    def _():
        out_ref[...] = _index_tile(qi_ref, ki_ref, w_ref)

    @pl.when(kj > c)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def index_scores(qi, ki, w, chunk, block, interpret):
    """The indexer's scores of query chunk ``chunk`` (a traced index) against
    every key, ``[block, L]`` float32; key tiles after the chunk's are
    zeros (the selection masks ``s > t`` itself) and are not fetched."""
    hi, seq, di = qi.shape
    n = seq // block
    return pl.pallas_call(
        _scores_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[pl.BlockSpec((hi, block, di), lambda kj, c: (0, c[0], 0)),
                      pl.BlockSpec((block, di), lambda kj, c: (jnp.minimum(kj, c[0]), 0)),
                      pl.BlockSpec((block, hi), lambda kj, c: (c[0], 0))],
            out_specs=pl.BlockSpec((block, block), lambda kj, c: (0, kj))),
        out_shape=jax.ShapeDtypeStruct((block, seq), jnp.float32),
        name="sparse_index_scores", **_params(interpret, ("arbitrary",)),
    )(jnp.reshape(chunk, (1,)).astype(jnp.int32), qi, ki, w)


# --------------------------------------------------------- the selection ---


_ROWS = 64  # the top-k's query rows a grid step (whole rows of words): a pass waits on the last one's counts
_UNROLL = 4  # key tiles a trip of a counting loop: one tile's compares and adds wait on the last's
_SIGN = -2 ** 31  # the sign bit of an int32


def _key_value(key):
    """The float32 whose order key is ``key`` (int32: the sign and magnitude of
    the float's bits, so that ``-0.0`` and ``+0.0`` are one key and the keys
    order as the floats do)."""
    return jax.lax.bitcast_convert_type(jnp.where(key < 0, -key | _SIGN, key), jnp.float32)


def _over_tiles(tiles, body, carry=0):
    """``body(kj, carry)`` over key tiles ``0..tiles-1``, ``_UNROLL`` of them a
    trip of the loop."""
    def trip(i, carry):
        for u in range(_UNROLL):
            carry = body(i * _UNROLL + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, tiles // _UNROLL, trip, carry)
    return jax.lax.fori_loop(tiles - tiles % _UNROLL, tiles, body, carry)


def _top_k_kernel(chunk_ref, scores_ref, words_ref, ties_ref, diag_ref, last_ref, *, topk):
    r, c = pl.program_id(0), chunk_ref[0]
    rows, block = diag_ref.shape
    seq = scores_ref.shape[1]
    words = rows // 32
    t = c * block + r * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)  # each row's query
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    at = pl.multiple_of(r * rows, rows)

    def scores(kj):  # a key tile before the diagonal one
        return scores_ref[pl.ds(at, rows), pl.ds(pl.multiple_of(kj * block, block), block)]

    def count(hit):
        """``[rows, 1]`` int32: the causal keys for which ``hit(scores, first
        column)`` holds, the chunk's key tiles up to its diagonal one."""
        def tile(x, col, acc):
            one = hit(x, col).astype(jnp.int32)
            parts = [one[:, i:i + _LANES] for i in range(0, block, _LANES)]
            while len(parts) > 1:  # a tree of lane-aligned slices, then one add into the partials
                parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + parts[len(parts) - len(parts) % 2:]
            return acc + parts[0]

        acc = _over_tiles(c, lambda kj, acc: tile(scores(kj), kj * block, acc),
                          jnp.zeros((rows, _LANES), jnp.int32))
        return jnp.sum(tile(diag_ref[...], c * block, acc), axis=1, keepdims=True)

    def pack(keep):
        """Each row's ``keep(scores, columns)`` over the causal tiles, as words."""
        shift = jnp.left_shift(1, jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0) % 32)

        def tile(x, kj, carry):
            bits = jnp.where(keep(x, kj * block + lane), shift, 0)
            for w in range(words):  # distinct bits: the sum is their OR
                words_ref[pl.ds(r * words + w, 1), pl.ds(pl.multiple_of(kj * block, block), block)] = jnp.sum(
                    bits[w * 32:(w + 1) * 32], axis=0, keepdims=True)
            return carry

        _over_tiles(c, lambda kj, carry: tile(scores(kj), kj, carry))
        tile(diag_ref[...], c, 0)

    @pl.when(r == 0)
    def _():  # the words of the key tiles after the chunk stay zeros
        words_ref[...] = jnp.zeros_like(words_ref)
        ties_ref[...] = jnp.zeros_like(ties_ref)

    # the diagonal tile, NaN past each row's query: it then neither counts nor is kept
    diag_ref[...] = jnp.where(c * block + lane <= t, scores_ref[pl.ds(at, rows), pl.ds(
        pl.multiple_of(c * block, block), block)], jnp.nan)
    few = c * block + (r + 1) * rows <= topk  # every row of the block has topk or fewer causal keys

    @pl.when(few)
    def _():
        pack(lambda x, col: col <= t)

    @pl.when(jnp.logical_not(few))
    def _():
        # the topk-th largest key, its bits most significant first (over key ^ _SIGN,
        # which orders as unsigned); a row is done once a candidate keeps exactly
        # topk, those being its top topk whatever ties lie below, and the passes
        # stop once every row of the block is
        few_keys = t < topk  # topk or fewer causal keys: all of them

        def unsure(carry):
            b, _, done = carry
            return (b < 32) & jnp.any(done == 0)

        def bit(carry):
            b, least, done = carry
            cand = least | jnp.left_shift(1, 31 - b)
            value = _key_value(cand ^ _SIGN)
            hits = count(lambda x, col: x >= value)
            keep = (done == 0) & (hits >= topk)
            return b + 1, jnp.where(keep, cand, least), jnp.where((done == 0) & (hits == topk), 1, done)

        zeros = jnp.zeros((rows, 1), jnp.int32)
        _, least, done = jax.lax.while_loop(unsure, bit, (0, zeros, few_keys.astype(jnp.int32)))
        # keep what is above least, and what equals it up to column last
        least = jnp.where(few_keys, -jnp.inf, _key_value(least ^ _SIGN))
        last_ref[...] = jnp.broadcast_to(jnp.where((done == 1) & ~few_keys, seq, t), last_ref.shape)

        @pl.when(jnp.any(done == 0))
        def _():
            # a row not done has more than topk keys at or above its topk-th: it is
            # tied, and some ties are left out; ties go to the lower column
            # (``lax.top_k``'s rule), so the last one kept is found by its column's bits
            search = done == 0
            ties_ref[...] += jnp.sum(search.astype(jnp.int32))
            need = topk - count(lambda x, col: x > least)
            width = (seq - 1).bit_length()

            def col_bit(b, m):
                cand = m | jnp.left_shift(1, width - 1 - b)
                before = count(lambda x, col: (x == least) & (col + lane < cand))
                return jnp.where(before < need, cand, m)

            m = jax.lax.fori_loop(0, width, col_bit, zeros)
            last_ref[...] = jnp.broadcast_to(jnp.where(search, m, last_ref[:, :1]), last_ref.shape)

        last = last_ref[:, :1]
        pack(lambda x, col: (x > least) | ((x == least) & (col <= last)))


def sparse_top_k(scores, chunk, topk: int, interpret):
    """Of each row of query chunk ``chunk``'s scores (``[block, L]`` float32,
    finite, :func:`index_scores`), the ``topk`` largest among its causal
    keys, ties to the lower column (``lax.top_k``'s rule), every causal key
    of a row that has ``topk`` or fewer, packed (:func:`pack_bits`' layout):
    -> (``[block / 32, L]`` int32, the rows whose ``topk``-th value was tied
    and had some of its ties left out). Exact, with no sort: counting passes
    find each row's ``topk``-th value bit by bit, a block of rows at a time,
    over the chunk's causal key tiles alone (the scores stay in VMEM; the
    tiles after the chunk's are neither read nor counted, their words are
    zeros), and stop once a candidate keeps exactly ``topk`` in every row (at
    most 32); a row tied at its ``topk``-th value finds its last kept tie by
    the column's bits."""
    block, seq = scores.shape
    if not interpret:  # where the score kernel writes them, as XLA's own top-k had them
        scores = pltpu.with_memory_space_constraint(scores, pltpu.VMEM)
    words, ties = pl.pallas_call(
        functools.partial(_top_k_kernel, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(block // _ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=[pl.BlockSpec((block // 32, seq), lambda r, c: (0, 0)),
                       pl.BlockSpec((1, _LANES), lambda r, c: (0, 0))],
            scratch_shapes=[pltpu.VMEM((_ROWS, block), jnp.float32),
                            pltpu.VMEM((_ROWS, _LANES), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((block // 32, seq), jnp.int32),
                   jax.ShapeDtypeStruct((1, _LANES), jnp.int32)],
        name="sparse_top_k", **_params(interpret, ("arbitrary",)),
    )(jnp.reshape(chunk, (1,)).astype(jnp.int32), scores)
    return words, ties[0, 0]


def pack_bits(keep):
    """``[rows, L]`` bool -> ``[rows / 32, L]`` int32, row ``r`` bit ``r % 32``
    of word row ``r // 32`` (``flash_attention.unpack_bits``'s inverse)."""
    r, seq = keep.shape
    parts = keep.reshape(r // 32, 32, seq).astype(jnp.int32) << jnp.arange(32, dtype=jnp.int32)[:, None]
    return jnp.sum(parts, axis=1, dtype=jnp.int32)  # distinct bits: the sum is their OR


def select(qi, ki, w, topk: int, block: int, dtype=jnp.bfloat16, interpret=None):
    """(the selection's words ``[L / 32, L]`` int32, the live tiles ``[n, n]``
    bool, query tile by key tile, the share of queries whose ``topk``-th
    value was tied and had some of its ties left out), by query chunks of
    ``block``: the chunk's scores, their top ``topk`` among its causal keys,
    packed (:func:`sparse_top_k`)."""
    if interpret is None:
        interpret = not on_tpu()
    seq = ki.shape[0]
    if block % _ROWS or seq % block:
        raise ValueError(f"{seq} positions do not split into tiles of {block} (a multiple of {_ROWS})")
    n = seq // block
    qi, ki = qi.astype(dtype), ki.astype(dtype)

    def chunk(c):
        words, tied = sparse_top_k(index_scores(qi, ki, w, c, block, interpret), c, topk, interpret)
        return words, jnp.any(words.reshape(block // 32, n, block) != 0, axis=(0, 2)), tied

    words, live, tied = jax.lax.map(chunk, jnp.arange(n))
    return words.reshape(seq // 32, seq), live, jnp.sum(tied) / seq


def _table(visit, kind, qi, kj, head, run, size):
    """The walk's steps in ``visit``'s (row-major) order, as long as
    ``size``: the visited ones, then the last repeated as ``skip`` with
    neither end of an accumulation."""
    flat = lambda a: jnp.broadcast_to(a, visit.shape).reshape(-1)  # noqa: E731
    visit, kind, qi, kj, head, run = (flat(a) for a in (visit, kind, qi, kj, head, run))
    count = jnp.sum(visit, dtype=jnp.int32)
    (at,) = jnp.nonzero(visit, size=size, fill_value=0)
    j = jnp.arange(size)
    real = j < count
    at = jnp.where(real, at, at[count - 1])
    kind, qi, kj, head, run = (a[at] for a in (kind, qi, kj, head, run))
    first = real & ((j == 0) | (run != jnp.roll(run, 1)))
    last = real & ((j == count - 1) | (run != jnp.roll(run, -1)))
    rows = {_QI: qi, _KJ: kj, _KIND: jnp.where(real, kind, _SKIP), _FIRST: first, _LAST: last,
            _HEAD: head}
    return jnp.stack([rows[i] for i in range(6)]).astype(jnp.int32)


def walks(bits, live, group: int) -> Selection:
    """The attention kernels' two walks over the live tiles (``live [n, n]``,
    query tile by key tile, causal), in the causal walks' order: by query
    tile then key tile, and by key tile, then query head of the group, then
    query tile. A tile a walk accumulates that holds nothing (a key tile no
    query selected from) is opened and closed by one ``skip`` step on its
    diagonal, so that its result is written, as zeros."""
    n = live.shape[0]
    causal = n * (n + 1) // 2
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    live = live & (j <= i)
    lone = (i == j) & ~jnp.any(live, axis=1, keepdims=True)
    by_query = _table(live | lone, jnp.where(live, _SPARSE, _SKIP), i, j, 0, i, causal)
    # [key tile, head, query tile]
    kj, h, qi = jnp.arange(n)[:, None, None], jnp.arange(group)[None, :, None], jnp.arange(n)[None, None, :]
    seen = live.T[:, None, :]
    lone = (qi == kj) & (h == 0) & ~jnp.any(live, axis=0)[:, None, None]
    by_key = _table(seen | lone, jnp.where(seen, _SPARSE, _SKIP), qi, kj, h, kj, group * causal)
    return Selection(bits, by_query, by_key)


# ------------------------------------------------- the probability pass ---


def _probabilities(q_ref, k_ref, lse_ref, keep, scale):
    """The main attention's probabilities of a tile summed over its query
    heads and divided by their number, zero off the selection."""
    heads, group = q_ref.shape[0], q_ref.shape[0] // k_ref.shape[0]
    lse = lse_ref[...]
    p = None
    for h in range(heads):
        s = jax.lax.dot_general(q_ref[h], k_ref[h // group], _TRANS_B,
                                preferred_element_type=jnp.float32) * scale
        term = jnp.exp(s - lse[:, h:h + 1])
        p = term if p is None else p + term
    return jnp.where(keep, p * (1.0 / heads), 0.0)


def _pass_kernel(steps_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, bits_ref, out_ref,
                 m_ref, l_ref, a_ref, b_ref, *, scale):
    t = pl.program_id(0)
    kind, first, last = steps_ref[_KIND, t], steps_ref[_FIRST, t], steps_ref[_LAST, t]

    @pl.when(first == 1)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        for ref in (l_ref, a_ref, b_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(kind == _SPARSE)
    def _():
        keep = unpack_bits(bits_ref[...])
        p = _probabilities(q_ref, k_ref, lse_ref, keep, scale)
        score = _index_tile(qi_ref, ki_ref, w_ref)
        masked = jnp.where(keep, score, _NEG_INF)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(masked, axis=1, keepdims=True))
        l_ref[...] = jnp.exp(m_old - m_new) * l_ref[...] + jnp.sum(
            jnp.where(keep, jnp.exp(masked - m_new[:, :1]), 0.0), axis=1, keepdims=True)
        m_ref[...] = m_new
        a_ref[...] += jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0),
                              axis=1, keepdims=True)
        b_ref[...] += jnp.sum(p * jnp.where(keep, score, 0.0), axis=1, keepdims=True)

    @pl.when(last == 1)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(lane == STAT_LSE, m_ref[...] + jnp.log(l_ref[...]),
                                 jnp.where(lane == STAT_PLOGP, a_ref[...],
                                           jnp.where(lane == STAT_PI, b_ref[...], 0.0)))


def _pass_specs(q, k, qi, block):
    h, _, d = q.shape
    hkv, hi, di = k.shape[0], qi.shape[0], qi.shape[-1]
    rows = lambda *shape: pl.BlockSpec(shape, lambda t, s: (s[_QI, t],) + (0,) * (len(shape) - 1))  # noqa: E731
    return [pl.BlockSpec((h, block, d), lambda t, s: (0, s[_QI, t], 0)),
            pl.BlockSpec((hkv, block, d), lambda t, s: (0, s[_KJ, t], 0)),
            rows(block, h),
            pl.BlockSpec((hi, block, di), lambda t, s: (0, s[_QI, t], 0)),
            pl.BlockSpec((block, di), lambda t, s: (s[_KJ, t], 0)),
            rows(block, hi),
            pl.BlockSpec((block // 32, block), lambda t, s: (s[_QI, t], s[_KJ, t]))]


def probability_pass(q, k, lse, qi, ki, w, sel: Selection, block: int, dtype=jnp.bfloat16,
                     interpret=None):
    """Per query, over its selection: ``[L, 128]`` float32 whose lanes
    ``STAT_LSE``, ``STAT_PLOGP`` and ``STAT_PI`` are the indexer scores'
    log-sum-exp, ``sum p log p`` and ``sum p I``. ``q [H, L, D]``, ``k [Hkv,
    L, D]`` and ``lse [H, L]`` are the main attention's (read, never
    differentiated), ``qi [HI, L, DI]``, ``ki [L, DI]``, ``w [L, HI]`` the
    indexer's; the walk is the selection's ``by_query``."""
    if interpret is None:
        interpret = not on_tpu()
    seq = k.shape[1]
    steps = sel.by_query
    return pl.pallas_call(
        functools.partial(_pass_kernel, scale=q.shape[-1] ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps.shape[1],),
            in_specs=_pass_specs(q, k, qi, block),
            out_specs=pl.BlockSpec((block, _LANES), lambda t, s: (s[_QI, t], 0)),
            scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32) for _ in range(4)]),
        out_shape=jax.ShapeDtypeStruct((seq, _LANES), jnp.float32),
        name="sparse_probability_pass", **_params(interpret, ("arbitrary",)),
    )(steps, q.astype(dtype), k.astype(dtype), lse.T, qi.astype(dtype), ki.astype(dtype), w, sel.bits)


# ------------------------------------------------------ the indexer loss ---


def _index_bwd_kernel(steps_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, bits_ref, stats_ref,
                      dqi_ref, dw_ref, dki_ref, dqi_acc, dw_acc, *, scale):
    t = pl.program_id(0)
    kind, first, last = steps_ref[_KIND, t], steps_ref[_FIRST, t], steps_ref[_LAST, t]

    @pl.when(first == 1)
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(kind == _SPARSE)
    def _():
        keep = unpack_bits(bits_ref[...])
        p = _probabilities(q_ref, k_ref, lse_ref, keep, scale)
        ki, w = ki_ref[...], w_ref[...]
        root = ki.shape[-1] ** -0.5
        score = _index_tile(qi_ref, ki_ref, w_ref)
        q_index = jnp.where(keep, jnp.exp(jnp.where(keep, score, _NEG_INF)
                                          - stats_ref[:, STAT_LSE:STAT_LSE + 1]), 0.0)
        d_score = (q_index - p) * root  # dL_I,t / dI and I's own 1 / sqrt(DI)
        dki = None
        for j in range(qi_ref.shape[0]):
            s = _index_heads(qi_ref, ki, j)
            dw_acc[:, j:j + 1] += jnp.sum(d_score * jnp.maximum(s, 0.0), axis=1, keepdims=True)
            g = (jnp.where(s > 0, d_score, 0.0) * w[:, j:j + 1]).astype(ki.dtype)
            dqi_acc[j] += jnp.dot(g, ki, preferred_element_type=jnp.float32)
            term = jax.lax.dot_general(g, qi_ref[j], _TRANS_A, preferred_element_type=jnp.float32)
            dki = term if dki is None else dki + term
        dki_ref[...] = dki

    @pl.when(last == 1)
    def _():
        dqi_ref[...] = dqi_acc[...]
        dw_ref[...] = dw_acc[...]


def _index_backward(q, k, lse, qi, ki, w, sel, stats, block, dtype, interpret):
    """(dqI, dw, dkI) of ``sum_t L_I,t`` (not yet the mean)."""
    hi, seq, di = qi.shape
    n = seq // block
    steps = sel.by_query
    dqi, dw, dki = pl.pallas_call(
        functools.partial(_index_bwd_kernel, scale=q.shape[-1] ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps.shape[1],),
            in_specs=_pass_specs(q, k, qi, block) + [
                pl.BlockSpec((block, _LANES), lambda t, s: (s[_QI, t], 0))],
            out_specs=[pl.BlockSpec((hi, block, di), lambda t, s: (0, s[_QI, t], 0)),
                       pl.BlockSpec((block, hi), lambda t, s: (s[_QI, t], 0)),
                       pl.BlockSpec((None, block, di), lambda t, s: (s[_QI, t], s[_KJ, t], 0))],
            scratch_shapes=[pltpu.VMEM((hi, block, di), jnp.float32),
                            pltpu.VMEM((block, hi), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((hi, seq, di), jnp.float32),
                   jax.ShapeDtypeStruct((seq, hi), jnp.float32),
                   jax.ShapeDtypeStruct((n, seq, di), jnp.float32)],
        name="sparse_index_bwd", **_params(interpret, ("arbitrary",)),
    )(steps, q.astype(dtype), k.astype(dtype), lse.T, qi.astype(dtype), ki.astype(dtype), w, sel.bits,
      stats)
    # a (query tile, key tile) block the walk did not write holds whatever the memory held
    written = _written(sel.by_query, n)
    dki = jnp.sum(jnp.where(jnp.repeat(written, block, axis=1)[..., None], dki, 0.0), axis=0)
    return dqi, dw, dki


def _written(steps, n):
    """``[n, n]`` bool: the (query tile, key tile) blocks a walk computed."""
    live = steps[_KIND] == _SPARSE
    return jnp.zeros((n, n), bool).at[steps[_QI], steps[_KJ]].max(live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def indexer_loss(qi, ki, w, q, k, lse, sel, stats, block, dtype, interpret):
    """``L_I = mean_t (sum p log p - sum p I + logsumexp I)`` over ``S_t``,
    from :func:`probability_pass`'s ``stats``; its gradient reaches ``qi``,
    ``ki`` and ``w`` alone (the main attention's ``q``, ``k``, ``lse`` and
    ``stats`` are held constant)."""
    del qi, ki, w, q, k, lse, sel, block, dtype, interpret
    return _loss_of(stats)


def _loss_of(stats):
    return jnp.mean(stats[:, STAT_PLOGP] - stats[:, STAT_PI] + stats[:, STAT_LSE])


def _indexer_loss_fwd(qi, ki, w, q, k, lse, sel, stats, block, dtype, interpret):
    return _loss_of(stats), (qi, ki, w, q, k, lse, sel, stats)


def _indexer_loss_bwd(block, dtype, interpret, res, ct):
    qi, ki, w, q, k, lse, sel, stats = res
    if interpret is None:
        interpret = not on_tpu()
    dqi, dw, dki = _index_backward(q, k, lse, qi, ki, w, sel, stats, block, dtype, interpret)
    g = ct / ki.shape[0]
    return (g * dqi, g * dki, g * dw, None, None, None, None, None)


indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def index_flops(seq_len: int, index_heads: int, index_dim: int, heads: int, head_dim: int,
                pairs: float) -> dict:
    """Operations of the indexer's kernels at ``seq_len`` positions: the
    score kernel over the causal pairs (``HI`` products of ``DI``), and, over
    ``pairs`` selected pairs a head (as the live tiles hold them: the
    probability pass and the backward compute whole tiles), the probability
    pass (the main attention's ``H`` score products and the indexer's) and
    the backward (both again, and ``dqI`` and ``dkI``)."""
    causal = seq_len * (seq_len + 1) / 2
    index = 2.0 * index_heads * index_dim
    probs = 2.0 * heads * head_dim
    return {"scores": causal * index, "pass": pairs * (probs + index),
            "bwd": pairs * (probs + 3 * index)}
