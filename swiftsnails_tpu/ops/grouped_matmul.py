"""Grouped matrix products over rows sorted by owner, for the experts held.

The rows of ``x`` are assignments (token, expert) laid out by expert, each
expert's segment starting on a tile boundary (:func:`plan_rows`: sort by
owner, segments first, a count of the live tiles, no work past it: the
contract ``parallel/store.merge_duplicate_rows`` + ``live_count`` have for
table rows). A tile of ``tile`` rows therefore belongs to one expert,
``tile_owner[t]``, and the kernel is a plain matmul whose weight block is
picked by that scalar: no capacity, no dropped row, under any skew; tiles
past ``live_tiles`` do nothing and fetch nothing. Every expert gets at least
one tile (all padding if it has no row), so that the weight gradient of an
expert nobody chose is written as zeros, not left as it was.

* :func:`grouped_matmul` — ``x [R, K]``, ``w [E, K, N]`` -> ``[R, N]`` float32
  (``y[r] = x[r] @ w[tile_owner[r // tile]]``), differentiable in ``x`` and
  ``w`` (``custom_vjp``): ``dx`` is the same kernel on the transposed weight
  blocks, ``dw[e]`` sums ``x_tile.T @ dy_tile`` over the expert's tiles. Padding
  rows of ``x`` must be zero (then they add nothing to ``dw``); the rows past
  the live tiles, which no kernel writes, come back as zeros.
* :func:`plan_rows` — from each assignment's expert to the row layout.
* :func:`rows_of_tokens`, :func:`tokens_of_rows` — the moves between tokens and
  rows, forward and backward: loops over the live tiles only, a tile's rows
  gathered or added at a time, so that their time follows the assignments
  held like the kernels' (XLA's gather costs a fixed time a row).
* :func:`grouped_flops` — operations per call, for the benchmark's roofline.

Operands are rounded to ``dtype`` (bfloat16) for the MXU, accumulation and
every result are float32. Off the chip the kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.rowdma import on_tpu

_TRANS_B = (((1,), (1,)), ((), ()))
_TRANS_A = (((0,), (0,)), ((), ()))
_VMEM_LIMIT = 96 * 1024 * 1024
TILE = 512


def grouped_flops(live_rows: float, k: int, n: int) -> float:
    """Operations one product needs for ``live_rows`` assignments (forward,
    ``dx`` and ``dw`` alike): padding rows are not counted."""
    return 2.0 * live_rows * k * n


class RowPlan(NamedTuple):
    """Where each assignment's row lies. ``source [R]``: the assignment in
    each row (``A``, the number of assignments, for padding); ``token [R]``:
    its token (``T`` for padding); ``tile_owner [R / tile]`` the expert of
    each tile; ``live_tiles`` how many tiles hold anything; ``counts [E]``
    assignments per held expert."""

    source: jax.Array
    token: jax.Array
    tile_owner: jax.Array
    live_tiles: jax.Array
    counts: jax.Array


def rows_for(assignments: int, experts: int, tile: int = TILE) -> int:
    """Rows that hold any split of ``assignments`` over ``experts``: each
    expert wastes under one tile, an empty one takes one."""
    return (-(-assignments // tile) + experts) * tile


def plan_rows(owner: jax.Array, experts: int, tile: int = TILE) -> RowPlan:
    """``owner [T, k]``: the expert in ``[0, experts)`` of each of a token's
    ``k`` assignments, or ``experts`` if it is not held here. Sorted by owner
    (stable: a token's order within its expert is kept), each expert's
    segment padded to whole tiles."""
    tokens = owner.shape[0]
    owner = owner.reshape(-1)
    a = owner.shape[0]
    rows = rows_for(a, experts, tile)
    order = jnp.argsort(owner, stable=True).astype(jnp.int32)
    sorted_owner = owner[order]
    counts = jnp.sum(owner[None, :] == jnp.arange(experts, dtype=owner.dtype)[:, None],
                     axis=1, dtype=jnp.int32)
    tiles = jnp.maximum(1, -(-counts // tile))
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tile
    first_sorted = jnp.cumsum(counts) - counts
    e = jnp.minimum(sorted_owner, experts - 1)
    dest = first_row[e] + jnp.arange(a, dtype=jnp.int32) - first_sorted[e]
    dest = jnp.where(sorted_owner < experts, dest, rows)
    source = jnp.full((rows,), a, jnp.int32).at[dest].set(order, mode="drop")
    token = jnp.where(source < a, source // (a // tokens), tokens)
    tile_owner = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile, dtype=jnp.int32), side="right"),
        experts - 1).astype(jnp.int32)
    return RowPlan(source, token, tile_owner, tile_end[-1].astype(jnp.int32), counts)


# ------------------------------------------------- tokens <-> rows ---
# Each move is a loop over the live tiles (``fori_loop`` to a traced bound,
# inside a ``custom_vjp`` because such a loop has no transpose of its own):
# towards the rows a tile is gathered, towards the tokens it is added. A
# token has one row at most in a tile (a tile has one expert), and padding
# carries an index one past the end, which reads as zeros and is dropped
# when written.


def _tile_of(a, t, tile):
    return jax.lax.dynamic_slice_in_dim(a, t * tile, tile)


def _take(table, index):
    """``table[index]``, zeros where the index is one past the end."""
    return table.at[index].get(mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(y, token, live_tiles, tile):
    def gather(t, rows):
        return jax.lax.dynamic_update_slice_in_dim(
            rows, _take(y, _tile_of(token, t, tile)), t * tile, 0)

    return jax.lax.fori_loop(
        0, live_tiles, gather, jnp.zeros((token.shape[0], y.shape[1]), y.dtype))


def _rows_fwd(y, token, live_tiles, tile):
    # an empty slice stands for the number of tokens: a residual is no static value
    return _rows_of_tokens(y, token, live_tiles, tile), (token, live_tiles, y[:, :0])


def _rows_bwd(tile, res, g):
    token, live_tiles, like = res

    def add(t, dy):
        return dy.at[_tile_of(token, t, tile)].add(_tile_of(g, t, tile), mode="drop")

    dy = jax.lax.fori_loop(0, live_tiles, add, jnp.zeros((like.shape[0], g.shape[1]), g.dtype))
    return dy, None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def rows_of_tokens(y, plan: RowPlan, tile: int = TILE):
    """``y [T, d]`` -> ``[R, d]``: row ``r`` is token ``plan.token[r]``, zeros
    where it is padding or past the live tiles."""
    return _rows_of_tokens(y, plan.token, plan.live_tiles, tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tokens_of_rows(rows, gates, source, live_tiles, tile):
    tokens, k = gates.shape
    flat = gates.reshape(-1)

    def add(t, out):
        src = _tile_of(source, t, tile)
        weighted = _tile_of(rows, t, tile) * _take(flat, src)[:, None]
        return out.at[src // k].add(weighted, mode="drop")

    return jax.lax.fori_loop(0, live_tiles, add, jnp.zeros((tokens, rows.shape[1]), rows.dtype))


def _tokens_fwd(rows, gates, source, live_tiles, tile):
    return _tokens_of_rows(rows, gates, source, live_tiles, tile), (rows, gates, source, live_tiles)


def _tokens_bwd(tile, res, g):
    rows, gates, source, live_tiles = res
    k = gates.shape[1]
    flat = gates.reshape(-1)

    def back(t, carry):
        d_rows, d_flat = carry
        src = _tile_of(source, t, tile)
        g_rows = _take(g, src // k)
        d_rows = jax.lax.dynamic_update_slice_in_dim(
            d_rows, _take(flat, src)[:, None] * g_rows, t * tile, 0)
        d_flat = d_flat.at[src].set(jnp.sum(_tile_of(rows, t, tile) * g_rows, axis=-1), mode="drop")
        return d_rows, d_flat

    d_rows, d_flat = jax.lax.fori_loop(
        0, live_tiles, back, (jnp.zeros_like(rows), jnp.zeros_like(flat)))
    return d_rows, d_flat.reshape(gates.shape), None, None


_tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)


def tokens_of_rows(rows, gates, plan: RowPlan, tile: int = TILE):
    """``rows [R, d]``, ``gates [T, k]`` -> ``[T, d]``: each token's gate-weighted
    sum over its assignments whose expert is held."""
    return _tokens_of_rows(rows, gates, plan.source, plan.live_tiles, tile)


# ------------------------------------------------------------ kernels ---


def _last_live(t, live):
    """A tile past the live ones is the last live one again: nothing new is
    fetched, nothing is written back."""
    return jnp.minimum(t, live[0] - 1)


def _mm_kernel(owner_ref, live_ref, x_ref, w_ref, o_ref, *, dims):
    del owner_ref

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32)


def _dw_kernel(owner_ref, live_ref, x_ref, dy_ref, o_ref):
    t = pl.program_id(2)
    live = t < live_ref[0]
    first = jnp.logical_or(t == 0, owner_ref[t] != owner_ref[jnp.maximum(t - 1, 0)])

    @pl.when(jnp.logical_and(live, first))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        o_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], _TRANS_A, preferred_element_type=jnp.float32)


def _params(interpret, semantics):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


def _mm(x, w, tile_owner, live_tiles, tile, transposed, interpret):
    """``x [R, K] @ w[owner]`` with ``w [E, K, N]``, or, ``transposed``,
    ``x [R, N] @ w[owner].T``."""
    rows, width = x.shape
    e, k, n = w.shape
    out = k if transposed else n
    assert width == (n if transposed else k) and rows % tile == 0

    return pl.pallas_call(
        functools.partial(_mm_kernel, dims=_TRANS_B if transposed else (((1,), (0,)), ((), ()))),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, width), lambda t, own, live: (_last_live(t, live), 0)),
                pl.BlockSpec((None, k, n), lambda t, own, live: (own[_last_live(t, live)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, out), lambda t, own, live: (_last_live(t, live), 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, out), jnp.float32),
        name="grouped_matmul_dx" if transposed else "grouped_matmul",
        **_params(interpret, ("arbitrary",)),
    )(tile_owner, live_tiles.reshape(1), x, w)


def _split(width: int, most: int) -> int:
    """The widest block of a dimension: all of it, or the largest multiple
    of 128 under ``most`` that divides it."""
    if width <= most:
        return width
    return max(b for b in range(128, most + 1, 128) if width % b == 0)


def _dw(x, dy, tile_owner, live_tiles, experts, tile, interpret):
    """``dw [E, K, N]``: per expert, ``x_tile.T @ dy_tile`` summed over its
    tiles. The result is cut along whichever of K and N is the wider, so
    that a block stays resident while the expert's tiles go by."""
    rows, k = x.shape
    n = dy.shape[1]
    bk, bn = (_split(k, 512), n) if k >= n else (k, _split(n, 512))

    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // bk, n // bn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, bk), lambda i, j, t, own, live: (_last_live(t, live), i)),
                pl.BlockSpec((tile, bn), lambda i, j, t, own, live: (_last_live(t, live), j)),
            ],
            out_specs=pl.BlockSpec(
                (None, bk, bn), lambda i, j, t, own, live: (own[_last_live(t, live)], i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((experts, k, n), jnp.float32),
        name="grouped_matmul_dw",
        **_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(tile_owner, live_tiles.reshape(1), x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm(x, w, tile_owner, live_tiles, tile, dtype, interpret):
    return _mm(x.astype(dtype), w.astype(dtype), tile_owner, live_tiles, tile, False, interpret)


def _gmm_fwd(x, w, tile_owner, live_tiles, tile, dtype, interpret):
    x, w = x.astype(dtype), w.astype(dtype)
    y = _mm(x, w, tile_owner, live_tiles, tile, False, interpret)
    return y, (x, w, tile_owner, live_tiles)


def _gmm_bwd(tile, dtype, interpret, res, dy):
    x, w, tile_owner, live_tiles = res
    dy = dy.astype(dtype)
    dx = _mm(dy, w, tile_owner, live_tiles, tile, True, interpret)
    dw = _dw(x, dy, tile_owner, live_tiles, w.shape[0], tile, interpret)
    return _live_rows(dx, live_tiles, tile), dw, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _live_rows(y, live_tiles, tile):
    """Zeros in the rows past the live tiles, which no kernel wrote."""
    live = jnp.arange(y.shape[0], dtype=jnp.int32) < live_tiles * tile
    return jnp.where(live[:, None], y, 0)


def grouped_matmul(x, w, plan: RowPlan, tile: int = TILE, dtype=jnp.bfloat16, interpret=None):
    """``y[r] = x[r] @ w[plan.tile_owner[r // tile]]`` over the live tiles,
    zeros after them; float32 in and out, the operands rounded to ``dtype``
    for the MXU."""
    if interpret is None:
        interpret = not on_tpu()
    y = _gmm(x, w, plan.tile_owner, plan.live_tiles, tile, jnp.dtype(dtype), interpret)
    return _live_rows(y, plan.live_tiles, tile)
