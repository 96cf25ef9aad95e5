"""Grouped matrix products over rows sorted by owner, for the experts held.

The rows of ``x`` are assignments (token, expert) laid out by expert, each
expert's segment starting on a tile boundary (:func:`plan_rows`: sort by
owner, segments first, a count of the live tiles, no work past it: the
contract ``parallel/store.merge_duplicate_rows`` + ``live_count`` have for
table rows). A tile of ``tile`` rows therefore belongs to one expert,
``tile_owner[t]``, and the kernel is a plain matmul whose weight block is
picked by that scalar: no capacity, no dropped row, under any skew; a
kernel's grid ends at ``live_tiles``, so the tiles past them cost nothing.
Every expert gets at least one tile (all padding if it has no row), so that
the weight gradient of an expert nobody chose is written as zeros, not left
as it was; one with more rows than a tile takes as many as it needs, and
its consecutive tiles keep the weight block's index, which is then fetched
once.

**Who picks the tile.** The caller: every function here takes ``tile`` (the
default :data:`TILE`, 512 rows, is the largest of :data:`TILES`), and one
layout is built and walked at one tile. :func:`tile_for` is the rule, from
the one thing that matters: the assignments a held expert expects a call.
A tile is the least an expert costs, in rows multiplied and rows moved, so
it should be the smallest that an expert at twice the mean load still fits
(128, 256, else 512: whole ``(16, 128)`` operand tiles and whole passes of a
128-wide MXU). ``models/moelm.MoELMTrainer._read_shape`` calls it with
``positions * num_experts_per_tok / router_experts`` when it reads its
shapes, logs the choice, and counts what came of it
(``moe_tile_fill_share``): 51 assignments an expert take 128 rows, 512 or
768 take 512. At 128 rows a product no longer hides a weight block's
fetch: the kernels run at the pace of the bytes they must move (an
expert's weights read, its gradient written), which is what is left when
nothing is padding.

* :func:`grouped_swiglu` — the held experts' whole feed-forward, ``(silu(x @
  w_gate[e]) * (x @ w_up[e])) @ w_down[e]`` with ``e`` the tile's owner,
  differentiable in the rows and the three weights (``custom_vjp``). Every
  array of the row layout between its argument and its result is written and
  read by a kernel whose grid ends at the live tiles: the
  rounding of an operand, SwiGLU, its derivative and the sum of the two
  products that make ``dx`` happen on a tile in VMEM. Forward: one kernel for
  the gate and up products and SwiGLU (it keeps the two float32 products
  where the backward pass follows), one for the down product. Backward: ``dy
  @ w_down.T`` with SwiGLU's derivative behind it (``dg``, ``du``), ``dg @
  w_gate.T + du @ w_up.T`` in one kernel, and ``dw[e]``, the sum of ``x_tile.T
  @ dy_tile`` over the expert's tiles, three times. **What the rows past the
  live tiles hold is unspecified**, in the result, in every intermediate and
  in the rows' gradient: no kernel writes them, nothing reads them (the moves
  stop at the live tiles too), and XLA walks no array of the layout's size.
  Padding rows INSIDE a live tile must be zero in ``rows`` (the move fills
  them); then they add nothing to ``dw``.
* :func:`grouped_matmul` — one product, ``x [R, K]``, ``w [E, K, N]`` -> ``[R,
  N]`` float32 (``y[r] = x[r] @ w[tile_owner[r // tile]]``), differentiable in
  ``x`` and ``w``, out of the same kernels; its result and its ``dx`` are
  zeros past the live tiles (an XLA pass over the layout: the tests'
  reference for the fused form, on no model's path).
* :func:`plan_rows` — from each assignment's expert to the row layout.
* :func:`rows_of_tokens`, :func:`tokens_of_rows` — the moves between tokens and
  rows, forward and backward: loops over the live tiles only, a tile's rows
  gathered or added at a time, so that their time follows the assignments
  held like the kernels' (XLA's gather costs a fixed time a row). A move
  towards the rows fills the live tiles of a buffer nobody zeroed; a move
  towards the tokens adds a row as whole ``(8, 128)`` tiles.
* :func:`grouped_flops` — operations per call, for the benchmark's roofline.

Operands are rounded to ``dtype`` (bfloat16) for the MXU, each once, in the
kernel that multiplies it; accumulation, ``silu``, its derivative and every
product's result are float32. Every ``pallas_call`` here has ``grouped_matmul``
in its name: the benchmark finds the expert kernels by it. Off the chip the
kernels run in interpret mode (``rowdma.on_tpu``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftsnails_tpu.ops.rowdma import on_tpu

_TRANS_B = (((1,), (1,)), ((), ()))
_TRANS_A = (((0,), (0,)), ((), ()))
_VMEM_LIMIT = 96 * 1024 * 1024
TILES = (128, 256, 512)  # the row tiles a caller picks from: whole (16, 128) operand tiles, whole MXU passes
TILE = TILES[-1]


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


def tile_for(expected: float) -> int:
    """The row tile for held experts that expect ``expected`` assignments
    each a call: the smallest of :data:`TILES` that holds twice that (an
    expert at twice the mean load still fits one tile), else the largest. An
    expert with more rows than a tile takes as many tiles as it needs."""
    return next((tile for tile in TILES if tile >= 2 * expected), TILES[-1])


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
# when written. A loop towards the rows starts from a buffer nobody filled
# (:func:`_unfilled`) and writes its live tiles: the rows past them are
# whatever was there.


def _tile_of(a, t, tile):
    return jax.lax.dynamic_slice_in_dim(a, t * tile, tile)


def _unfilled(shape, dtype, after):
    """``shape`` of whatever the memory held: the result of a kernel that
    writes nothing, which runs once ``after`` (a scalar) is there and, having
    side effects, is never merged with its like (two loops would then share a
    buffer, and XLA copies it for the second). (``lax.empty`` is the same
    buffer with no operand: XLA allocates all fifteen of a step's at the
    program's start and holds them, 5.8 GB of Moonlight's step, which then no
    longer fits the chip.)"""
    if not on_tpu():
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda after_ref, o_ref: None,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        name="grouped_matmul_unfilled_rows",
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(after.reshape(1))


def _slabs(a):
    """``[n, d]`` as ``[n, d / 128, 128]`` where ``d`` allows it: XLA's
    scatter-add then moves a row as whole ``(8, 128)`` tiles, 55 ns a row of
    2,048 for 96 on the chip (its gather reads 42 either way)."""
    return a.reshape(a.shape[0], -1, 128) if a.shape[1] % 128 == 0 else a


def _take(table, index):
    """``table[index]``, zeros where the index is one past the end."""
    return table.at[index].get(mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(y, token, live_tiles, tile):
    def gather(t, rows):
        return jax.lax.dynamic_update_slice_in_dim(
            rows, _take(y, _tile_of(token, t, tile)), t * tile, 0)

    return jax.lax.fori_loop(
        0, live_tiles, gather, _unfilled((token.shape[0], y.shape[1]), y.dtype, live_tiles))


def _rows_fwd(y, token, live_tiles, tile):
    # an empty slice stands for the number of tokens: a residual is no static value
    return _rows_of_tokens(y, token, live_tiles, tile), (token, live_tiles, y[:, :0])


def _rows_bwd(tile, res, g):
    token, live_tiles, like = res

    def add(t, dy):
        return dy.at[_tile_of(token, t, tile)].add(_slabs(_tile_of(g, t, tile)), mode="drop")

    shape = (like.shape[0], g.shape[1])
    dy = jax.lax.fori_loop(0, live_tiles, add, _slabs(jnp.zeros(shape, g.dtype)))
    return dy.reshape(shape), None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def rows_of_tokens(y, plan: RowPlan, tile: int = TILE):
    """``y [T, d]`` -> ``[R, d]``: row ``r`` of a live tile is token
    ``plan.token[r]``, or zeros where it is padding; the rows past the live
    tiles are not written (unspecified), and neither is their part of
    :func:`tokens_of_rows`' gradient."""
    return _rows_of_tokens(y, plan.token, plan.live_tiles, tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tokens_of_rows(rows, gates, source, live_tiles, tile):
    tokens, k = gates.shape
    flat = gates.reshape(-1)

    def add(t, out):
        src = _tile_of(source, t, tile)
        weighted = _tile_of(rows, t, tile) * _take(flat, src)[:, None]
        return out.at[src // k].add(_slabs(weighted), mode="drop")

    shape = (tokens, rows.shape[1])
    return jax.lax.fori_loop(
        0, live_tiles, add, _slabs(jnp.zeros(shape, rows.dtype))).reshape(shape)


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
        0, live_tiles, back, (_unfilled(rows.shape, rows.dtype, live_tiles), jnp.zeros_like(flat)))
    return d_rows, d_flat.reshape(gates.shape), None, None


_tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)


def tokens_of_rows(rows, gates, plan: RowPlan, tile: int = TILE):
    """``rows [R, d]``, ``gates [T, k]`` -> ``[T, d]``: each token's gate-weighted
    sum over its assignments whose expert is held."""
    return _tokens_of_rows(rows, gates, plan.source, plan.live_tiles, tile)


# ------------------------------------------------------------ kernels ---
# One grid step a LIVE tile: the grid's extent over the tiles is
# ``live_tiles`` itself, a traced scalar (every expert has a tile, so it is
# at least 1), and ``tile_owner`` is a prefetched scalar array. No step runs
# for a tile past the live ones, so a kernel's time follows the live tiles
# at any tile height (a layout of 136 tiles of 128 rows with 8 live is 8
# steps, not 136) and the rows past them are never read and never written.
# Operands are rounded to ``dtype`` in VMEM.


def _mm_kernel(owner_ref, *refs, dims, dtype):
    """``o = x_1 . w_1 + x_2 . w_2 + ...``: ``refs`` are the ``x``, the ``w``, ``o``."""
    del owner_ref
    n = len(refs) // 2
    o_ref = refs[-1]
    first, *more = [
        jax.lax.dot_general(x_ref[...].astype(dtype), w_ref[...], dims,
                            preferred_element_type=jnp.float32)
        for x_ref, w_ref in zip(refs[:n], refs[n:])]
    o_ref[...] = sum(more, first)


def _swiglu_kernel(owner_ref, x_ref, wg_ref, wu_ref, h_ref, *kept_refs, dtype):
    """``h = silu(x . w_gate) * (x . w_up)`` rounded to ``dtype``; where the
    backward pass is going to read them, the two products (float32) and the
    rounded ``x`` too."""
    del owner_ref
    x = x_ref[...].astype(dtype)
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h_ref[...] = (jax.nn.silu(g) * u).astype(dtype)
    for ref, value in zip(kept_refs, (g, u, x)):
        ref[...] = value


def _dswiglu_kernel(owner_ref, dy_ref, g_ref, u_ref, wd_ref, dg_ref, du_ref, *, dtype):
    """``dh = dy . w_down.T``, then SwiGLU's derivative on it: ``dg = dh * u *
    silu'(g)`` and ``du = dh * silu(g)``, each rounded to ``dtype``."""
    del owner_ref
    dh = jax.lax.dot_general(dy_ref[...].astype(dtype), wd_ref[...], _TRANS_B,
                             preferred_element_type=jnp.float32)
    g, u = g_ref[...], u_ref[...]
    s = jax.nn.sigmoid(g)
    dg_ref[...] = (dh * u * (s * (1.0 + g * (1.0 - s)))).astype(dtype)
    du_ref[...] = (dh * (g * s)).astype(dtype)


def _dw_kernel(owner_ref, x_ref, dy_ref, o_ref, *, dtype):
    t = pl.program_id(2)

    @pl.when(jnp.logical_or(t == 0, owner_ref[t] != owner_ref[jnp.maximum(t - 1, 0)]))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(dtype), dy_ref[...].astype(dtype), _TRANS_A,
        preferred_element_type=jnp.float32)


def _params(interpret, semantics):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


class _Tiles(NamedTuple):
    """What every kernel call of a layer shares."""

    owner: jax.Array
    live: jax.Array
    tile: int
    dtype: Any
    interpret: bool


def _by_tile(kernel, name, tiles: _Tiles, rows, weights, outs):
    """``kernel`` over the live tiles: ``rows`` (``[R, *]`` each) and the
    results (``outs``: a ``(width, dtype)`` each, ``[R, width]``) go by whole
    tiles, ``weights`` (``[E, K, N]`` each) by the block of the tile's owner."""
    n_rows = rows[0].shape[0]
    assert n_rows % tiles.tile == 0 and all(r.shape[0] == n_rows for r in rows)

    def row_spec(width):
        return pl.BlockSpec((tiles.tile, width), lambda t, own: (t, 0))

    def weight_spec(w):
        return pl.BlockSpec((None,) + w.shape[1:], lambda t, own: (own[t], 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, dtype=tiles.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles.live,),
            in_specs=[row_spec(r.shape[1]) for r in rows] + [weight_spec(w) for w in weights],
            out_specs=[row_spec(width) for width, _ in outs],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), dt) for width, dt in outs],
        name=name,
        **_params(tiles.interpret, ("arbitrary",)),
    )(tiles.owner, *rows, *weights)


def _mm(xs, ws, tiles: _Tiles, transposed=False):
    """``sum_i xs[i] [R, K] @ ws[i][owner]`` with ``ws[i] [E, K, N]`` (already
    in ``dtype``), or, ``transposed``, ``sum_i xs[i] [R, N] @ ws[i][owner].T``."""
    k, n = ws[0].shape[1:]
    assert all(x.shape[1] == (n if transposed else k) for x in xs)
    dims = _TRANS_B if transposed else (((1,), (0,)), ((), ()))
    return _by_tile(functools.partial(_mm_kernel, dims=dims),
                    "grouped_matmul_dx" if transposed else "grouped_matmul",
                    tiles, xs, ws, [(k if transposed else n, jnp.float32)])[0]


def _split(width: int, most: int) -> int:
    """The widest block of a dimension: all of it, or the largest multiple
    of 128 under ``most`` that divides it."""
    if width <= most:
        return width
    return max(b for b in range(128, most + 1, 128) if width % b == 0)


def _dw(x, dy, experts, tiles: _Tiles):
    """``dw [E, K, N]``: per expert, ``x_tile.T @ dy_tile`` summed over its
    tiles. The result is cut along whichever of K and N is the wider, so
    that a block stays resident while the expert's tiles go by; the cut is
    512 wide at the largest tile and widens as the tile shrinks (a row
    block ``[tile, cut]`` holds as much at any tile), because every block of
    the cut is one more pass over the live tiles: at 128 rows a product is
    too short to hide a pass's steps behind, and the result's write, once an
    expert and block, sets the pace."""
    k, n = x.shape[1], dy.shape[1]
    tile = tiles.tile
    most = 512 * max(1, TILE // tile)
    bk, bn = (_split(k, most), n) if k >= n else (k, _split(n, most))

    return pl.pallas_call(
        functools.partial(_dw_kernel, dtype=tiles.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // bk, n // bn, tiles.live),
            in_specs=[
                pl.BlockSpec((tile, bk), lambda i, j, t, own: (t, i)),
                pl.BlockSpec((tile, bn), lambda i, j, t, own: (t, j)),
            ],
            out_specs=pl.BlockSpec((None, bk, bn), lambda i, j, t, own: (own[t], i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((experts, k, n), jnp.float32),
        name="grouped_matmul_dw",
        **_params(tiles.interpret, ("parallel", "parallel", "arbitrary")),
    )(tiles.owner, x, dy)


def _tiles(plan: RowPlan, tile, dtype, interpret):
    """(the plan's two scalar operands, what is static), as the ``custom_vjp``
    functions take them; together they are a :class:`_Tiles`."""
    interpret = (not on_tpu()) if interpret is None else interpret
    return (plan.tile_owner, plan.live_tiles), (tile, jnp.dtype(dtype), interpret)


# ---------------------------------------------------------- one product ---


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, scalars, static):
    tiles = _Tiles(*scalars, *static)
    return _mm([x], [w.astype(tiles.dtype)], tiles)


def _gmm_fwd(x, w, scalars, static):
    tiles = _Tiles(*scalars, *static)
    w = w.astype(tiles.dtype)
    return _mm([x], [w], tiles), (x, w, scalars)


def _gmm_bwd(static, res, dy):
    x, w, scalars = res
    tiles = _Tiles(*scalars, *static)
    dx = _mm([dy], [w], tiles, transposed=True)
    return _live_rows(dx, tiles), _dw(x, dy, w.shape[0], tiles), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _live_rows(y, tiles: _Tiles):
    """Zeros in the rows past the live tiles, which no kernel wrote."""
    live = jnp.arange(y.shape[0], dtype=jnp.int32) < tiles.live * tiles.tile
    return jnp.where(live[:, None], y, 0)


def grouped_matmul(x, w, plan: RowPlan, tile: int = TILE, dtype=jnp.bfloat16, interpret=None):
    """``y[r] = x[r] @ w[plan.tile_owner[r // tile]]`` over the live tiles,
    zeros after them; float32 in and out, the operands rounded to ``dtype``
    for the MXU."""
    scalars, static = _tiles(plan, tile, dtype, interpret)
    return _live_rows(_gmm(x, w, scalars, static), _Tiles(*scalars, *static))


# ------------------------------------------------ an expert's whole SwiGLU ---


def _swiglu(x, wg, wu, tiles: _Tiles, keep: bool):
    """(hidden in ``dtype``,) or, ``keep``, (hidden, gate, up: the two float32,
    ``x`` in ``dtype``)."""
    width = wg.shape[2]
    outs = [(width, tiles.dtype)]
    if keep:
        outs += [(width, jnp.float32)] * 2 + [(x.shape[1], tiles.dtype)]
    return _by_tile(_swiglu_kernel, "grouped_matmul_swiglu", tiles, [x], [wg, wu], outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gsw(x, wg, wu, wd, scalars, static):
    tiles = _Tiles(*scalars, *static)
    (hidden,) = _swiglu(x, wg.astype(tiles.dtype), wu.astype(tiles.dtype), tiles, keep=False)
    return _mm([hidden], [wd.astype(tiles.dtype)], tiles)


def _gsw_fwd(x, wg, wu, wd, scalars, static):
    tiles = _Tiles(*scalars, *static)
    wg, wu, wd = (w.astype(tiles.dtype) for w in (wg, wu, wd))
    hidden, g, u, x = _swiglu(x, wg, wu, tiles, keep=True)
    return _mm([hidden], [wd], tiles), (x, wg, wu, wd, hidden, g, u, scalars)


def _gsw_bwd(static, res, dy):
    x, wg, wu, wd, hidden, g, u, scalars = res
    tiles = _Tiles(*scalars, *static)
    experts, _, width = wg.shape
    dg, du = _by_tile(_dswiglu_kernel, "grouped_matmul_dswiglu", tiles, [dy, g, u], [wd],
                      [(width, tiles.dtype)] * 2)
    dx = _mm([dg, du], [wg, wu], tiles, transposed=True)
    return (dx, _dw(x, dg, experts, tiles), _dw(x, du, experts, tiles),
            _dw(hidden, dy, experts, tiles), None)


_gsw.defvjp(_gsw_fwd, _gsw_bwd)


def grouped_swiglu(rows, w_gate, w_up, w_down, plan: RowPlan, tile: int = TILE,
                   dtype=jnp.bfloat16, interpret=None):
    """The held experts' feed-forward on their rows: ``(silu(x @ w_gate[e]) *
    (x @ w_up[e])) @ w_down[e]`` with ``e = plan.tile_owner[r // tile]``, for
    the rows of the live tiles; ``rows [R, K]`` float32, ``w_gate``, ``w_up``
    ``[E, K, N]``, ``w_down [E, N, K]`` -> ``[R, K]`` float32. Differentiable
    in all four. Nothing reads a row past the live tiles and nothing writes
    one: what the result and the rows' gradient hold there is unspecified.
    The operands of the five products (the rows, the hidden rows, and in the
    backward pass ``dy``, ``dg``, ``du``) are rounded to ``dtype``; the
    products' results, ``silu`` and its derivative are float32."""
    return _gsw(rows, w_gate, w_up, w_down, *_tiles(plan, tile, dtype, interpret))
