"""Weights from ``--seed``, made on the device, as logical float32 arrays.
Which leaves a model has, and of what shape, says its file under
``benchmark/models/`` (``layout``); the rule of each leaf is stated in the
configuration's file under ``init``: ``{"rule": "uniform", "scale": s}`` is
U(-s, s), ``{"rule": "normal_he"}`` is N(0, 2/fan_in) on a matrix,
``{"rule": "zeros"}`` is zeros.

A leaf is made in blocks of ``BLOCK`` rows, block b from
``fold_in(leaf's key, b)``, so that whoever needs a table in another layout
(the program's packed tiles), or only a sum over it (the change since the
start), maps over the blocks and never holds the logical table beside the
program's: the peak the run reports is then the program's, not the
harness's. ``make_weights`` gives the leaves whole, for the references,
which run once the peak has been read.

A narrow table (Wide&Deep's 17-wide rows) is made lane-dense, ``[rows / group,
128]`` with ``group`` rows side by side at ``stride`` lanes each and zeros in
the lanes past ``dim``: a ``[16777216, 17]`` float32 array would be padded to
128 lanes on the chip, 8 GiB for 1.1 GiB of numbers. ``table_rows`` takes
logical rows back out.
"""

import functools

import jax
import jax.numpy as jnp

BLOCK = 1 << 16
LANES = 128


def _leaf(key, shape, spec):
    if "stride" in spec:
        dense = _leaf(key, shape, {k: v for k, v in spec.items() if k != "stride"})
        lane = (jnp.arange(shape[-1]) % spec["stride"]) < spec["dim"]
        return jnp.where(lane[None, :], dense, 0.0)
    rule = spec["rule"]
    if rule == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if rule == "uniform":
        s = float(spec["scale"])
        return jax.random.uniform(key, shape, jnp.float32, -s, s)
    if rule == "normal_he":
        return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / shape[0])
    raise ValueError(f"unknown init rule {rule!r}")


def blocks_of(shape):
    """(number of blocks, rows to a block) of a leaf: a table of more than
    ``BLOCK`` rows, a whole number of blocks, is cut; any other leaf is one."""
    if len(shape) >= 2 and shape[0] > BLOCK and shape[0] % BLOCK == 0:
        return shape[0] // BLOCK, BLOCK
    return 1, (shape[0] if shape else 1)


def map_blocks(seed, layout, name, fn, *per_block):
    """``fn(block of the leaf [, block of each array in per_block])`` over
    the leaf's blocks in order, results stacked. For use inside a jit;
    ``seed`` may be traced. The arrays in ``per_block`` have the leaf's
    number of rows."""
    i, shape, spec = next((i, s, dict(sp)) for i, (n, s, sp) in enumerate(layout) if n == name)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    nb, rows = blocks_of(shape)
    block_shape = ((rows,) + tuple(shape[1:])) if shape else ()
    xs = (jnp.arange(nb),) + tuple(a.reshape((nb, rows) + a.shape[1:]) for a in per_block)
    return jax.lax.map(
        lambda x: fn(_leaf(jax.random.fold_in(key, x[0]), block_shape, spec), *x[1:]), xs)


@functools.partial(jax.jit, static_argnames=("layout", "names"))
def _make(seed, layout, names):
    shapes = {n: s for n, s, _ in layout}
    return {n: map_blocks(seed, layout, n, lambda b: b).reshape(shapes[n]) for n in names}


def make_weights(layout, seed: int, only=None):
    """{leaf name: float32 array}, whole, from the seed; ``only`` restricts
    to some leaves (same values: a leaf's key is its position)."""
    names = tuple(only) if only is not None else tuple(n for n, _, _ in layout)
    return _make(jnp.uint32(seed & 0xFFFFFFFF), layout, names)


def layout_of(leaves, init: dict):
    """((name, shape, rule items), ...), hashable, from [(name, shape)] and
    the configuration's ``init``: a leaf takes the rule under its own name or
    under its name without the trailing digits (``w0`` -> ``w``)."""
    return tuple(
        (name, tuple(shape),
         tuple(sorted(init[name if name in init else name.rstrip("0123456789")].items())))
        for name, shape in leaves)


def small_rows(dim: int):
    """(rows per 128-lane tile, lanes per row) of a narrow table: the stride
    is the smallest power of two that holds ``dim``."""
    stride = 1
    while stride < dim:
        stride *= 2
    if stride > LANES:
        raise ValueError(f"a row of {dim} does not fit a tile")
    return LANES // stride, stride


def table_rows(tiles, rows, dim: int):
    """Logical rows ``rows`` of a lane-dense table -> [n, dim]."""
    group, stride = small_rows(dim)
    rows = jnp.asarray(rows)
    picked = jnp.take(tiles, rows // group, axis=0).reshape(-1, group, stride)
    return jnp.take_along_axis(picked, (rows % group)[:, None, None], axis=1)[:, 0, :dim]
