"""Compile-and-agree checks for every Pallas kernel the TPU dispatch selects.

Interpret mode (all CI ever runs) cannot see the two ways a row-DMA kernel
goes wrong on silicon: a completion wait that returns early corrupts rows
silently, and one that never returns hangs the process. So each kernel is
compiled for the chip at the north-star table shape, timed, run once, and
compared with a reference that does not share its DMA machinery:

* ``ops/rowdma.py`` kernels against the plain ``jax.numpy`` gather/scatter;
* ``ops/fused_sgns.py`` steps against the same step with ``interpret=True``
  and full-f32 matmul precision. On COLLISION-FREE inputs — every row id
  distinct across the whole call — the hogwild, grouped, resident, dedup
  and composed steps have no race and no merge to disagree about, so the
  grouped-family kernels all share one interpreted reference.

The bar is relative to the largest update the call applies (``REL_TOL``).
What it has to tell apart, measured on a TPU v5e (PR 21): the kernels' MXU
contractions run at the platform's default precision, which rounds f32
operands to bf16 — every fused kernel sits 2.6e-3..3.1e-3 of an update from
the f32 reference for that reason alone (against an interpreted run at
default precision the flat kernel agrees bit for bit, the grouped one to
1.4e-4; the resident and dedup kernels' one-hot expansions add up to
1.7e-3) — while a row that missed a DMA is off by a whole update (1.0) and
a row read before its DMA landed by ~100.

``tools/compile_probe.py`` prints the table; ``chip_smoke.py`` runs the
grouped check on every start. Off the chip (tests) the "compiled" side is
interpret mode too, which only exercises this module's own control flow.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from swiftsnails_tpu.ops import fused_sgns as fs
from swiftsnails_tpu.ops import rowdma

ROWDMA_KERNELS = (
    "gather_rows", "scatter_add_rows", "scatter_write_rows",
    "scatter_adagrad_rows", "scatter_adagrad_fused_rows",
)
FUSED_KERNELS = (
    "fused_sgns_step", "fused_sgns_grouped_step", "fused_sgns_resident_step",
    "fused_sgns_dedup_step", "fused_sgns_dedup_resident_step",
)
KERNELS = ROWDMA_KERNELS + FUSED_KERNELS

# Update-relative agreement bar: max |kernel - reference| over max |update|.
# ~3x above the bf16 operand rounding of a default-precision contraction
# (2^-8 per product, 3.1e-3 measured), 100x below one missed update.
REL_TOL = 1e-2


def _table(key, shape, scale=0.1):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale)


def _compile(fn, *args, **static):
    """AOT lower+compile ``fn``; returns (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    return compiled, time.perf_counter() - t0


def _rel_err(got, want, before) -> Tuple[float, float]:
    """(max abs error, the same relative to the largest update applied)."""
    err = float(max(jnp.max(jnp.abs(g - w)) for g, w in zip(got, want)))
    upd = float(max(jnp.max(jnp.abs(w - b)) for w, b in zip(want, before)))
    return err, err / max(upd, 1e-30)


def collision_free_batch(capacity: int, n: int, window: int, pc: int,
                         pn: int, seed: int = 0, pad_frac: float = 0.3):
    """(centers [n], ctxs [n, 2*window] with -1 pads, pool_rows) in which no
    row id repeats within a table, so no two copies of the call can race."""
    rng = np.random.default_rng(seed)
    cw = 2 * window
    n_pool = (n // pc) * pn
    if n * cw + n_pool > capacity or n > capacity:
        raise ValueError(
            f"capacity {capacity} too small for {n} collision-free centers")
    centers = rng.permutation(capacity)[:n].astype(np.int32)
    out_ids = rng.permutation(capacity)[: n * cw + n_pool].astype(np.int32)
    ctxs = out_ids[: n * cw].reshape(n, cw)
    ctxs = np.where(rng.random((n, cw)) < pad_frac, -1, ctxs).astype(np.int32)
    return centers, ctxs, out_ids[n * cw:]


def _check_rowdma(name: str, capacity: int, dim: int, n: int,
                  interpret: bool) -> Dict:
    s = -(-dim // rowdma.ROW_LANES)
    shape = (capacity, s, rowdma.ROW_LANES)
    k_t, k_a, k_d = jax.random.split(jax.random.PRNGKey(1), 3)
    rng = np.random.default_rng(2)
    block = min(512, n)
    uniq = rng.permutation(capacity)[:n].astype(np.int32)
    uniq[-7:] = capacity  # padding slots: must be skipped, not written
    rows = jnp.asarray(uniq)
    deltas = _table(k_d, (n, s, rowdma.ROW_LANES), 0.05)
    lr, eps = 0.1, 1e-8
    kw = dict(block_rows=block, interpret=interpret)

    if name == "gather_rows":
        table = _table(k_t, shape)
        any_rows = jnp.asarray(rng.integers(0, capacity, n).astype(np.int32))
        fn, secs = _compile(rowdma.gather_rows, table, any_rows, **kw)
        got = fn(table, any_rows)
        err = float(jnp.max(jnp.abs(got - table[any_rows])))
        return {"compile_s": secs, "max_err": err, "rel_err": err,
                "agrees": err == 0.0}
    if name in ("scatter_add_rows", "scatter_write_rows"):
        kernel = getattr(rowdma, name)
        fn, secs = _compile(kernel, _table(k_t, shape), rows, deltas, **kw)
        got = fn(_table(k_t, shape), rows, deltas)
        before = _table(k_t, shape)
        at = before.at[rows]
        want = (at.add(deltas, mode="drop") if name == "scatter_add_rows"
                else at.set(deltas, mode="drop"))
        err, rel = _rel_err([got], [want], [before])
        return {"compile_s": secs, "max_err": err, "rel_err": rel,
                "agrees": err == 0.0}
    if name == "scatter_adagrad_rows":
        accum0 = jnp.abs(_table(k_a, shape)) + 0.01
        fn, secs = _compile(rowdma.scatter_adagrad_rows, _table(k_t, shape),
                            accum0 + 0, rows, deltas, lr, eps=eps, **kw)
        got = fn(_table(k_t, shape), accum0 + 0, rows, deltas, lr)
        before = (_table(k_t, shape), accum0)
        acc_rows = accum0[jnp.minimum(rows, capacity - 1)] + deltas * deltas
        new_p = (before[0][jnp.minimum(rows, capacity - 1)]
                 - lr * deltas * jax.lax.rsqrt(acc_rows + eps))
        want = (before[0].at[rows].set(new_p, mode="drop"),
                accum0.at[rows].set(acc_rows, mode="drop"))
        err, rel = _rel_err(got, want, before)
        return {"compile_s": secs, "max_err": err, "rel_err": rel,
                "agrees": rel <= REL_TOL}
    if name == "scatter_adagrad_fused_rows":
        # the kernel is told how many leading rows are live: everything
        # (the padding slots fall past the count) and a quarter, where the
        # slots past the count hold valid ids that must stay as they were
        fshape = (capacity, 2, rowdma.ROW_LANES)  # sublane 0 param, 1 accum
        g = deltas[:, :1, :]

        def fresh():
            t = _table(k_t, fshape)
            return t.at[:, 1, :].set(jnp.abs(t[:, 1, :]) + 0.01)

        fn, secs = _compile(rowdma.scatter_adagrad_fused_rows, fresh(), rows,
                            g, lr, n, eps=eps, **kw)
        before = fresh()
        cur = before[jnp.minimum(rows, capacity - 1)]
        acc = cur[:, 1:2, :] + g * g
        par = cur[:, 0:1, :] - lr * g * jax.lax.rsqrt(acc + eps)
        new = jnp.concatenate([par, acc], axis=1)
        err = rel = 0.0
        for count in (n - 7, n // 4):
            got = fn(fresh(), rows, g, lr, count)
            live = jnp.where(jnp.arange(n) < count, rows, capacity)
            want = before.at[live].set(new, mode="drop")
            e, r = _rel_err([got], [want], [before])
            err, rel = max(err, e), max(rel, r)
        return {"compile_s": secs, "max_err": err, "rel_err": rel,
                "agrees": rel <= REL_TOL, "live_counts": [n - 7, n // 4]}
    raise KeyError(name)


def _precision(name):
    """Matmul-precision scope for a reference run: ``"float32"`` forces
    full-f32 products, ``None`` leaves the platform default (what the
    compiled kernel's own contractions use)."""
    return (jax.default_matmul_precision(name) if name
            else contextlib.nullcontext())


@functools.lru_cache(maxsize=2)
def _grouped_reference(capacity, dim, n, window, pc, pn, lr, precision):
    """The interpreted grouped step on the collision-free batch — the one
    reference all four grouped-family kernels are compared with (kept on
    the device between checks; :func:`release_reference` frees it)."""
    args, kw = _fused_inputs(capacity, dim, n, window, pc, pn, lr)
    with _precision(precision):
        return fs.fused_sgns_grouped_step(*args, **kw, interpret=True)


def release_reference() -> None:
    """Drop the cached reference tables (2 GiB of device memory each at the
    north-star shape)."""
    _grouped_reference.cache_clear()


def _fused_inputs(capacity, dim, n, window, pc, pn, lr):
    s = -(-dim // rowdma.ROW_LANES)
    shape = (capacity, s, rowdma.ROW_LANES)
    k_in, k_out = jax.random.split(jax.random.PRNGKey(3))
    centers, ctxs, pool = collision_free_batch(capacity, n, window, pc, pn)
    args = (_table(k_in, shape), _table(k_out, shape), jnp.asarray(centers),
            jnp.asarray(ctxs), jnp.asarray(pool), jnp.float32(lr))
    kw = dict(lam=5.0 / pn, window=window, centers_per_block=pc, pool_size=pn)
    return args, kw


def _check_fused(name: str, capacity: int, dim: int, n: int, window: int,
                 pc: int, pn: int, u_cap: int, hot_rows: int,
                 interpret: bool, default_precision_too: bool) -> Dict:
    # the substep normalizes by n*(window+1); an lr of that order makes each
    # update ~1% of the row it lands on, so a missed or stale row shows
    lr = 0.2 * n * (window + 1)
    s = -(-dim // rowdma.ROW_LANES)
    shape = (capacity, s, rowdma.ROW_LANES)
    k_in, k_out = jax.random.split(jax.random.PRNGKey(3))
    before = (_table(k_in, shape), _table(k_out, shape))

    if name == "fused_sgns_step":
        # flat pair schema: one collision-free pair per center's first slot
        centers, ctxs, pool = collision_free_batch(
            capacity, n, 1, pc, pn, pad_frac=0.0)
        args = (jnp.asarray(centers), jnp.asarray(ctxs[:, 0]),
                jnp.asarray(pool), jnp.float32(lr / (window + 1)))
        kw = dict(lam=5.0 / pn, pairs_per_block=pc, pool_size=pn)
        step = fs.fused_sgns_step
        fn, secs = _compile(step, *before, *args, **kw, interpret=interpret)
        got = fn(_table(k_in, shape), _table(k_out, shape), *args)

        def reference(precision):
            with _precision(precision):
                return step(_table(k_in, shape), _table(k_out, shape), *args,
                            **kw, interpret=True)
    else:
        args, kw = _fused_inputs(capacity, dim, n, window, pc, pn, lr)
        extra = {
            "fused_sgns_grouped_step": {},
            "fused_sgns_resident_step": {"hot_rows": hot_rows},
            "fused_sgns_dedup_step": {"u_cap": u_cap},
            "fused_sgns_dedup_resident_step": {
                "u_cap": u_cap, "hot_rows": min(hot_rows, u_cap)},
        }[name]
        step = getattr(fs, name)
        fn, secs = _compile(step, *args, **kw, **extra, interpret=interpret)
        got = fn(*args)

        def reference(precision):
            return _grouped_reference(capacity, dim, n, window, pc, pn, lr,
                                      precision)

    want = reference("float32")
    err, rel = _rel_err(got[:2], want[:2], before)
    loss_err = abs(float(got[2]) - float(want[2])) / max(abs(float(want[2])), 1e-30)
    out = {"compile_s": secs, "max_err": err, "rel_err": rel,
           "loss_rel_err": loss_err,
           "agrees": rel <= REL_TOL and loss_err <= REL_TOL}
    if default_precision_too:
        out["rel_err_vs_default_precision"] = _rel_err(
            got[:2], reference(None)[:2], before)[1]
    return out


def check_kernel(name: str, *, capacity: int = 1 << 20, dim: int = 200,
                 n: int = 8192, window: int = 5, centers_per_block: int = 256,
                 pool_size: int = 64, u_cap: int = 384, hot_rows: int = 2048,
                 interpret: bool = False,
                 default_precision_too: bool = False) -> Dict:
    """Compile ``name`` at the given shape, run it once and compare with its
    reference. Returns ``{"kernel", "compile_s", "max_err", "rel_err",
    "agrees", ...}``; raises whatever the compiler raises.
    ``default_precision_too`` adds ``rel_err_vs_default_precision`` for the
    fused steps: the same comparison against an interpreted run whose
    contractions use the platform default, like the kernel's own."""
    if name in ROWDMA_KERNELS:
        out = _check_rowdma(name, capacity, dim, n, interpret)
    elif name in FUSED_KERNELS:
        out = _check_fused(name, capacity, dim, n, window, centers_per_block,
                           pool_size, u_cap, hot_rows, interpret,
                           default_precision_too)
    else:
        raise KeyError(f"unknown kernel {name!r}; known: {KERNELS}")
    return {"kernel": name, **out}
