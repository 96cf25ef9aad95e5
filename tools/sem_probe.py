#!/usr/bin/env python
"""DMA-semaphore unit probe + chunked-wait lab.

The round-5 ablation (docs/ARCHITECTURE.md) found every kernel family
pays ~60ns PER SCALAR OP in the DMA issue+wait loops — the wait loop is
half those ops. ``pltpu.semaphore_wait`` rejects DMA-typed semaphores at
trace time, so the only batched wait is a LARGER DESCRIPTOR: the wait
amount is compiler-derived from the descriptor (``tpu.wait_dma2``), and
production kernels already exploit that equal-size copies retire each
other's waits across different arrays (ops/fused_sgns.py wait_all). If
completion increments are additive across rows, ONE wait on a
``[CH, S, 128]`` view retires CH row-copies.

Rows use the production layout: tables are ``[V, S, 128]`` and a row is
the ``[S, 128]`` unit at an untiled leading index (2-D refs hit Mosaic's
8-row tiling alignment on single-row slices; 3-D leading-dim indexing is
what ops/fused_sgns.py ships).

Experiments (in hang-proof order):
  1. unit: issue one copy, poll ``semaphore_read`` (bounded), report the
     increment; drain with the matching descriptor wait. S in {1,2,4},
     an 8-row descriptor, and bf16 establish the scaling law.
  2. chunk correctness: issue 64 scattered row copies, poll until the
     expected total is OBSERVED present, only then issue the one-shot
     [64, S, 128] descriptor wait (pl.when-guarded: it cannot block on
     an amount that never arrives); verify gathered bytes.
  3. timing: K-copy blocks, per-copy wait loop vs chunked waits.

Run alone on the chip (one process per chip):

    python tools/sem_probe.py [--quick]
"""

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--dim", type=int, default=200)
    args = p.parse_args(argv)

    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    print(f"devices: {jax.devices()}", flush=True)

    # ---- 1. unit probe ---------------------------------------------------
    def unit_kernel(x_ref, o_ref, buf, sem, *, rows):
        if rows == 1:
            cp = lambda: pltpu.make_async_copy(x_ref.at[0], buf.at[0], sem)
        else:
            cp = lambda: pltpu.make_async_copy(x_ref, buf, sem)
        cp().start()

        def poll(_, mx):
            return jnp.maximum(mx, pltpu.semaphore_read(sem))

        mx = jax.lax.fori_loop(0, 100_000, poll, jnp.int32(0))
        o_ref[...] = jnp.full(o_ref.shape, mx, jnp.int32)
        cp().wait()

    def probe_unit(rows, s, dtype):
        n = max(rows, 8)
        x = jnp.ones((n, s, 128), dtype)
        out = pl.pallas_call(
            functools.partial(unit_kernel, rows=rows),
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((n, s, 128), dtype),
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
        )(x)
        return int(out[0, 0])

    units = {}
    for rows, s, dtype, tag in (
        (1, 1, jnp.float32, "f32[1,128]"),
        (1, 2, jnp.float32, "f32[2,128]"),
        (1, 4, jnp.float32, "f32[4,128]"),
        (8, 2, jnp.float32, "f32[8,2,128]"),
        (1, 2, jnp.bfloat16, "bf16[2,128]"),
    ):
        u = units[tag] = probe_unit(rows, s, dtype)
        print(f"unit probe {tag:>12}: sem observed = {u}", flush=True)

    if all(v == 1 for v in units.values()):
        print("=> completions increment 1 PER COPY; chunked descriptor "
              "waits would retire too much — NOT usable", flush=True)
    linear = units["f32[8,2,128]"] == 8 * units["f32[2,128]"]
    print(f"=> row-additive increments: {linear}", flush=True)

    S = -(-args.dim // 128)
    u_row = units["f32[2,128]"] if S == 2 else probe_unit(1, S, jnp.float32)
    print(f"row unit f32[{S},128]: {u_row}", flush=True)

    if not linear:
        # without row-additive completions the mx >= CH*u_row poll below can
        # pass (e.g. fixed 1-per-copy increments) while the one-shot chunk
        # descriptor's compiler-derived decrement exceeds what ever arrives
        # — an unbounded in-kernel wait. Nothing downstream is safe to run.
        print("=> increments are not row-additive; chunked waits are "
              "unsound on this platform — stopping before experiment 2",
              flush=True)
        return

    # ---- 2. chunk-wait correctness (guarded) ----------------------------
    CH = 64
    V = 4096

    def chunk_kernel(rows_ref, x_ref, o_ref, flag_ref, buf, sem, *, unit):
        def issue(k, _):
            pltpu.make_async_copy(x_ref.at[rows_ref[k]], buf.at[k],
                                  sem).start()
            return 0

        jax.lax.fori_loop(0, CH, issue, 0)
        want = jnp.int32(CH * unit)

        def poll(_, mx):
            return jnp.maximum(mx, pltpu.semaphore_read(sem))

        mx = jax.lax.fori_loop(0, 200_000, poll, jnp.int32(0))
        ok = mx >= want
        flag_ref[...] = jnp.full(
            flag_ref.shape, jnp.where(ok, mx, -mx), jnp.int32)

        @pl.when(ok)
        def _():
            # the amount is KNOWN present: this cannot block indefinitely
            pltpu.make_async_copy(x_ref.at[:CH], buf, sem).wait()

        @pl.when(jnp.logical_not(ok))
        def _():
            def w(k, _):
                pltpu.make_async_copy(x_ref.at[0], buf.at[0], sem).wait()
                return 0

            jax.lax.fori_loop(0, CH, w, 0)

        o_ref[...] = buf[...]

    rng = np.random.default_rng(0)
    x_np = rng.random((V, S, 128), dtype=np.float32)
    rows_np = rng.integers(0, V, CH).astype(np.int32)
    out, flag = pl.pallas_call(
        functools.partial(chunk_kernel, unit=u_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(
                pl.BlockSpec((CH, S, 128), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec((8, 128), lambda i, *_: (0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((CH, S, 128), jnp.float32),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((CH, S, 128), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(jnp.asarray(rows_np), jnp.asarray(x_np))
    f = int(flag[0, 0])
    err = float(np.abs(np.asarray(out) - x_np[rows_np]).max())
    print(f"chunk wait: observed={abs(f)} expected={CH * u_row} "
          f"one-shot={'YES' if f > 0 else 'NO (fell back per-copy)'} "
          f"gather max err={err}", flush=True)

    if args.quick or f <= 0 or err != 0.0:
        return

    # ---- 3. timing: per-copy vs chunked waits ---------------------------
    K = 1856  # the bench shape's copies/block (docs/ARCHITECTURE.md)
    B = 64
    VB = 100_000
    rows2_np = rng.integers(0, VB, (B, K)).astype(np.int32)

    def pipe_kernel(rows_ref, x_ref, o_ref, buf, sem, *, chunked):
        i = pl.program_id(0)

        def issue(k, _):
            pltpu.make_async_copy(
                x_ref.at[rows_ref[i * K + k]], buf.at[k], sem
            ).start()
            return 0

        jax.lax.fori_loop(0, K, issue, 0)
        if chunked:
            nch, rem = divmod(K, CH)

            def wch(c, _):
                pltpu.make_async_copy(
                    x_ref.at[:CH], buf.at[:CH], sem).wait()
                return 0

            jax.lax.fori_loop(0, nch, wch, 0)
            for _ in range(rem):
                pltpu.make_async_copy(x_ref.at[0], buf.at[0], sem).wait()
        else:

            def w(k, _):
                pltpu.make_async_copy(x_ref.at[0], buf.at[0], sem).wait()
                return 0

            jax.lax.fori_loop(0, K, w, 0)
        o_ref[...] = jnp.full(o_ref.shape, buf[0, 0, 0], jnp.float32)

    def run_pipe(chunked):
        x = jnp.asarray(rng.random((VB, S, 128), dtype=np.float32))
        rows = jnp.asarray(rows2_np.reshape(-1))
        f = pl.pallas_call(
            functools.partial(pipe_kernel, chunked=chunked),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((8, 128), lambda i, *_: (0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((K, S, 128), jnp.float32),
                    pltpu.SemaphoreType.DMA,
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
        )
        o = f(rows, x)
        o.block_until_ready()
        reps = 12
        t0 = time.perf_counter()
        for _ in range(reps):
            o = f(rows, x)
        o.block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        print(
            f"{'chunked' if chunked else 'per-copy'} wait: "
            f"{dt * 1e3:.2f} ms/call  {dt / B * 1e6:.1f} us/block  "
            f"{dt / B / K * 1e9:.1f} ns/copy",
            flush=True,
        )
        return dt

    t_loop = run_pipe(chunked=False)
    t_chunk = run_pipe(chunked=True)
    print(f"chunked-wait speedup on DMA pipeline: {t_loop / t_chunk:.2f}x",
          flush=True)


if __name__ == "__main__":
    main()
