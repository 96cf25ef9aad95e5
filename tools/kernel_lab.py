#!/usr/bin/env python
"""Row-DMA kernel lab: hardware correctness + ns/row sweep.

Run on the real chip to validate ops/rowdma kernels post-compile and pick
block_rows / dtype:

    python tools/kernel_lab.py [--quick]

Each timed loop runs a donated-state chain and ends in a device->host fetch
of a scalar, which waits for the whole chain. One process holds the chip.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--vocab", type=int, default=1_000_000)
    p.add_argument("--rows", type=int, default=98304)
    p.add_argument("--dim", type=int, default=200)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.ops import rowdma

    S = -(-args.dim // rowdma.ROW_LANES)
    rng = np.random.default_rng(0)

    def fresh(dtype):
        t = rng.random((args.vocab, S, 128), dtype=np.float32)
        return jnp.asarray(t, dtype=dtype)

    rows_np = rng.integers(0, args.vocab, args.rows).astype(np.int32)
    rows = jnp.asarray(rows_np)
    uniq_np = rng.permutation(args.vocab)[: args.rows].astype(np.int32)
    uniq = jnp.asarray(uniq_np)

    # --- hardware correctness on small shapes first -----------------------
    small_t = fresh(jnp.float32)[:4096]
    small_rows = jnp.asarray(rng.integers(0, 4096, 1024).astype(np.int32))
    got = rowdma.gather_rows(small_t, small_rows, block_rows=256)
    want = small_t[small_rows]
    err = float(jnp.abs(got - want).max())
    print(f"gather correctness: max err {err}")
    assert err == 0.0

    small_uniq = jnp.asarray(
        np.concatenate([rng.permutation(4096)[:1000], np.full(24, 4096)]).astype(np.int32)
    )
    deltas = jnp.asarray(rng.random((1024, S, 128), dtype=np.float32))
    t2 = rowdma.scatter_add_rows(small_t + 0, small_uniq, deltas, block_rows=256)
    want2 = np.asarray(small_t)
    w = want2.copy()
    for r, d in zip(np.asarray(small_uniq), np.asarray(deltas)):
        if r < 4096:
            w[r] += d
    err2 = float(np.abs(np.asarray(t2) - w).max())
    print(f"scatter correctness: max err {err2}")
    assert err2 < 1e-5

    # --- throughput sweep -------------------------------------------------
    probe = jnp.zeros((8, 128), jnp.float32)

    def bench(name, fn, *operands, n=20):
        # tables ride as operands: a closed-over 4 GiB table would be baked
        # into the program as a constant, through the host's memory
        f = jax.jit(fn)
        _ = float(f(f(probe, *operands), *operands)[0, 0])  # compile, warm

        def timed():
            o = probe
            t0 = time.perf_counter()
            for _ in range(n):
                o = f(o, *operands)
            _ = float(o[0, 0])
            return (time.perf_counter() - t0) / n * 1e3

        # the faster of two passes: a process's first timed loop stalled on
        # the host for 0.4 s, once (first dispatch, 4 GiB table, on a v5e)
        dt = min(timed(), timed())
        print(f"{name}: {dt:.3f} ms  ({dt * 1e6 / args.rows:.1f} ns/row)")
        return dt

    dtypes = [jnp.float32] if args.quick else [jnp.float32, jnp.bfloat16]
    blocks = [512] if args.quick else [256, 512, 1024]
    for dtype in dtypes:
        table = fresh(dtype)
        for br in blocks:
            bench(
                f"gather {args.rows} rows dtype={dtype.__name__} R={br}",
                lambda p, table, br=br: p
                + rowdma.gather_rows(
                    table, (rows + p[0, 0].astype(jnp.int32)) % args.vocab,
                    block_rows=br,
                )[:8, 0, :].astype(jnp.float32),
                table,
            )
        # XLA reference (all rows summed: a slice of the result would let XLA
        # gather the eight rows it keeps and no others)
        bench(
            f"gather {args.rows} XLA dtype={dtype.__name__}",
            lambda p, table: p
            + table.at[(rows + p[0, 0].astype(jnp.int32)) % args.vocab]
            .get(mode="promise_in_bounds")
            .astype(jnp.float32).sum(axis=(0, 1))[None, :] * 1e-9,
            table,
        )

        deltas_big = jnp.asarray(
            rng.random((args.rows, S, 128), dtype=np.float32) * 1e-9, dtype=dtype
        )
        for br in blocks:
            def scat(p, table, deltas_big, br=br):
                t = rowdma.scatter_add_rows(table + p[0, 0] * 0, uniq, deltas_big, block_rows=br)
                return p + t[0, 0, :].astype(jnp.float32)[None, :]
            bench(f"scatter {args.rows} unique dtype={dtype.__name__} R={br}",
                  scat, table, deltas_big)

        def scat_xla(p, table, deltas_big):
            t = (table + p[0, 0] * 0).at[uniq].add(deltas_big, mode="drop")
            return p + t[0, 0, :].astype(jnp.float32)[None, :]
        bench(f"scatter {args.rows} XLA dtype={dtype.__name__}", scat_xla,
              table, deltas_big)


def resident_lab(argv=None):
    """Grouped vs resident vs dedup fused-SGNS sweep on the real chip.

    Times the center-major kernels on REAL skip-gram window batches over a
    zipf corpus (bench-shaped: 1M vocab, dim 200, window 5, pool 64) — the
    synthetic independent-draw workload this lab used first overstated the
    resident win (duplicate/pad structure differs from real windows; lesson
    recorded in docs/ARCHITECTURE.md). Shuffled batches feed grouped and
    resident; block-ordered batches (batch_stream_blocks) feed grouped and
    dedup. Prints words/sec per config — the tuning input for the bench's
    fused-resident/fused-dedup paths.

        python tools/kernel_lab.py --resident [--quick]
    """
    p = argparse.ArgumentParser()
    p.add_argument("--resident", action="store_true")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--vocab", type=int, default=1_000_000)
    p.add_argument("--dim", type=int, default=200)
    p.add_argument("--batch", type=int, default=8192)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.data.sampler import (
        batch_stream, batch_stream_blocks, skipgram_windows,
    )
    from swiftsnails_tpu.ops import rowdma
    from swiftsnails_tpu.ops.fused_sgns import (
        fused_sgns_dedup_resident_step,
        fused_sgns_dedup_step,
        fused_sgns_grouped_step,
        fused_sgns_resident_step,
    )

    interp = not rowdma.on_tpu()
    S = -(-args.dim // rowdma.ROW_LANES)
    W, PN, N = 5, 64, args.batch
    rng = np.random.default_rng(1)
    ranks = np.arange(1, args.vocab + 1, dtype=np.float64)
    w = 1.0 / ranks**1.05
    cdf = np.cumsum(w) / w.sum()

    def zipf(n):
        return np.searchsorted(cdf, rng.random(n)).astype(np.int32)

    ids = zipf(400_000)
    g_c, g_x = skipgram_windows(ids, W, rng)
    b_shuf = next(batch_stream(g_c, g_x, N, rng))
    # block-ordered batches per kernel block size (the sampler block must
    # equal the kernel's centers_per_block — the locality the dedup copy
    # list converts into fewer DMAs); --quick only consumes pc=256
    b_blk = {
        pc: next(batch_stream_blocks(g_c, g_x, N, rng, block=pc))
        for pc in ((256,) if args.quick else (128, 256, 512))
    }
    in_np = rng.random((args.vocab, S, 128), dtype=np.float32)

    def timeit(fn, name, batch, reps=12, pc=256, dtype=jnp.float32, **kw):
        cj = jnp.asarray(batch["centers"])
        xj = jnp.asarray(batch["contexts"])
        a = jnp.asarray(in_np, dtype)
        b = jnp.zeros((args.vocab, S, 128), dtype)
        pool = jnp.asarray(zipf((N // pc) * PN))
        try:
            a, b, loss = fn(a, b, cj, xj, pool, lr=0.025, lam=5 / PN,
                            window=W, centers_per_block=pc, pool_size=PN,
                            interpret=interp, **kw)
            _ = float(loss)
            t0 = time.perf_counter()
            for _i in range(reps):
                a, b, loss = fn(a, b, cj, xj, pool, lr=0.025,
                                lam=5 / PN, window=W, centers_per_block=pc,
                                pool_size=PN, interpret=interp, **kw)
            _ = float(loss)  # waits for the whole donated chain
            dt = (time.perf_counter() - t0) / reps
            print(f"{name}: {dt * 1e3:.2f} ms/substep  "
                  f"{N / dt:,.0f} words/sec", flush=True)
            return N / dt
        except Exception as e:
            print(f"{name} FAILED: {type(e).__name__}: {str(e)[:160]}",
                  flush=True)
            return 0.0

    results = {}
    results["dedup pc=256 u_cap=384"] = timeit(
        fused_sgns_dedup_step, "dedup pc=256 u_cap=384 (block-ordered)",
        b_blk[256], u_cap=384)
    results["grouped"] = timeit(
        fused_sgns_grouped_step, "grouped (shuffled)", b_shuf)
    if not args.quick:
        results["grouped block"] = timeit(
            fused_sgns_grouped_step, "grouped (block-ordered)", b_blk[256])
        # pc x u_cap sweep: u_cap must cover the block's distinct-row count
        # (~pc on block-ordered corpus) or overflow slots fall back to
        # per-slot hogwild copies; beyond that it only grows the one-hot
        # broadcast matmuls
        for pc, ucs in ((128, (128, 256)), (256, (256, 512, 1024)),
                        (512, (512, 768))):
            for uc in ucs:
                if pc == 256 and uc == 384:
                    continue  # measured above
                results[f"dedup pc={pc} u_cap={uc}"] = timeit(
                    fused_sgns_dedup_step,
                    f"dedup pc={pc} u_cap={uc} (block-ordered)",
                    b_blk[pc], pc=pc, u_cap=uc)
        for hot in (512, 2048):
            results[f"resident hot={hot}"] = timeit(
                fused_sgns_resident_step, f"resident hot={hot} (shuffled)",
                b_shuf, hot_rows=hot)
        # composed: head resident + cold dedup (u_cap >= hot required)
        for uc, hot in ((384, 256), (512, 512), (1024, 1024)):
            results[f"dedup+res u={uc} hot={hot}"] = timeit(
                fused_sgns_dedup_resident_step,
                f"dedup+res pc=256 u_cap={uc} hot={hot} (block-ordered)",
                b_blk[256], u_cap=uc, hot_rows=hot)
        # r5: three kernels with 3x different copies/pair measured within 7%
        # (BENCH r5 run 1) — the bound is per-block fixed cost, not copy
        # count. Larger blocks amortize it; bf16 halves scratch bytes.
        results["grouped pc=512"] = timeit(
            fused_sgns_grouped_step, "grouped pc=512 (shuffled)", b_shuf,
            pc=512)
        for hot in (512, 2048):
            results[f"resident pc=512 hot={hot}"] = timeit(
                fused_sgns_resident_step,
                f"resident pc=512 hot={hot} (shuffled)", b_shuf, pc=512,
                hot_rows=hot)
        for uc, hot in ((768, 512), (1024, 1024)):
            results[f"dedup+res pc=512 u={uc} hot={hot}"] = timeit(
                fused_sgns_dedup_resident_step,
                f"dedup+res pc=512 u_cap={uc} hot={hot} (block-ordered)",
                b_blk[512], pc=512, u_cap=uc, hot_rows=hot)
        for nm, fn2, batch2, kw in (
            ("grouped", fused_sgns_grouped_step, b_shuf, {}),
            ("resident hot=2048", fused_sgns_resident_step, b_shuf,
             {"hot_rows": 2048}),
            ("dedup+res u=512 hot=512", fused_sgns_dedup_resident_step,
             b_blk[256], {"u_cap": 512, "hot_rows": 512}),
        ):
            results[f"{nm} bf16"] = timeit(
                fn2, f"{nm} bf16", batch2, dtype=jnp.bfloat16, **kw)
    best = max(results, key=results.get)
    print(f"best: {best} ({results[best]:,.0f} words/sec)")


def ctr_lab(argv=None):
    """CTR small-row plane vs the 2-D XLA plane on the real chip.

    Measures pull+push rows/sec at the Criteo W&D shape (table_dim 17,
    AdaGrad) on both planes, plus the fused AdaGrad RMW kernel against the
    two-phase XLA scatter_update — the VERDICT r2 "no CTR number exists"
    gap. Run: ``python tools/kernel_lab.py --ctr [--quick]``
    """
    p = argparse.ArgumentParser()
    p.add_argument("--ctr", action="store_true")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--capacity", type=int, default=1 << 20)
    p.add_argument("--dim", type=int, default=17)
    p.add_argument("--rows", type=int, default=131072)  # B=8192 x F=16
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.parallel.access import AdaGradAccess
    from swiftsnails_tpu.parallel.store import (
        TableState,
        create_packed_small_table,
        create_table,
        pull,
        pull_packed_small,
        push,
        push_packed_small,
        small_group,
    )

    cap, dim, n = args.capacity, args.dim, args.rows
    access = AdaGradAccess()
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, cap, n).astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32) * 1e-3)
    g = small_group(dim)
    print(f"config: capacity={cap:,} dim={dim} rows/step={n:,} "
          f"(group={g} rows/tile, {128 // g} lanes each)")

    reps = 5 if args.quick else 15

    def timeit(name, make_state, step):
        state = make_state()
        state, probe = step(state)
        _ = float(probe)  # waits for the device
        t0 = time.perf_counter()
        for _i in range(reps):
            state, probe = step(state)
        _ = float(probe)
        dt = (time.perf_counter() - t0) / reps
        print(f"{name}: {dt * 1e3:.2f} ms  ({dt * 1e9 / n:.1f} ns/row, "
              f"{n / dt:,.0f} rows/sec)")
        return dt

    def small_state():
        return create_packed_small_table(cap, dim, access, seed=0)

    def small_step(state):
        vals = pull_packed_small(state, rows, dim)
        state, _ = push_packed_small(
            state, rows, grads + vals * 1e-6, access, 0.01, dim)
        return state, state.table[0, 0, 0]

    def dense_state():
        return create_table(cap, dim, access, seed=0)

    def dense_step(state):
        vals = pull(state, rows)
        state = push(state, rows, grads + vals * 1e-6, access, 0.01)
        return state, state.table[0, 0]

    t_small = timeit("small-plane pull+push (fused AdaGrad)", small_state,
                     jax.jit(small_step, donate_argnums=(0,)))
    t_dense = timeit("2-D XLA plane pull+push (two-phase AdaGrad)",
                     dense_state, jax.jit(dense_step, donate_argnums=(0,)))
    print(f"small-row plane speedup: {t_dense / t_small:.2f}x")

    # mesh path: the same plane through the collective twins (shard_map,
    # tile-granular ownership). On the one real chip this is a (1, 1) mesh —
    # it measures the collective plane's dispatch/overhead envelope; the
    # cross-shard traffic itself needs real ICI (same caveat as --push).
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from swiftsnails_tpu.parallel.transfer import (
        pull_collective_packed_small,
        push_collective_packed_small,
    )

    n_dev = len(jax.devices())
    model = max(d for d in (4, 2, 1) if n_dev % d == 0 and (n_dev // d) > 0)
    mesh = make_mesh({DATA_AXIS: n_dev // model, MODEL_AXIS: model})

    def mesh_state():
        return create_packed_small_table(cap, dim, access, mesh=mesh, seed=0)

    def mesh_step(state):
        vals = pull_collective_packed_small(mesh, state, rows, dim)
        state = push_collective_packed_small(
            mesh, state, rows, grads + vals * 1e-6, access, 0.01, dim)
        return state, state.table[0, 0, 0]

    t_mesh = timeit(
        f"mesh small-plane pull+push (data={n_dev // model}, model={model})",
        mesh_state, jax.jit(mesh_step, donate_argnums=(0,)))
    print(f"mesh-path overhead vs single-device plane: {t_mesh / t_small:.2f}x")


def _compiled_collective_bytes(fn, args, op_pattern):
    """Bytes moved by collectives matching ``op_pattern`` in the optimized
    HLO of ``jit(fn)(*args)`` — the hardware-transferable traffic number.

    Single implementation: ``swiftsnails_tpu.telemetry.audit`` (imported
    lazily — the labs pin the platform before jax loads). The audit parser
    recognizes async collective pairs (``all-gather-start``/``-done``) that
    the old f32-anchored regex here silently missed (ADVICE r5), so a
    backend that emits async collectives no longer reports 0 bytes.
    """
    from swiftsnails_tpu.telemetry.audit import compiled_collective_bytes

    return compiled_collective_bytes(fn, args, op_pattern)


def push_lab():
    """Gather vs owner-bucketed push on the virtual CPU mesh.

    Reports (a) compiled all-gather bytes from the optimized HLO — the
    deterministic traffic measurement (ICI volume on real hardware scales
    the same way) — and (b) wall-clock step time on the 8-virtual-CPU mesh
    (directional only: CPU "collectives" are memcpys sharing one host).

        python tools/kernel_lab.py --push   # self-pins the 8-vCPU mesh
    """
    from swiftsnails_tpu.utils.platform_pin import pin_cpu

    pin_cpu(8)  # before jax initializes: needs a fresh process

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.parallel import SgdAccess, create_table, make_mesh
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, batch_sharding
    from swiftsnails_tpu.parallel.transfer import (
        push_collective,
        push_collective_bucketed,
    )

    cap, dim, b = 1 << 16, 64, 8192
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    access = SgdAccess()
    state = create_table(cap, dim, access, mesh=mesh, seed=0)
    rng = np.random.default_rng(0)
    bs = batch_sharding(mesh)
    rows = jax.device_put(rng.integers(0, cap, b).astype(np.int32), bs)
    grads = jax.device_put(rng.normal(size=(b, dim)).astype(np.float32), bs)

    def ag_bytes(fn):
        return _compiled_collective_bytes(fn, (state, rows, grads),
                                          "all-gather")

    def timeit(fn, n=30):
        f = jax.jit(fn)
        out = f(state, rows, grads)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(state, rows, grads)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    gather_fn = lambda s, r, g: push_collective(mesh, s, r, g, access, 0.1).table
    bucket_fn = lambda s, r, g: push_collective_bucketed(mesh, s, r, g, access, 0.1)[0].table
    gb, bb = ag_bytes(gather_fn), ag_bytes(bucket_fn)
    gt, bt = timeit(gather_fn), timeit(bucket_fn)
    print(f"push all-gather bytes: gather={gb:,}  bucketed={bb:,}  "
          f"({gb / max(bb, 1):.2f}x less traffic)")
    print(f"push step time (8-vCPU mesh): gather={gt:.2f} ms  bucketed={bt:.2f} ms")
    print("NOTE: on one host the 'collectives' are free memcpys, so the vCPU")
    print("time shows ONLY the bucketed path's added dedup/compaction sorts;")
    print("on real multi-chip the 2x ICI-traffic cut is what the all_gather")
    print("pays for. The traffic number is the hardware-transferable result.")


def dedup_traffic_lab():
    """Plain vs dedup'd collective packed plane: compiled collective bytes.

    The mesh dedup plane (transfer.pull/push_collective_packed_dedup) claims
    a large ICI-traffic cut on zipf window batches; this measures it the
    hardware-independent way (like --push): psum + all-gather bytes in the
    optimized HLO, on rows drawn from a REAL block-ordered window batch so
    the duplicate rate is the production one.

        python tools/kernel_lab.py --dedup-traffic   # self-pins 8-vCPU mesh
    """
    from swiftsnails_tpu.utils.platform_pin import pin_cpu

    pin_cpu(8)  # before jax initializes: needs a fresh process

    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.data import native as nat
    from swiftsnails_tpu.parallel import SgdAccess, make_mesh
    from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, batch_sharding
    from swiftsnails_tpu.parallel.store import create_packed_table
    from swiftsnails_tpu.parallel.transfer import (
        pull_collective_packed,
        pull_collective_packed_dedup,
        push_collective_packed,
        push_collective_packed_dedup,
    )

    cap, dim, n_batch, u_cap = 1 << 16, 200, 8192, 1024
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    access = SgdAccess()
    state = create_packed_table(cap, dim, access, mesh=mesh, seed=0)

    # production-shaped rows: context ids of a block-ordered zipf window
    # batch (adjacent windows overlap -> the duplicate rate dedup exploits)
    rng = np.random.default_rng(0)
    ranks = rng.zipf(1.2, size=200_000).astype(np.int64)
    ids = np.minimum(ranks - 1, cap - 1).astype(np.int32)
    wp = nat.WindowPrefetcher(*nat.skipgram_windows(ids, 5, seed=1),
                              batch_size=4096, block=256, epochs=1, seed=1)
    batch = next(iter(wp))
    wp.close()
    ctx = batch["contexts"].reshape(-1)
    ctx = ctx[ctx >= 0][:n_batch]
    rows_np = np.resize(ctx, n_batch).astype(np.int32)
    uniq_frac = len(np.unique(rows_np)) / n_batch
    bs = batch_sharding(mesh)
    rows = jax.device_put(rows_np, bs)
    grads = jax.device_put(
        rng.normal(size=(n_batch,) + state.table.shape[1:]).astype(np.float32),
        bs)

    def coll_bytes(fn, *args):
        return _compiled_collective_bytes(
            fn, args, "all-gather|all-reduce|reduce-scatter|all-to-all")

    plain_pull = lambda s, r: pull_collective_packed(mesh, s, r)
    plain_push = lambda s, r, g: push_collective_packed(
        mesh, s, r, g, access, 0.1).table
    pp = coll_bytes(plain_pull, state, rows)
    ps = coll_bytes(plain_push, state, rows, grads)
    print(f"window-batch rows: n={n_batch}, distinct={uniq_frac:.1%}")
    print(f"plain collective bytes: pull={pp:,}  push={ps:,}")
    for uc in (u_cap, 512):
        dedup_pull = lambda s, r: pull_collective_packed_dedup(
            mesh, s, r, uc)[0]
        dedup_push = lambda s, r, g: push_collective_packed_dedup(
            mesh, s, r, g, access, 0.1, uc)[0].table
        dp = coll_bytes(dedup_pull, state, rows)
        ds = coll_bytes(dedup_push, state, rows, grads)
        # the compiled cut is STATIC (n_local/u_cap — collective shapes
        # cannot depend on row values); what the batch content decides is
        # whether the static cap LOSES anything. Assert it does not: the
        # production-duplicate-rate batch must fit the unique list with
        # zero overflow, otherwise the "cut" drops gradients.
        ovf = int(pull_collective_packed_dedup(mesh, state, rows, uc)[2])
        assert ovf == 0, f"u_cap={uc} overflows ({ovf}) on this batch"
        print(f"dedup u_cap={uc}: pull={dp:,} ({pp / max(dp, 1):.2f}x less)  "
              f"push={ds:,} ({ps / max(ds, 1):.2f}x less)  overflow=0 ok")
    print("NOTE: the cut is the static n_local/u_cap shape ratio; the window")
    print("batch's role is proving zero unique-list overflow at that cap.")
    print("Compiled psum/all-gather volume transfers to hardware (ICI volume")
    print("scales the same way); vCPU wall time does not.")


if __name__ == "__main__":
    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if "--push" in sys.argv:
        push_lab()
    elif "--dedup-traffic" in sys.argv:
        dedup_traffic_lab()
    elif "--resident" in sys.argv:
        resident_lab(sys.argv[1:])
    elif "--ctr" in sys.argv:
        ctr_lab(sys.argv[1:])
    else:
        main(sys.argv[1:])
